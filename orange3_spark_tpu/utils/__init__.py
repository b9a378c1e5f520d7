"""Leaf helpers. Imports nothing from the package: `utils.knobs` is imported
by nearly every module, so anything pulled in here (the model checkpointing
in `utils.checkpoint` reaches workflow → widgets → every model) closes an
import cycle. Import the submodule you need."""
