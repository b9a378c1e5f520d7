"""Failure recovery: resumable streaming fits via periodic checkpoints.

Spark recovers mid-job failures by lineage recompute + executor relaunch
(SURVEY.md §5 "Failure/elastic"; reconstructed, mount empty). The TPU-native
model has no lineage — recomputation would mean replaying the whole stream —
so recovery is CHECKPOINT-based (§2b "Fault tolerance" row): long-running
stream fits snapshot (step counter, optimizer state, model params) every
``every_steps`` device steps, and a restarted process resumes from the last
snapshot, fast-forwarding the input stream to the recorded position.

Determinism note: resuming replays the exact same chunk sequence from the
recorded step, so an interrupted-and-resumed fit produces bit-identical
parameters to an uninterrupted one (asserted by the kill-and-resume test —
the fault-injection strategy this framework uses in place of Spark's
lineage recompute).
"""

from __future__ import annotations

import os
import pickle
import tempfile

import jax
import numpy as np


#: estimator parameters that are gone, but that a snapshot written before
#: their removal still carries under ``meta['params']``. Each chose among
#: lowerings of the same arithmetic and never the result, so a saved value
#: says nothing about the configuration: ``load`` drops it before it
#: compares — THE one place, which every resume path (``fit_stream``, the
#: online trainer, the multi-host wrapper) goes through.
RETIRED_PARAMS = ("emb_update",)


def _without_retired(meta):
    if not (isinstance(meta, dict) and isinstance(meta.get("params"), dict)):
        return meta
    return {**meta, "params": {k: v for k, v in meta["params"].items()
                               if k not in RETIRED_PARAMS}}


class StreamCheckpointer:
    """Atomic pickle snapshots of (step, pytree-of-arrays) training state."""

    def __init__(self, path: str, every_steps: int = 100):
        self.path = path
        self.every_steps = max(1, int(every_steps))

    def maybe_save(self, step: int, state, meta=None) -> bool:
        if step % self.every_steps != 0:
            return False
        self.save(step, state, meta)
        return True

    def save(self, step: int, state, meta=None) -> None:
        host_state = jax.tree.map(
            lambda x: np.asarray(x) if isinstance(x, jax.Array) else x, state
        )
        d = os.path.dirname(os.path.abspath(self.path)) or "."
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=d, suffix=".ckpt.tmp")
        try:
            with os.fdopen(fd, "wb") as f:
                pickle.dump(
                    {"step": int(step), "state": host_state, "meta": meta}, f
                )
            os.replace(tmp, self.path)  # atomic: a crash never truncates
        except BaseException:
            if os.path.exists(tmp):
                os.unlink(tmp)
            raise

    def delete(self) -> None:
        """Remove the snapshot (called by fits on successful completion)."""
        if os.path.exists(self.path):
            os.unlink(self.path)

    def load(self, expect_meta=None):
        """(step, state) of the last snapshot, or (0, None) if none exists.

        ``expect_meta``: the caller's config fingerprint — resuming a run
        whose snapshot was written under DIFFERENT hyper-parameters/shapes
        would silently train a corrupted model, so a mismatch raises."""
        if not os.path.exists(self.path):
            return 0, None
        with open(self.path, "rb") as f:
            blob = pickle.load(f)
        saved_meta = _without_retired(blob.get("meta"))
        if expect_meta is not None and saved_meta is not None                 and saved_meta != expect_meta:
            raise ValueError(
                f"checkpoint {self.path!r} was written with a different "
                f"configuration: saved={saved_meta!r} vs current={expect_meta!r}. "
                "Delete the checkpoint or restore the original settings."
            )
        return blob["step"], blob["state"]
