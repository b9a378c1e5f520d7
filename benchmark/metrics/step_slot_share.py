"""The share of the sparse step's static slot bound that its steps really
gathered, ran through the rule and wrote back: blocks of slots run over
blocks possible, from the program's own counter
(``otpu_sparse_slot_blocks_total`` of ``obs.registry``, fed once per
finished fit from a count kept on the device), over every fit of the
process — the warm job and the window's jobs run the same chunks. 1.0 is
a step that walks the whole bound whatever its chunk touched; a program
without the counter reads ``None``."""


def read(run: dict):
    try:
        from orange3_spark_tpu.obs.registry import REGISTRY
    except ImportError:
        return None
    blocks = REGISTRY.get("otpu_sparse_slot_blocks_total")
    if blocks is None:
        return None
    possible = blocks.value(which="possible")
    return blocks.value(which="run") / possible if possible else None
