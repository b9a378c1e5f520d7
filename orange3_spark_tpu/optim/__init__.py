"""Optimizer subsystem — touched-row-only (sparse) updates for the hashed
embedding hot path, plus their dense twins. See ``optim/sparse.py`` and
``docs/optim.md``."""

from orange3_spark_tpu.optim.sparse import (  # noqa: F401
    ADAGRAD_EPS,
    DENSE_UPDATES,
    FTRL_BETA,
    OPTIM_UPDATES,
    SPARSE_UPDATES,
    adopt_optim_state,
    apply_rule,
    dense_update,
    finalize_lazy_decay,
    init_optim_state,
    is_sparse_update,
    note_slot_blocks,
    note_sorts,
    occurrence_dead,
    optim_kind,
    plan_slots,
    slot_blocks,
    sort_keys,
    sort_keys_bytes,
    sort_slots,
    resolve_optim_update,
    resolve_sparse_lowering,
    sparse_embedding_update,
    touched_rows,
)
