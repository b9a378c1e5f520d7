"""Hashed-sparse linear models — the Criteo-scale categorical path.

BASELINE config 2 (the headline metric) is Criteo click-through: 13 dense
numerics + 26 categoricals hashed to millions of dimensions. A dense design
matrix is unrepresentable; MLlib fits it as a SparseVector pipeline
(FeatureHasher -> LogisticRegression over treeAggregate; SURVEY.md §2b rows
"Distributed dataframe"/"LogReg"; reconstructed, mount empty).

TPU-native redesign — fixed-nnz-per-row, not CSR:

* every row has EXACTLY n_cat categorical slots (Criteo's shape), so the
  sparse structure is two static-shape arrays: raw codes [N, C] (hashed to
  indices on device, ops/hashing.py) and an embedding table [n_dims, k].
  Static shapes mean ONE compiled step for the whole stream — CSR's ragged
  rows would force re-compilation or host-side bucketing.
* the forward is an embedding gather ``take(emb, idx)`` + a dense matmul for
  the numeric block; the backward is XLA's scatter-add. No SpMV kernel to
  hand-write — gather/scatter are native TPU ops.
* binary targets use the k=1 sigmoid formulation (``binary_logistic`` in
  models/_linear.py) — identical optimum to 2-column softmax at HALF the
  gather/scatter bytes, the step's dominant cost (measured 3.3x faster on a
  v5e chip).
* the chunk arrives as ONE [N, 1+n_dense+n_cat] f32 array straight from
  fastcsv — label column INCLUDED (``label_in_chunk``) — so the host does
  zero per-cell work, zero column splits, and the transfer is a single DMA;
  label/dense/categorical split happens inside the jit. Padding rows are
  masked by a traced ``n_valid`` scalar instead of a shipped weight vector.
* epoch overlap: parse+DMA of chunk t+1 runs on a prefetch thread while the
  device runs step t (io/streaming.py ``prefetch_map``).
* ``cache_device=True`` retains each device-put chunk in HBM and replays it
  for epochs 2+, exactly Spark's ``dataset.persist()`` before an iterative
  fit (MLlib LogisticRegression caches its input RDD): later epochs run at
  pure step speed with ZERO host involvement. Configs that exceed
  ``cache_device_bytes`` (the 1B-row regime) degrade to pure streaming for
  EVERY epoch — a partial replay would reorder/double-count chunks, and a
  CSV source cannot seek past its cached prefix, so the host parse (the
  actual bottleneck) would be paid anyway.
* data parallelism: rows sharded P('data'); the embedding table is
  replicated (4 MB at 2^20 x 1) and its gradient all-reduces over ICI by
  GSPMD — treeAggregate without the shuffle. A 'model'-axis sharded table
  variant lives in ``emb_sharding`` (factor tables wider than HBM shard
  P('model', None)).
"""

from __future__ import annotations

import dataclasses
import itertools
import time
from functools import partial, reduce
from typing import Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np
import optax

from orange3_spark_tpu.core.session import TpuSession
from orange3_spark_tpu.exec.donate import donating_jit
from orange3_spark_tpu.exec.pipeline import PipelineStats
from orange3_spark_tpu.io.codec import (
    BF16, bit_width, pack_rows_np, resolve_cache_dtype, unpack_rows,
)
from orange3_spark_tpu.io.multihost import put_sharded
from orange3_spark_tpu.io.native import hash_pack_rows
from orange3_spark_tpu.models._linear import EPS_TOTAL_WEIGHT, per_row_loss
from orange3_spark_tpu.models.base import Estimator, Model, Params
from orange3_spark_tpu.ops.hashing import (
    column_salts, hash_columns, hash_columns_np,
)
from orange3_spark_tpu.optim.sparse import (
    adopt_optim_state, dense_update, finalize_lazy_decay,
    init_optim_state, is_sparse_update, note_slot_blocks, note_sorts,
    optim_kind, resolve_optim_update, resolve_sparse_lowering, slot_blocks,
    sort_keys, sort_keys_bytes, sort_slots, sparse_embedding_update,
    touched_rows,
)
from orange3_spark_tpu.obs import prof
from orange3_spark_tpu.obs.registry import REGISTRY
from orange3_spark_tpu.obs.report import RunReport
from orange3_spark_tpu.obs.trace import span, span_iter, stage, traced
from orange3_spark_tpu.obs.trace import refreshed_enabled as obs_enabled
from orange3_spark_tpu.resilience.numerics import check_finite_training
from orange3_spark_tpu.utils.dispatch import bound_dispatch
from orange3_spark_tpu.utils.profiling import count_dispatch

# unit-lr adam; the traced lr scales its updates (see io/streaming.py)
_ADAM_UNIT = optax.adam(1.0)

#: per-process ledger-entry numbering for hashed fits (obs/prof.py)
_FIT_LEDGER_SEQ = itertools.count()

_M_ENCODE_CHUNKS = REGISTRY.counter(
    "otpu_encode_chunks_total",
    "chunks encoded under the 'packed' cache codec, by what hashed and "
    "bit-packed their categoricals: how=native (one pass of the fastcsv "
    "library over the parsed rows) | numpy (the library did not build or "
    "load here: hash_columns_np + pack_rows_np, the same bytes, ~5x the "
    "host seconds)")


@dataclasses.dataclass(frozen=True)
class HashedLinearParams(Params):
    n_dims: int = 1 << 20        # hashed feature space (power of two)
    n_dense: int = 13            # leading numeric columns (Criteo I1-I13)
    n_cat: int = 26              # trailing categorical columns (C1-C26)
    loss: str = "logistic"       # 'logistic' | 'squared' | 'squared_hinge'
    n_classes: int = 2
    epochs: int = 1
    step_size: float = 0.02
    reg_param: float = 0.0       # L2 on emb + coef
    chunk_rows: int = 1 << 18
    threshold: float = 0.5
    seed: int = 0
    compute_dtype: str = "float32"
    label_in_chunk: bool = False  # chunks carry the label as column 0
    prefetch_depth: int = 2       # host->device pipeline depth (0 disables)
    # Optimizer rule + lowering (optim/ subsystem, docs/optim.md):
    # 'adam' is the legacy dense optax path (in-loss L2, full-table moment
    # sweeps every step). The sparse_* rules update ONLY the rows a step
    # touches — per-row f32 slots, lazy decoupled weight decay via
    # last-seen timestamps — and each has a dense_* twin (same math, full
    # sweeps), chosen by name: the reference of the parity tests.
    # Note: the non-adam rules treat reg_param as DECOUPLED weight decay
    # (FTRL: its closed-form L2), not an in-loss term, and report the
    # pure data loss.
    optim_update: str = "adam"   # 'adam' | '{dense,sparse}_{sgd,adagrad,ftrl}'
    # Dedup lowering for sparse_* rules. There is one, 'sort' (argsort in
    # the jit, no per-chunk aux memory), on every backend; the field is
    # kept for the callers that name it (ROADMAP D2a).
    sparse_lowering: str = "auto"   # 'auto' | 'sort'
    l1_param: float = 0.0        # FTRL-proximal l1 (sparse/dense ftrl only)
    fused_replay: bool = True    # cache replay epochs as scan program(s)
    # Granularity of the fused replay dispatches: 'all' lowers epochs 2+
    # to ONE scan (n_epochs-1 trip count — cheapest, one dispatch);
    # 'epoch' dispatches one n_epochs=1 scan PER epoch (n_epochs-1
    # dispatches over the same chunk stack) — the grain at which epoch-
    # boundary checkpoints can land, at n_epochs-1 dispatches instead of
    # n_chunks*(n_epochs-1) per-chunk ones. The default 'all' ran the
    # published Criteo shape on a v5e chip without a fault (PR 22,
    # chip_smoke.py; CHANGES.md).
    replay_granularity: str = "all"   # 'all' | 'epoch'
    # With replay_granularity='epoch': fold K epochs into each scan
    # dispatch — ceil(n_replay/K) dispatches instead of n_replay, the
    # amortization dial between 'epoch' (K=1, most dispatches) and
    # 'all' (one program). Step sequence
    # is identical at every K, and checkpoint cadence is preserved (groups
    # clamp at snapshot boundaries — io/streaming.run_epoch_replay).
    epochs_per_dispatch: int = 1
    # Defer epoch-1 training into the replay program: the streaming pass
    # becomes pure ingest (parse -> pad -> DMA -> cache/spill, NO step
    # dispatches) and the replay then runs ``epochs`` full passes instead
    # of ``epochs - 1``. The step sequence is IDENTICAL (epoch 1's
    # per-chunk steps visit the same chunks in the same order the first
    # replay pass does), so results are bit-identical to the default —
    # pinned by tests/test_hashed_defer.py. What it buys: epoch 1 sheds
    # n_chunks step dispatches and overlaps nothing but DMA, and no
    # per-chunk step program is ever compiled or run. Requires
    # cache_device. Checkpointing composes ONLY with
    # replay_granularity='epoch' (snapshots land at epoch boundaries
    # between the per-epoch replay dispatches; resume re-ingests the
    # cache step-free and fast-forwards checkpointed epochs — see
    # tests/test_hashed_defer.py kill-and-resume); with granularity
    # 'all' a checkpointered fit silently keeps the default schedule,
    # whose per-chunk dispatches give step-granular snapshots.
    defer_epoch1: bool = False
    # Crash-resumable fits (docs/resilience.md): with a checkpointer
    # passed to fit_stream, K > 0 switches the snapshot cadence from
    # per-step (checkpointer.every_steps) to EPOCH BOUNDARIES every K
    # epochs — atomic write-to-temp + rename, so a fit SIGKILLed
    # mid-epoch resumes at the last boundary and replays the identical
    # step sequence. Inert under OTPU_RESILIENCE=0 and without a
    # checkpointer (same contract as StreamingLinearParams).
    checkpoint_every_epochs: int = 0
    # value-weighted sparse rows (MLlib SparseVector semantics): chunks
    # carry n_cat (index, value) PAIRS — [label?, idx..., val...] — and the
    # forward is sum(emb[hash(idx)] * val), io/libsvm.py's fixed-nnz
    # layout. Requires n_dense == 0; -1 index padding is inert because its
    # value is 0 (zero forward contribution, zero gradient).
    value_weighted: bool = False
    # Missing-value semantics (real Criteo TSV ships EMPTY cells in both
    # dense and categorical columns; fastcsv parses empty dense -> NaN and
    # empty marked-categorical -> crc32("")==0, the reserved code):
    # 'zero' (default) imputes NaN dense cells to 0 and NaN categorical
    # cells to the reserved code 0 INSIDE the jit (fused, free); 'keep'
    # passes NaN through for an upstream imputer to handle — a NaN
    # reaching the step then poisons the loss, visibly.
    missing: str = "zero"        # 'zero' | 'keep'
    # Cache/spill storage precision (io/codec.py; resolved ONCE at fit
    # entry via resolve_chunk_codec, OTPU_CACHE_DTYPE kill-switch —
    # '=f32' restores the legacy cache exactly):
    #   'f32'    legacy padded-f32 chunks, bit-for-bit.
    #   'bf16'   dense numeric block stored bfloat16 (lossy, bounded:
    #            RTNE, rel. err <= 2^-8); label stored u8 where exact
    #            (classification losses); categorical codes stay f32.
    #   'packed' bf16 PLUS lossless integer packing: categorical columns
    #            pre-hash on the prefetch thread (the host hash twin is
    #            pinned bit-identical to the device's) and store at
    #            log2(n_dims) bits. Decode
    #            is static shifts/masks INSIDE the step — HBM, disk spill
    #            and h2d DMA all move ~2x fewer bytes, and the cache/
    #            fusion-gate capacity roughly doubles.
    #   'auto'   the session policy knob (TpuSession.default_cache_dtype,
    #            'packed').
    # value_weighted fits keep 'f32' (explicit (idx, val) pairs carry
    # their own -1/0 padding the codec must not re-encode), and 'packed'
    # degrades to 'bf16' under missing='keep' (NaN codes must reach the
    # in-jit hash to poison visibly — pre-hashing would hide them).
    cache_dtype: str = "f32"     # 'f32' | 'bf16' | 'packed' | 'auto'


def _effective_k(p: HashedLinearParams) -> int:
    """Width of theta's class dimension: binary logistic collapses to k=1
    (sigmoid) — half the embedding traffic of the 2-column softmax."""
    if p.loss != "logistic":
        return 1
    return 1 if p.n_classes == 2 else p.n_classes


def _impute_flag(p: HashedLinearParams) -> bool:
    """Static impute flag for the jitted functions; value-weighted rows
    carry explicit (index, value) pairs with their own -1/0 padding
    convention, so 'zero' imputation only applies to the dense+categorical
    layout."""
    if p.missing not in ("zero", "keep"):
        raise ValueError(f"missing must be 'zero' or 'keep', got {p.missing!r}")
    return p.missing == "zero" and not p.value_weighted


def _row_loss_kind(p: HashedLinearParams) -> str:
    if p.loss == "logistic" and p.n_classes == 2:
        return "binary_logistic"
    return p.loss


def _hashed_logits(theta, dense, idx, compute_dtype, vals=None):
    """One [N, C] gather of the table, summed over the columns, plus the
    dense block's matmul; autodiff (the adam and ``dense_*`` paths) emits
    one fused scatter. ``vals`` (value-weighted sparse mode): per-pair
    multipliers — the forward becomes sum(emb[idx] * val), MLlib
    SparseVector semantics."""
    emb = theta["emb"].astype(compute_dtype)
    emb_rows = jnp.take(emb, idx, axis=0)
    if vals is not None:
        emb_rows = emb_rows * vals[:, :, None]
    logits = jnp.sum(emb_rows, axis=1, dtype=jnp.float32)        # [N, k]
    return _add_dense_logits(logits, theta, dense, compute_dtype)


def _sum_columns(occ):
    """``occ[C, N, k]`` summed over its columns in float32, in a fixed
    order written out here: neighbouring columns in pairs, then the pairs
    one after the other — ``((c0 + c1) + (c2 + c3)) + (c4 + c5) …``. It is
    the order in which the chip's compiler adds the 26 columns of
    ``_hashed_logits``' ``jnp.sum(axis=1)`` (a v5e, jax 0.9.0: of 37
    orders tried against 262,144 of its logits the one that gives every
    bit, PERF.md §6 PR 36), so a sparse fit keeps the bits it had while
    its forward still gathered; and written out, it is this program's
    order and not a layout's."""
    x = occ.astype(jnp.float32)
    odd = x.shape[0] % 2
    pairs = [x[c] + x[c + 1] for c in range(0, x.shape[0] - odd, 2)]
    return reduce(jnp.add, pairs + [x[-1]] * odd)


def _touched_logits(theta, dense, keys, shape, compute_dtype, vals=None):
    """The sparse step's forward: ``_hashed_logits``' values on every
    live row of a chunk whose ``optim.sparse.sort_keys`` are ``keys``
    (``shape`` = the chunk's ``(N, C)``), from ONE read of each distinct
    table row (``optim.sparse.touched_rows``) instead of a gather at the
    ``N x C`` occurrences. Returns ``(rows, logits)``: the rows read, for
    the update that follows, and the ``[N, k]`` logits. Not
    differentiable through the table — the ``sparse_*`` rules never ask."""
    rows, occ = touched_rows(theta["emb"], keys, *shape)
    occ = occ.astype(compute_dtype)                   # [C, N, k]
    if vals is not None:
        occ = occ * vals.T[:, :, None]
    return rows, _add_dense_logits(_sum_columns(occ), theta, dense,
                                   compute_dtype)


def _add_dense_logits(logits, theta, dense, compute_dtype):
    """The table's share of the logits plus the dense block's matmul and
    the intercept."""
    if theta["coef"].shape[0]:
        logits = logits + jnp.dot(
            dense.astype(compute_dtype),
            theta["coef"].astype(compute_dtype),
            preferred_element_type=jnp.float32,
        )
    return logits + theta["intercept"]


def _split_chunk(Xall, n_valid, y, w, *, label_in_chunk: bool, n_dense: int,
                 value_weighted: bool = False, impute_missing: bool = False):
    """In-jit chunk anatomy. label_in_chunk: column 0 is the label and the
    row mask is iota < n_valid (no y/w host vectors shipped at all).
    value_weighted: the feature block is C (index, value) PAIRS —
    [idx..., val...] — instead of dense+categorical columns.
    impute_missing: NaN dense cells -> 0, NaN categorical cells -> the
    reserved code 0 (== crc32 of the empty string, what fastcsv emits for
    an empty marked-categorical cell) — Criteo-TSV missing-cell semantics,
    fused into the step for free."""
    if label_in_chunk:
        yv = Xall[:, 0]
        feat = Xall[:, 1:]
        wv = (jnp.arange(Xall.shape[0], dtype=jnp.int32)
              < n_valid).astype(jnp.float32)
    else:
        yv = y
        feat = Xall
        wv = w
    if value_weighted:
        C = feat.shape[1] // 2
        return yv, feat[:, :0], feat[:, :C], wv, feat[:, C:]
    dense, cats = feat[:, :n_dense], feat[:, n_dense:]
    if impute_missing:
        dense = jnp.where(jnp.isnan(dense), 0.0, dense)
        cats = jnp.where(jnp.isnan(cats), 0.0, cats)
    return yv, dense, cats, wv, None


def _chunk_fields(Xall, n_valid, y, w, salts, *, n_dims: int, n_dense: int,
                  label_in_chunk: bool, value_weighted: bool,
                  impute_missing: bool, codec):
    """One cached chunk as the step reads it: ``(yv, dense, cats, idx, wv,
    vals)``, ``idx`` the hashed table rows [N, C]. ``codec`` None is the
    legacy f32 chunk (hashed here); otherwise ``Xall`` is the compressed
    block dict and ``cats`` / ``vals`` are None (no value-weighted codec)."""
    if codec is None:
        yv, dense, cats, wv, vals = _split_chunk(
            Xall, n_valid, y, w, label_in_chunk=label_in_chunk,
            n_dense=n_dense, value_weighted=value_weighted,
            impute_missing=impute_missing,
        )
        return yv, dense, cats, hash_columns(cats, salts, n_dims), wv, vals
    yv, dense, idx, wv = _decode_chunk(codec, Xall, n_valid, y, w, salts)
    return yv, dense, None, idx, wv, None


def _step_core(
    theta, opt_state, Xall, n_valid, y, w, salts, reg, lr, l1=0.0, keys=None,
    *, loss_kind: str, n_dims: int, n_dense: int, compute_dtype=jnp.float32,
    label_in_chunk: bool = False, value_weighted: bool = False,
    impute_missing: bool = False, optim_update: str = "adam",
    sparse_lowering: str = "none", use_decay: bool = False, codec=None,
):
    """One optimizer step on one chunk — traced by both the per-chunk jit
    (`_hashed_step`) and the fused replay scan (`_hashed_replay_epochs`).

    optim_update == 'adam' is the legacy path: in-loss L2 + a dense optax
    adam sweep over the whole table. Every other rule (optim/ subsystem)
    reports the pure data loss, treats reg as decoupled weight decay, and
    — for the sparse_* rules — reads each distinct touched row once for
    its forward (``_touched_logits``) and updates only those rows, with
    ``keys`` this chunk's ``optim.sparse.sort_keys`` where the fused
    replay has built them ahead of its scan (None: the step sorts for
    itself). ``sparse_lowering`` ('sort' | 'none') names the dedup in the
    jit's static key and chooses nothing.

    codec (io/codec.py, resolved once at fit entry): None is the legacy
    f32 chunk; otherwise ``Xall`` is the compressed block dict and the
    decode (bf16 widen / static bit-unpack, fused by XLA) happens HERE, so
    the replay scan reads compressed HBM bytes.

    The phases carry ``jax.named_scope``s (``step/decode``,
    ``step/forward``, ``step/loss_grad``, ``step/dense_leaf`` here;
    ``step/sort|segment|gather|rule|scatter`` in optim/sparse.py):
    metadata only, so that a device trace is read by phase and not by
    XLA's fusion numbers (docs/observability.md, "Device scopes")."""
    with jax.named_scope("step/decode"):
        yv, dense, cats, idx, wv, vals = _chunk_fields(
            Xall, n_valid, y, w, salts, n_dims=n_dims, n_dense=n_dense,
            label_in_chunk=label_in_chunk, value_weighted=value_weighted,
            impute_missing=impute_missing, codec=codec)

    def forward(theta):
        with jax.named_scope("step/forward"):
            return _hashed_logits(theta, dense, idx, compute_dtype, vals)

    def data_loss(logits):
        with jax.named_scope("step/loss_grad"):
            row = per_row_loss(loss_kind, logits, yv)
            sw = jnp.maximum(jnp.sum(wv), EPS_TOTAL_WEIGHT)
            return jnp.sum(row * wv) / sw

    if optim_update == "adam":
        def loss_fn(theta):
            data = data_loss(forward(theta))
            return data + 0.5 * reg * (
                jnp.sum(theta["emb"] ** 2) + jnp.sum(theta["coef"] ** 2)
            )

        loss, g = jax.value_and_grad(loss_fn)(theta)
        updates, opt_state = _ADAM_UNIT.update(g, opt_state, theta)
        updates = jax.tree.map(lambda u: lr * u, updates)
        return optax.apply_updates(theta, updates), opt_state, loss

    kind = optim_kind(optim_update)
    decay = 1.0 - lr * reg
    step = opt_state["step"]
    slots = opt_state["slots"]
    if is_sparse_update(optim_update):
        # forward only — no autodiff through the table: the [N, k] logits
        # gradient is all the touched-row engine needs. So the forward is
        # free to read each DISTINCT row once and reach the occurrences
        # through the dedup's own sort (touched_rows: _hashed_logits'
        # values, bit for bit, on every live row), and the update takes
        # its weight rows from that one read
        if keys is None:
            keys = sort_keys(idx, n_dims, sort_slots(*idx.shape, n_dims),
                             n_valid, cats if value_weighted else None)
        with jax.named_scope("step/forward"):
            rows, logits = _touched_logits(theta, dense, keys, idx.shape,
                                           compute_dtype, vals)
        loss, dl = jax.value_and_grad(data_loss)(logits)
        emb, t, eslots, n_blocks = sparse_embedding_update(
            kind, theta["emb"], opt_state["t"], slots["emb"], dl, idx,
            lr, decay, reg, l1, step, use_decay=use_decay, vals=vals,
            keys=keys, rows=rows,
        )
        # dense small parameters: the same rule, full-array (they are tiny)
        with jax.named_scope("step/dense_leaf"):
            if theta["coef"].shape[0]:
                g_coef = jnp.dot(dense.astype(compute_dtype).T, dl,
                                 preferred_element_type=jnp.float32)
            else:
                g_coef = jnp.zeros_like(theta["coef"])
            g_int = jnp.sum(dl, axis=0)
    else:
        # dense twin: autodiff through the table, then a full-array rule
        # sweep — the parity baseline the sparse path is measured against
        loss, g = jax.value_and_grad(
            lambda theta: data_loss(forward(theta)))(theta)
        t = opt_state["t"]
        n_blocks = 0
        emb, eslots = dense_update(
            kind, theta["emb"], slots["emb"], g["emb"], lr, decay, reg, l1,
            use_decay=use_decay)
        g_coef, g_int = g["coef"], g["intercept"]
    with jax.named_scope("step/dense_leaf"):
        coef, cslots = dense_update(
            kind, theta["coef"], slots["coef"], g_coef, lr, decay, reg, l1,
            use_decay=use_decay)
        intercept, islots = dense_update(
            kind, theta["intercept"], slots["intercept"], g_int, lr, decay,
            reg, l1, use_decay=False)    # reg never touched the intercept
    theta = {"emb": emb, "coef": coef, "intercept": intercept}
    opt_state = {"step": step + 1, "blocks": opt_state["blocks"] + n_blocks,
                 "t": t,
                 "slots": {"emb": eslots, "coef": cslots,
                           "intercept": islots}}
    return theta, opt_state, loss


_STEP_STATICS = (
    "loss_kind", "n_dims", "n_dense", "compute_dtype", "label_in_chunk",
    "value_weighted", "impute_missing", "optim_update", "sparse_lowering",
    "use_decay", "codec",
)


@donating_jit(static_argnames=_STEP_STATICS, donate_argnums=(0, 1))
def _hashed_step(
    theta, opt_state, Xall, n_valid, y, w, salts, reg, lr, l1=0.0,
    *, loss_kind: str, n_dims: int, n_dense: int, compute_dtype=jnp.float32,
    label_in_chunk: bool = False, value_weighted: bool = False,
    impute_missing: bool = False, optim_update: str = "adam",
    sparse_lowering: str = "none", use_decay: bool = False, codec=None,
):
    return _step_core(
        theta, opt_state, Xall, n_valid, y, w, salts, reg, lr, l1,
        loss_kind=loss_kind, n_dims=n_dims, n_dense=n_dense,
        compute_dtype=compute_dtype, label_in_chunk=label_in_chunk,
        value_weighted=value_weighted, impute_missing=impute_missing,
        optim_update=optim_update, sparse_lowering=sparse_lowering,
        use_decay=use_decay, codec=codec,
    )


def _to_lanes(v):
    """A vector as rows of 128 lanes (zero-padded to whole rows)."""
    if v.ndim != 1:
        return v
    return jnp.pad(v, (0, -v.shape[0] % 128)).reshape(-1, 128)


def _from_lanes(v, like):
    """``_to_lanes`` undone: the array of ``like``'s shape again."""
    return v.reshape(-1)[:like.size].reshape(like.shape)


@donating_jit(static_argnames=_STEP_STATICS + ("n_epochs", "hoist_keys"),
              donate_argnums=(0, 1))
def _hashed_replay_epochs(
    theta, opt_state, stacks, salts, reg, lr, l1=0.0,
    *, loss_kind: str, n_dims: int, n_dense: int, compute_dtype=jnp.float32,
    label_in_chunk: bool = False, value_weighted: bool = False,
    impute_missing: bool = False, optim_update: str = "adam",
    sparse_lowering: str = "none", use_decay: bool = False, codec=None,
    n_epochs: int, hoist_keys: bool = False,
):
    """Epochs 2+ of a cached fit as ONE XLA program: an epoch-level scan
    around a chunk-level scan over the HBM-resident chunk stack.

    ``stacks`` is the chunk stack as one pytree — ``(Xstack, n_valid_vec,
    ystack, wstack)``, each leaf [n_chunks, ...]; the scan slices all of
    them in lockstep.

    ``hoist_keys`` (sparse_* rules only; resolved by ``_hoist_sort_keys``
    from the caller's cache budget): a cached chunk's keys do not change
    between epochs, so its sort, segment ids and ``uniq``
    (``optim.sparse.sort_keys``) are built ONCE here, ahead of the epoch
    scan, and ride the chunk scan beside the chunk — ``n_chunks`` sorts a
    dispatch instead of ``n_epochs x n_chunks``, for ``n_chunks x
    sort_keys_bytes`` of temp. The steps compute what they computed.

    Rationale: the per-chunk jit replay pays one dispatch + sync per step;
    fusing the whole replay phase into one dispatch removes that overhead
    by construction (dispatch count n_chunks*n_epochs -> 1) — and is the
    idiomatic XLA shape for a fixed iteration over fixed data
    (compiler-visible loop, no host round trips).
    Returns per-epoch mean losses ([n_epochs], one small d2h at the end).
    """
    kw = dict(loss_kind=loss_kind, n_dims=n_dims, n_dense=n_dense,
              compute_dtype=compute_dtype, label_in_chunk=label_in_chunk,
              value_weighted=value_weighted, impute_missing=impute_missing,
              optim_update=optim_update, sparse_lowering=sparse_lowering,
              use_decay=use_decay, codec=codec)

    stacks = tuple(stacks)
    if hoist_keys:
        def chunk_keys(chunk):
            _, _, cats, idx, _, _ = _chunk_fields(
                *chunk, salts, n_dims=n_dims, n_dense=n_dense,
                label_in_chunk=label_in_chunk, value_weighted=value_weighted,
                impute_missing=impute_missing, codec=codec)
            return sort_keys(idx, n_dims, sort_slots(*idx.shape, n_dims),
                             chunk[1], cats if value_weighted else None)

        # a TPU tiles an array's last two axes (8, 128): stacked as
        # [n_chunks, M] the 6 chunks of the benchmark would be padded to 8
        # and a chunk's row read 512 bytes at a time, so each vector rides
        # the scan as [n_chunks, M / 128, 128] — for the step a bitcast
        key_shapes = jax.eval_shape(
            chunk_keys, jax.tree.map(lambda a: a[0], stacks[:4]))
        with jax.named_scope("replay/keys"):
            stacks = stacks[:4] + (jax.lax.map(
                lambda chunk: jax.tree.map(_to_lanes, chunk_keys(chunk)),
                stacks[:4]),)

    def chunk_body(carry, xs):
        theta, opt = carry
        Xall, n_valid, y, w = xs[:4]
        # the fifth element, where there is one: the hoisted keys
        keys = (jax.tree.map(_from_lanes, xs[4], key_shapes)
                if hoist_keys else None)
        with jax.named_scope("replay/chunk"):
            theta, opt, loss = _step_core(
                theta, opt, Xall, n_valid, y, w, salts, reg, lr, l1, keys,
                **kw
            )
        return (theta, opt), loss

    def epoch_body(carry, _):
        with jax.named_scope("replay/epoch"):
            carry, losses = jax.lax.scan(chunk_body, carry, stacks)
        return carry, losses

    (theta, opt_state), chunk_losses = jax.lax.scan(
        epoch_body, (theta, opt_state), None, length=n_epochs
    )
    # [n_epochs, n_chunks]: [-1, -1] is the last chunk's loss — the same
    # value the per-step loop path reports as final_loss_
    return theta, opt_state, chunk_losses


@partial(jax.jit, static_argnames=("n_dims", "n_dense", "value_weighted",
                                       "impute_missing"))
def _hashed_predict(theta, Xall, salts, *, n_dims: int, n_dense: int,
                    value_weighted: bool = False,
                    impute_missing: bool = False):
    # one layout authority: the same _split_chunk the training step uses
    _, dense, cats, _, vals = _split_chunk(
        Xall, 0, None, None, label_in_chunk=False, n_dense=n_dense,
        value_weighted=value_weighted, impute_missing=impute_missing,
    )
    idx = hash_columns(cats, salts, n_dims)
    return _hashed_logits(theta, dense, idx, jnp.float32, vals=vals)


@partial(
    jax.jit,
    static_argnames=("loss_kind", "n_dims", "n_dense", "label_in_chunk",
                     "value_weighted", "impute_missing", "codec"),
)
@jax.named_scope("eval/chunk")
def _hashed_eval_chunk(
    theta, Xall, n_valid, y, w, salts,
    *, loss_kind: str, n_dims: int, n_dense: int, label_in_chunk: bool,
    value_weighted: bool = False, impute_missing: bool = False, codec=None,
):
    """Device-side eval accumulators for one chunk: (weighted logloss sum,
    weighted correct sum, weight sum, pos/neg score histograms for AUC).
    Nothing but these small arrays ever crosses back to the host — device->
    host bandwidth is the scarcest resource in the whole pipeline.
    ``codec``: the fit's cache codec when evaluating compressed cached
    chunks (decode-in-jit, same contract as the step)."""
    if codec is None:
        yv, dense, cats, wv, vals = _split_chunk(
            Xall, n_valid, y, w, label_in_chunk=label_in_chunk,
            n_dense=n_dense, value_weighted=value_weighted,
            impute_missing=impute_missing,
        )
        idx = hash_columns(cats, salts, n_dims)
        vals_arg = vals
    else:
        yv, dense, idx, wv = _decode_chunk(codec, Xall, n_valid, y, w, salts)
        vals_arg = None
    logits = _hashed_logits(theta, dense, idx, jnp.float32, vals=vals_arg)
    row = per_row_loss(loss_kind, logits, yv)
    loss_sum = jnp.sum(row * wv)
    if loss_kind == "binary_logistic":
        score = jax.nn.sigmoid(logits[:, 0])
        pred = (score > 0.5).astype(jnp.float32)
    elif loss_kind == "logistic":
        score = jax.nn.softmax(logits, axis=-1)[:, -1]
        pred = jnp.argmax(logits, axis=-1).astype(jnp.float32)
    else:
        score = logits[:, 0]
        pred = (logits[:, 0] > 0).astype(jnp.float32)
    correct = jnp.sum((pred == yv).astype(jnp.float32) * wv)
    bins = 4096
    b = jnp.clip((score * bins).astype(jnp.int32), 0, bins - 1)
    pos = jnp.zeros((bins,), jnp.float32).at[b].add(wv * (yv > 0.5))
    neg = jnp.zeros((bins,), jnp.float32).at[b].add(wv * (yv <= 0.5))
    return loss_sum, correct, jnp.sum(wv), pos, neg


def _auc_from_hists(pos_h: np.ndarray, neg_h: np.ndarray) -> float | None:
    npos, nneg = pos_h.sum(), neg_h.sum()
    if not (npos and nneg):
        return None
    cum_neg = np.concatenate([[0.0], np.cumsum(neg_h)[:-1]])
    return float((pos_h * (cum_neg + 0.5 * neg_h)).sum() / (npos * nneg))


class HashedLinearModel(Model):
    """Fitted hashed-sparse linear model; predicts on raw (dense+categorical)
    chunks — the hashing travels with the model via its salts."""

    def __init__(self, params: HashedLinearParams, theta, salts, class_values):
        self.params = params
        self.theta = theta            # {'emb': [D,k], 'coef': [dd,k], 'intercept': [k]}
        self.salts = np.asarray(salts, np.uint32)
        self.class_values = tuple(class_values) if class_values else None
        self.n_steps_: int | None = None
        self.final_loss_: float | None = None
        # the cache codec of the producing fit (None = raw f32 chunks):
        # evaluate_device's default decode key for device_chunks_
        self.cache_codec_ = None

    @property
    def state_pytree(self):
        return dict(self.theta)

    @property
    def _binary(self) -> bool:
        return _row_loss_kind(self.params) == "binary_logistic"

    def _serve_array_state(self):
        """Serving hook (serve/context.py served_array): the state pytree
        the AOT executable takes as ARGUMENTS — the embedding table is the
        big-state case where closing over constants would duplicate it
        into every bucket's executable."""
        return {"theta": self.theta, "salts": np.asarray(self.salts)}

    def _serve_array_fn(self, state, Xp):
        """Device fn for the bucketed logits executable: row-wise (hash +
        gather + matmul), so bucket padding cannot perturb live rows."""
        p = self.params
        return _hashed_predict(
            state["theta"], Xp, state["salts"], n_dims=p.n_dims,
            n_dense=p.n_dense, value_weighted=p.value_weighted,
            impute_missing=_impute_flag(p),
        )

    def _logits(self, Xall: np.ndarray) -> np.ndarray:
        from orange3_spark_tpu.serve.context import (
            _reentrant, active_serving_context,
        )

        ctx = active_serving_context()
        if ctx is not None and not _reentrant():
            out = ctx.served_array(self, np.asarray(Xall, np.float32))
            if out is not None:
                return out
        p = self.params
        out = _hashed_predict(
            self.theta, jnp.asarray(Xall, jnp.float32),
            jnp.asarray(self.salts), n_dims=p.n_dims, n_dense=p.n_dense,
            value_weighted=p.value_weighted, impute_missing=_impute_flag(p),
        )
        return np.asarray(out)

    def predict(self, Xall: np.ndarray) -> np.ndarray:
        p = self.params
        logits = self._logits(Xall)
        if p.loss == "logistic":
            if self._binary:
                prob = 1.0 / (1.0 + np.exp(-logits[:, 0]))
                return (prob > p.threshold).astype(np.float32)
            if logits.shape[1] == 2:
                prob = 1.0 / (1.0 + np.exp(logits[:, 0] - logits[:, 1]))
                return (prob > p.threshold).astype(np.float32)
            return np.argmax(logits, axis=-1).astype(np.float32)
        if p.loss == "squared":
            return logits[:, 0]
        return (logits[:, 0] > 0).astype(np.float32)  # hinge margins

    def predict_proba(self, Xall: np.ndarray) -> np.ndarray:
        z = self._logits(Xall)
        if self._binary:
            p1 = 1.0 / (1.0 + np.exp(-z[:, 0]))
            return np.stack([1.0 - p1, p1], axis=1)
        z = z - z.max(axis=1, keepdims=True)
        e = np.exp(z)
        return e / e.sum(axis=1, keepdims=True)

    def evaluate_stream(self, source: Callable[[], Iterator]) -> dict:
        """Stream logloss + accuracy (+AUC when binary) without collecting
        the dataset: exact running sums, fixed memory. Host-side loop — fine
        for tests/small tails; at bench scale use ``evaluate_device``."""
        p = self.params
        n = 0
        loss_sum = 0.0
        correct = 0
        # binary AUC via 4096-bin score histograms (rank-sum on bins)
        bins = 4096
        pos_h = np.zeros(bins)
        neg_h = np.zeros(bins)
        for chunk in source():
            Xall, y = chunk[0], chunk[1]
            if y is None:
                raise ValueError("evaluate_stream needs labeled chunks")
            prob = self.predict_proba(Xall)
            yi = np.asarray(y).astype(int)
            pi = np.clip(prob[np.arange(len(yi)), yi], 1e-12, 1.0)
            loss_sum += float(-np.log(pi).sum())
            correct += int((prob.argmax(1) == yi).sum())
            n += len(yi)
            if prob.shape[1] == 2:
                b = np.minimum((prob[:, 1] * bins).astype(int), bins - 1)
                pos_h += np.bincount(b[yi == 1], minlength=bins)
                neg_h += np.bincount(b[yi == 0], minlength=bins)
        out = {"logloss": loss_sum / max(n, 1), "accuracy": correct / max(n, 1)}
        auc = _auc_from_hists(pos_h, neg_h)
        if auc is not None:
            out["auc"] = auc
        return out

    def evaluate_device(self, device_chunks, *, codec="auto") -> dict:
        """Evaluate over device-resident chunks (as cached/returned by
        ``fit_stream(..., cache_device=True)``: (Xall, n_valid, y, w)
        tuples — ``Xall`` is the compressed block dict when the fit cached
        compressed, see ``cache_dtype``). All reduction happens on device;
        only five small arrays come home at the END — no per-chunk
        device->host round trips. ``codec='auto'`` reads the codec the
        producing fit recorded on this model (``cache_codec_``); pass
        ``None`` explicitly for raw f32 chunks built by hand."""
        p = self.params
        if codec == "auto":
            codec = getattr(self, "cache_codec_", None)
        salts = jnp.asarray(self.salts)
        kind = _row_loss_kind(p)
        tot = None
        with span("evaluate", hbm=True):
            for i, chunk in enumerate(device_chunks):
                Xd, n_valid, yd, wd = chunk[:4]
                count_dispatch()
                with span("eval_chunk", i, hbm=True):
                    out = _hashed_eval_chunk(
                        self.theta, Xd, n_valid, yd, wd, salts,
                        loss_kind=kind, n_dims=p.n_dims, n_dense=p.n_dense,
                        label_in_chunk=p.label_in_chunk,
                        value_weighted=p.value_weighted,
                        impute_missing=_impute_flag(p), codec=codec,
                    )
                    tot = out if tot is None else tuple(
                        a + b for a, b in zip(tot, out)
                    )
            if tot is None:
                raise ValueError("no chunks to evaluate")
            loss_sum, correct, wsum, pos, neg = jax.device_get(tot)
        out = {
            "logloss": float(loss_sum / max(wsum, 1e-12)),
            "accuracy": float(correct / max(wsum, 1e-12)),
        }
        # AUC only for probability-calibrated scores (matching
        # evaluate_stream): margin losses produce unbounded scores whose
        # [0,1]-binned histogram would mass-tie at the edge bins
        if kind in ("binary_logistic", "logistic"):
            auc = _auc_from_hists(np.asarray(pos), np.asarray(neg))
            if auc is not None:
                out["auc"] = auc
        return out


@dataclasses.dataclass(frozen=True)
class _ChunkCodec:
    """STATIC description of a fit's compressed chunk layout — a hashable
    jit argument resolved once at fit entry (``resolve_chunk_codec``), so
    the compile cache is keyed on the resolution, never on the env var.
    ``None`` stands for the legacy f32 layout everywhere."""

    mode: str             # 'bf16' | 'packed'
    label_in_chunk: bool
    n_dense: int
    n_cat: int
    n_dims: int
    label_u8: bool        # classification labels stored u8 (exact)
    impute: bool          # NaN -> 0 semantics live in the decode

    @property
    def idx_bits(self) -> int:
        return bit_width(self.n_dims)

    @property
    def cat_words(self) -> int:
        return -(-(self.n_cat * self.idx_bits) // 32)


def resolve_chunk_codec(p: HashedLinearParams,
                        session: TpuSession | None = None):
    """The concrete cache codec for this fit — THE one resolver (the
    ``resolve_optim_update`` convention; ``OTPU_CACHE_DTYPE=f32`` is the
    kill-switch back to the legacy layout). Returns ``None`` for f32."""
    mode = resolve_cache_dtype(p.cache_dtype, session)
    if mode == "f32" or p.value_weighted:
        # vw chunks are explicit (idx, val) PAIRS with their own -1/0
        # padding convention — kept f32 (see the Params docstring)
        return None
    impute = _impute_flag(p)
    if mode == "packed" and not impute and p.n_cat:
        # missing='keep': NaN codes must reach the in-jit hash and poison
        # visibly; pre-hash packing would silently launder them
        mode = "bf16"
    kind = _row_loss_kind(p)
    return _ChunkCodec(
        mode=mode, label_in_chunk=p.label_in_chunk, n_dense=p.n_dense,
        n_cat=p.n_cat, n_dims=p.n_dims,
        # classification labels are small ints — u8-exact — but only
        # while every class id fits a byte: a 300-class logistic fit
        # keeps f32 labels instead of refusing the compressed cache
        label_u8=(p.label_in_chunk
                  and (kind in ("binary_logistic", "hinge", "squared_hinge")
                       or (kind == "logistic" and p.n_classes <= 256))),
        impute=impute,
    )


def _encode_chunk_np(codec: _ChunkCodec, Xp: np.ndarray,
                     salts_np: np.ndarray) -> dict:
    """Host-side encode of one PADDED chunk on the prefetch thread: the
    dict this returns is what the HBM cache, the disk spill and the h2d
    DMA all carry — compressed bytes, decoded only inside the step."""
    off = 1 if codec.label_in_chunk else 0
    enc = {}
    if codec.label_in_chunk:
        lab = Xp[:, 0]
        if codec.label_u8:
            lab8 = lab.astype(np.uint8)
            if not np.array_equal(lab8.astype(np.float32), lab):
                raise ValueError(
                    "cache_dtype compression stores classification labels "
                    "as u8, but a label is not an integer in [0, 255] — "
                    "soft/duplicated-range labels need cache_dtype='f32' "
                    "(or OTPU_CACHE_DTYPE=f32)"
                )
            enc["y"] = lab8
        else:
            enc["y"] = np.ascontiguousarray(lab, np.float32)
    if codec.n_dense:
        enc["dense"] = np.asarray(
            Xp[:, off:off + codec.n_dense]).astype(BF16)
    cats = Xp[:, off + codec.n_dense:]
    if codec.mode == "packed":
        # impute + bucket hash + bit-pack: one native pass over the rows
        # where the parser left them; without the library, the numpy pair
        # it is held bit-identical to (tests/test_cache_codec.py)
        words = hash_pack_rows(cats, salts_np, codec.n_dims, codec.idx_bits,
                               impute=codec.impute)
        _M_ENCODE_CHUNKS.inc(how="numpy" if words is None else "native")
        if words is None:
            if codec.impute:
                cats = np.where(np.isnan(cats), np.float32(0.0), cats)
            words = pack_rows_np(
                hash_columns_np(cats, salts_np, codec.n_dims),
                codec.idx_bits)
        enc["cats"] = words
    else:
        enc["cats"] = np.ascontiguousarray(cats, np.float32)
    return enc


def _decode_chunk(codec: _ChunkCodec, enc: dict, n_valid, y, w, salts):
    """In-jit decode: compressed blocks -> (yv, dense f32, idx i32, wv).
    A widen-on-load XLA fuses into the consumers (the embedding gather,
    the dense matmul) — HBM holds compressed bytes, the math stays f32.
    The packed mode's indices were pre-hashed on the host (the host twin
    is pinned bit-identical to ``hash_columns``), so the step skips the
    hash entirely; bf16 mode hashes exactly as the legacy step does."""
    N = enc["cats"].shape[0]
    if codec.label_in_chunk:
        yv = enc["y"].astype(jnp.float32)
        wv = (jnp.arange(N, dtype=jnp.int32) < n_valid).astype(jnp.float32)
    else:
        yv, wv = y, w
    if codec.n_dense:
        dense = enc["dense"].astype(jnp.float32)
        if codec.impute:
            dense = jnp.where(jnp.isnan(dense), 0.0, dense)
    else:
        dense = jnp.zeros((N, 0), jnp.float32)
    if codec.mode == "packed":
        idx = unpack_rows(enc["cats"], codec.idx_bits, codec.n_cat)
    else:
        cats = enc["cats"]
        if codec.impute:
            cats = jnp.where(jnp.isnan(cats), 0.0, cats)
        idx = hash_columns(cats, salts, codec.n_dims)
    return yv, dense, idx, wv


def _put_encoded(enc: dict, session: TpuSession) -> dict:
    """Device-put an encoded block dict: [N] vectors on the vector
    sharding, [N, k] blocks row-sharded — compressed bytes over the DMA.
    THE one leaf->sharding rule: fit ingest, disk replay and the warm
    builders must produce identical avals or the warm compiles miss."""
    return {k: put_sharded(v, session.row_sharding if v.ndim == 2
                           else session.vector_sharding)
            for k, v in enc.items()}


def _chunk_field_specs(p: HashedLinearParams, codec, pad_rows: int) -> tuple:
    """Ordered (name, shape, dtype) of one spill record — the one
    authority the spill writer/reader and the warm-path builders share."""
    if codec is None:
        n_cols = _chunk_cols(p)
        fields = [("x", (pad_rows, n_cols), np.dtype(np.float32))]
        if not p.label_in_chunk:
            fields += [("yv", (pad_rows,), np.dtype(np.float32)),
                       ("wv", (pad_rows,), np.dtype(np.float32))]
        return tuple(fields)
    fields = []
    if codec.label_in_chunk:
        fields.append(("y", (pad_rows,),
                       np.dtype(np.uint8 if codec.label_u8 else np.float32)))
    if codec.n_dense:
        fields.append(("dense", (pad_rows, codec.n_dense), np.dtype(BF16)))
    if codec.mode == "packed":
        fields.append(("cats", (pad_rows, codec.cat_words),
                       np.dtype(np.uint32)))
    else:
        fields.append(("cats", (pad_rows, codec.n_cat),
                       np.dtype(np.float32)))
    if not codec.label_in_chunk:
        fields += [("yv", (pad_rows,), np.dtype(np.float32)),
                   ("wv", (pad_rows,), np.dtype(np.float32))]
    return tuple(fields)


def _raw_chunk_bytes(p: HashedLinearParams, pad_rows: int) -> int:
    """f32-layout bytes of one cached chunk — the denominator of the
    bench's ``compression_ratio`` and the legacy term in capacity
    estimates."""
    n = pad_rows * _chunk_cols(p) * 4
    if not p.label_in_chunk:
        n += 2 * pad_rows * 4
    return n


def estimate_cached_chunk_bytes(p: HashedLinearParams,
                                session: TpuSession) -> int:
    """Per-chunk HBM cache bytes under the RESOLVED codec — the
    estimate bench.py's overflow/fusion pre-gates use; it must agree with
    what ``fit_stream``'s cache accounting will actually see or the two
    gates disagree in a boundary window."""
    pad_rows = session.pad_rows(p.chunk_rows)
    specs = _chunk_field_specs(p, resolve_chunk_codec(p, session), pad_rows)
    return sum(int(np.prod(s)) * dt.itemsize for _, s, dt in specs)


def _hoist_sort_keys(static_kw: dict, pad_rows: int, n_cat: int,
                     n_chunks: int, cache_nbytes: int,
                     cache_device_bytes: int) -> bool:
    """Whether a fused replay over ``n_chunks`` cached chunks builds their
    sort keys once per dispatch (``_hashed_replay_epochs(hoist_keys=)``)
    — THE one place that decides it, from what the caller already gave:
    the stacked keys are a temp of the replay program (three i32 arrays of
    the occurrences' length per chunk, several times the packed cache they
    describe), so they are taken only where the budget that admitted the
    cache and its stack holds them too. Otherwise every step sorts for
    itself, as ``_hashed_step`` does."""
    return (static_kw["sparse_lowering"] == "sort"
            and 2 * cache_nbytes
            + n_chunks * sort_keys_bytes(pad_rows, n_cat,
                                         static_kw["n_dims"])
            <= cache_device_bytes)


def warm_eval_chunk(p: HashedLinearParams, session: TpuSession) -> tuple:
    """A zero device chunk in the fit's CACHE layout (encoded under the
    resolved codec) — bench.py warms the eval program against it so the
    eval compile never lands inside the timed window. Mirrors the fit's
    salts derivation so the encode path is byte-compatible."""
    pad_rows = session.pad_rows(p.chunk_rows)
    codec = resolve_chunk_codec(p, session)
    Xp0 = np.zeros((pad_rows, _chunk_cols(p)), np.float32)
    if codec is None:
        Xd = put_sharded(Xp0, session.row_sharding)
    else:
        # codec is never active for value_weighted fits (resolve_chunk_codec
        # returns None there), so the fit's plain per-column salts apply
        salts_np = column_salts(p.n_cat, p.seed)
        Xd = _put_encoded(_encode_chunk_np(codec, Xp0, salts_np), session)
    if p.label_in_chunk:
        zy = zw = jnp.zeros((1,), jnp.float32)
    else:
        zy = put_sharded(np.zeros((pad_rows,), np.float32),
                         session.vector_sharding)
        zw = zy
    return (Xd, jnp.int32(1), zy, zw)


def _chunk_cols(p: HashedLinearParams) -> int:
    """Expected chunk width — THE one place that knows the layout:
    [label?] + (idx..., val...) pairs in value-weighted mode, or
    [label?] + dense + categorical columns otherwise."""
    return ((2 if p.value_weighted else 1) * p.n_cat + p.n_dense
            + (1 if p.label_in_chunk else 0))


def _pin_tables(opt_state: dict, session: TpuSession) -> dict:
    """The table-sized optimizer arrays (the rule's slots of ``emb``, the
    last-seen steps ``t``) placed where the table is: rows over 'model',
    replicated over 'data'. ``zeros_like`` hands them that placement
    already (then this moves nothing); it is stated here so that a
    replicated accumulator — 8.6 GB a chip more at 2^30 rows — cannot
    come back with a change of ``init_optim_state`` or of jax."""
    ax = session.model_axis
    slots = dict(opt_state["slots"])
    slots["emb"] = {n: jax.device_put(v, session.sharding(ax, None))
                    for n, v in slots["emb"].items()}
    return {**opt_state, "slots": slots,
            "t": jax.device_put(opt_state["t"], session.sharding(ax))}


def _table_specs(theta: dict, opt_state) -> dict:
    """name -> ``PartitionSpec`` (as text) of every table-sized array of a
    fit's state, read off the arrays themselves: ``emb``, the rule's slots
    of it (``acc`` | ``z``, ``n``) and ``t``; optax's adam state has none
    by these names."""
    tables = {"emb": theta["emb"]}
    if isinstance(opt_state, dict):
        tables.update(opt_state["slots"]["emb"], t=opt_state["t"])
    return {n: str(getattr(v.sharding, "spec", None))
            for n, v in tables.items()}


def _init_fit_state(p: HashedLinearParams, session: TpuSession):
    """Fresh (theta, opt_state, salts_np, salts_dev, static_kw) exactly as a
    fit starts — shared by fit_stream and warm_replay so the warm program's
    avals/statics can never drift from the real fit's (a silent-drift bug
    class: a mismatch just misses the jit cache and moves the scan compile
    back into the timed fit)."""
    k = _effective_k(p)
    model_parallel = (session.model_axis is not None and
                      session.mesh.shape.get(session.model_axis, 1) > 1)
    # model-parallel embedding: the table (the one large parameter) shards
    # its rows over 'model' — P('model', None) — so HBM holds 1/mp of it
    # per device, and is MADE in that placement: a table one chip cannot
    # hold (2^30 rows: 4.3 GB, 12.9 GB with its accumulator and
    # timestamps) never stands whole on any device. GSPMD turns the in-jit
    # gathers/scatters into per-shard masked lookups plus an all-reduce
    # over 'model' (tests/test_tpu_compile.py holds it to that at 2^30).
    emb = jnp.zeros((p.n_dims, k), jnp.float32,
                    device=(session.sharding(session.model_axis, None)
                            if model_parallel else None))
    theta = {
        "emb": emb,
        "coef": jnp.zeros((p.n_dense, k), jnp.float32),
        "intercept": jnp.zeros((k,), jnp.float32),
    }
    optim = resolve_optim_update(p.optim_update)
    lowering = (resolve_sparse_lowering(p.sparse_lowering)
                if is_sparse_update(optim) else "none")
    if optim == "adam":
        opt_state = _ADAM_UNIT.init(theta)
    else:
        opt_state = init_optim_state(optim, theta)
        if model_parallel:
            opt_state = _pin_tables(opt_state, session)
    if p.value_weighted:
        # position-INDEPENDENT hashing: libsvm-style sources pack
        # (idx, val) pairs positionally, so every slot must share ONE salt
        # or a single feature fragments across slot-dependent buckets
        salts_np = np.repeat(column_salts(1, p.seed), p.n_cat)
    else:
        salts_np = column_salts(p.n_cat, p.seed)
    salts = jax.device_put(salts_np, session.replicated)
    if p.value_weighted and p.n_dense:
        raise ValueError(
            "value_weighted mode carries (index, value) pairs only — "
            f"n_dense must be 0, got {p.n_dense}"
        )
    static_kw = dict(
        loss_kind=_row_loss_kind(p), n_dims=p.n_dims, n_dense=p.n_dense,
        compute_dtype=jnp.dtype(p.compute_dtype),
        label_in_chunk=p.label_in_chunk,
        value_weighted=p.value_weighted, impute_missing=_impute_flag(p),
        optim_update=optim, sparse_lowering=lowering,
        # static decay gate: reg == 0 compiles the sparse step without the
        # timestamp gathers/pow (and ftrl owns its L2 in closed form)
        use_decay=(p.reg_param != 0.0 and optim_kind(optim) != "ftrl"),
        # cache codec (io/codec.py): resolved HERE, once, like the
        # optimizer rule — the OTPU_CACHE_DTYPE kill-switch can never
        # poison the jit cache key space mid-process
        codec=resolve_chunk_codec(p, session),
    )
    return theta, opt_state, salts_np, salts, static_kw


class StreamingHashedLinearEstimator(Estimator):
    """Out-of-core hashed-sparse fit over (fastcsv) chunk streams.

    ``fit_stream(source)`` consumes chunks of ``(Xall [n, n_dense+n_cat], y)``
    — exactly what ``io.streaming.csv_chunk_source`` yields — or, with
    ``label_in_chunk=True``, raw ``[n, 1+n_dense+n_cat]`` arrays from
    ``csv_raw_chunk_source``. The full Criteo pipeline is therefore:
    ``csv_raw_chunk_source(path) -> fit_stream -> model.evaluate_device``.
    """

    ParamsCls = HashedLinearParams
    params: HashedLinearParams

    def _fit(self, table):  # Estimator protocol: in-memory fallback
        from orange3_spark_tpu.io.streaming import array_chunk_source
        from orange3_spark_tpu.models.base import infer_class_values

        if self.params.value_weighted:
            # a TpuTable's feature matrix is DENSE columns, never the
            # (idx..., val...) pair layout — feeding it through would hash
            # feature VALUES as indices and train a nonsense model
            raise ValueError(
                "value_weighted fits consume (index, value) pair chunks "
                "(io.libsvm.libsvm_chunk_source) via fit_stream, not "
                "dense tables"
            )
        X, Y, W = table.to_numpy()
        y = Y[:, 0] if Y is not None else None
        class_values = (
            infer_class_values(table) if self.params.loss == "logistic" else None
        )
        return self.fit_stream(
            array_chunk_source(X, y, W, chunk_rows=self.params.chunk_rows),
            session=table.session,
            class_values=class_values,
        )

    def warm_replay(self, n_chunks: int, *,
                    session: TpuSession | None = None,
                    cache_device_bytes: int = 8 << 30):
        """Pre-compile the fused replay program for a fit whose cache will
        hold ``n_chunks`` train chunks, so a subsequent (timed) fit_stream
        hits the jit cache instead of paying the scan compile mid-fit.
        ``n_epochs`` and the chunk-stack shape are static to that program,
        so the warm shapes must match the real fit's (bench.py computes
        n_chunks = total chunks - holdout chunks), and so must
        ``cache_device_bytes`` where the fit is given one: it decides
        whether the replay hoists its sort keys (``_hoist_sort_keys``).
        Device-side zeros only — one chunk-sized host transfer, no data
        pass.

        Returns ``(theta, salts_np)`` from the executed warm scan (or None
        when no replay program applies): scan-OUTPUT provenance, which is
        exactly what a defer fit's post-fit ``evaluate_device`` sees — so a
        caller can warm the eval program against it and hit the jit cache
        in the timed run (bench.py does).

        The warmed program mirrors ``defer_epoch1`` as configured on the
        params; the subsequent fit must use the SAME effective schedule.
        With ``replay_granularity='epoch'`` a checkpointered defer fit
        keeps the fused schedule (epoch-boundary snapshots), so warming it
        is correct; with granularity 'all' a checkpointered fit silently
        falls back to the default schedule (as does any fit without
        cache_device), and the warm would compile a program that fit
        never dispatches."""
        p = self.params
        from orange3_spark_tpu.io.streaming import check_replay_granularity

        check_replay_granularity(p.replay_granularity)
        session = session or TpuSession.active()
        if not (p.fused_replay and (p.epochs > 1 or p.defer_epoch1)
                and n_chunks > 0):
            return None
        n_cols = _chunk_cols(p)
        pad_rows = session.pad_rows(p.chunk_rows)
        theta, opt, salts_np, salts, kw = _init_fit_state(p, session)
        codec = kw["codec"]
        # one zero chunk through the SAME encode + device-put path as the
        # real fit, so the stacked avals (incl. dtypes/shardings of the
        # compressed blocks) match the timed run's
        Xp0 = np.zeros((pad_rows, n_cols), np.float32)
        if codec is None:
            z = put_sharded(Xp0, session.row_sharding)
        else:
            z = _put_encoded(_encode_chunk_np(codec, Xp0, salts_np),
                             session)
        nv = jnp.int32(pad_rows)
        if p.label_in_chunk:
            zy = zw = jnp.zeros((1,), jnp.float32)
        else:
            zy = put_sharded(np.zeros((pad_rows,), np.float32),
                             session.vector_sharding)
            zw = zy
        l1 = jnp.float32(p.l1_param)
        if not p.defer_epoch1:
            # theta/opt must have step-OUTPUT provenance (GSPMD-placed),
            # like the real replay's inputs after a per-chunk epoch 1. A
            # defer fit hands the replay _init_fit_state outputs directly,
            # so its warm must NOT run a step.
            theta, opt, _ = _hashed_step(
                theta, opt, z, nv, zy, zw, salts,
                jnp.float32(p.reg_param), jnp.float32(p.step_size),
                l1, **kw)
        n_rep = p.epochs - 1 + (1 if p.defer_epoch1 else 0)
        stacks = (
            jax.tree.map(lambda a: jnp.stack([a] * n_chunks), z),
            jnp.stack([nv] * n_chunks),
            jnp.stack([zy] * n_chunks), jnp.stack([zw] * n_chunks),
        )
        theta, opt, losses = _hashed_replay_epochs(
            theta, opt, stacks, salts,
            jnp.float32(p.reg_param), jnp.float32(p.step_size), l1,
            # 'epoch' granularity dispatches n_epochs=K scans (the
            # epochs_per_dispatch group size, clamped to the replay span)
            n_epochs=(min(max(1, p.epochs_per_dispatch), n_rep)
                      if p.replay_granularity == "epoch" else n_rep),
            hoist_keys=_hoist_sort_keys(
                kw, pad_rows, p.n_cat, n_chunks,
                n_chunks * estimate_cached_chunk_bytes(p, session),
                cache_device_bytes),
            **kw)
        jax.block_until_ready(losses)
        return theta, np.asarray(salts)

    @traced("fit", model="hashed_linear")
    def fit_stream(
        self,
        source: Callable[[], Iterator],
        *,
        session: TpuSession | None = None,
        class_values: tuple | None = None,
        checkpointer=None,
        cache_device: bool = False,
        cache_device_bytes: int = 8 << 30,
        cache_spill_dir: str | None = None,
        holdout_chunks: int = 0,
        stage_times: dict | None = None,
    ) -> HashedLinearModel:
        """Fit over a re-iterable chunk source.

        cache_device: retain device-put chunks in HBM and replay them for
          epochs 2+ (Spark's ``persist()`` before MLlib's iterative fit).
          If the stream outgrows ``cache_device_bytes`` the fit degrades
          (no partial replay — see the module docstring): with
          ``cache_spill_dir`` set, epochs 2+ replay padded records
          (encoded per ``cache_dtype``) from an on-disk cache written
          during epoch 1 (read + DMA, no re-parse — the 1B-row regime);
          without it, every epoch re-runs
          the source, which for a CSV source means re-PARSING the file
          per epoch — a loud ``warnings.warn`` says so once. The cached
          chunk list is exposed on the returned model as
          ``model.device_chunks_``.
        cache_spill_dir: directory for the epoch-1 disk spill (written on
          the prefetch thread, sequential f32, released when the fit
          returns). The write happens during epoch 1 WHETHER OR NOT the
          cache ends up overflowing (the overflow point is unknowable
          mid-stream, and device->host readback to recover dropped
          chunks is the slowest path there is) — arm it when
          the dataset is expected to exceed ``cache_device_bytes``, as
          bench.py does from its known row count.
        holdout_chunks: exclude the LAST n device batches of each epoch from
          training; with cache_device they are retained (and exposed as
          ``model.holdout_chunks_``) for ``evaluate_device``.
        stage_times: optional dict that receives host-side stage seconds
          ('parse_s', 'h2d_s' — accumulated on the PREFETCH thread, so they
          overlap device work and may sum past wall) plus 'epoch_s', the
          measured phase walls. With ``fused_replay`` off this is one wall
          per epoch (epoch 1 = streaming, later cached epochs = pure
          device); with it ON (the default) epochs 2+ run as ONE fused
          dispatch, so 'epoch_s' is ``[epoch1_wall, whole_replay_wall]``
          and 'replay_fused_s' carries that second number explicitly.
        """
        from orange3_spark_tpu.io.streaming import (
            DiskChunkCache, _pad_chunk, _rechunk, check_replay_granularity,
            epoch_boundary_snapshot, resolve_epoch_checkpointing,
            warn_cache_overflow,
        )

        p = self.params
        check_replay_granularity(p.replay_granularity)
        # the run report rides the OTPU_OBS kill-switch (its two counter
        # snapshots are this path's only per-fit obs cost)
        report = (RunReport("fit_stream", estimator=type(self).__name__,
                            n_dims=p.n_dims, epochs=p.epochs)
                  if obs_enabled() else None)
        # goodput accountant (obs/prof.py): per-epoch bottleneck
        # classification + the five-way wall decomposition; None under
        # OTPU_PROF=0 (every downstream hook no-ops on the contextvar)
        acc = prof.begin_fit()
        # the HBM account (obs/prof.py): a mark here, before the state is
        # made, closes the interval since the last fit's last mark (what
        # the caller did between fits, what the last job left) and opens
        # this fit's; every span below built with hbm=True marks as it closes
        prof.hbm_mark("between_fits", first=True)
        session = session or TpuSession.active()
        k = _effective_k(p)
        n_cols = _chunk_cols(p)
        theta, opt_state, salts_np, salts, static_kw = _init_fit_state(
            p, session
        )
        # device-memory ledger: the table + optimizer slots are the
        # other big HBM tenant beside the chunk cache — named so an
        # OOM-adjacent post-mortem can tell table growth from cache
        # growth. Re-set to theta-only at fit end (slots die with the
        # fit); released when the fitted model itself dies.
        state_key = f"hashed-{next(_FIT_LEDGER_SEQ)}"

        def state_now():
            """What the entry holds at this moment: every step donates
            the state and hands back new arrays (asked at a census)."""
            return theta, opt_state

        prof.ledger_set_tree("model_state", state_key, (theta, opt_state),
                             now=state_now)
        prof.hbm_mark("model_state")
        # what this fit runs on: the mesh's shape and where each table
        # stands (gauge otpu_mesh_devices + one "mesh" event in the trace)
        prof.note_mesh(session.mesh, **_table_specs(theta, opt_state))
        # frame-scoped guard: a fit that ABORTS (divergence, wedge,
        # retry exhaustion) must not strand its model_state entry — the
        # guard's death releases it; the success tail detaches it and
        # hands ownership to the model's own finalizer
        _state_guard = prof.ledger_guard("model_state", state_key)
        resume_from = 0
        ckpt_meta = {"params": p.to_dict(), "k": k}
        # epoch-cadence snapshots (checkpoint_every_epochs): the shared
        # arming rule — see StreamingLinearParams for the contract
        ckpt_epochs = resolve_epoch_checkpointing(p, checkpointer)
        # the 'sort' lowering's block count rides opt_state; where this
        # fit's own steps start counting (a resumed fit starts above zero)
        counted_from = (0, 0)
        if checkpointer is not None:
            step0, saved = checkpointer.load(expect_meta=ckpt_meta)
            if saved is not None:
                theta = jax.tree.map(jnp.asarray, saved["theta"])
                saved_opt = saved["opt_state"]
                if isinstance(opt_state, dict):
                    saved_opt = adopt_optim_state(saved_opt)
                    counted_from = (int(saved_opt["step"]),
                                    int(saved_opt["blocks"]))
                opt_state = jax.tree.map(
                    lambda tmpl, v: jnp.asarray(v)
                    if isinstance(tmpl, (jax.Array, np.ndarray)) else v,
                    opt_state, saved_opt,
                )
                resume_from = step0

        pad_rows = session.pad_rows(p.chunk_rows)
        row_sh = session.row_sharding
        vec_sh = session.vector_sharding
        reg = jnp.float32(p.reg_param)
        lr = jnp.float32(p.step_size)
        l1 = jnp.float32(p.l1_param)
        optim_resolved = static_kw["optim_update"]
        # cache codec (io/codec.py), resolved once in _init_fit_state: all
        # storage surfaces — HBM cache, disk spill, h2d DMA — carry the
        # encoded blocks; decode happens inside the jitted step
        codec = static_kw["codec"]
        chunk_specs = _chunk_field_specs(p, codec, pad_rows)
        # stage seconds are the sums of span durations (obs.trace.stage):
        # they collect for the caller's stage_times= dict AND for the run
        # report (obs/report.py); under OTPU_OBS=0 with no caller dict
        # `times` is None and `staged` below is a no-op.
        # honest_walls CHANGES THE RUN: a caller that passes stage_times=
        # (the benchmark does) gets one more block_until_ready(last_loss)
        # at every epoch boundary, so that an epoch's wall ends with its
        # device work; a default fit has none. What each costs is the
        # "epoch_barrier" span's duration.
        times = ({"parse_s": 0.0, "h2d_s": 0.0}
                 if stage_times is not None or obs_enabled() else None)
        honest_walls = stage_times is not None

        def staged(name, key, **args):
            """A ``name`` span whose seconds also land in ``times[key]``."""
            if times is None:       # spans are off too: the shared no-op
                return span(name, **args)
            return stage(name, times, key, **args)
        # fit-level pipeline counters: every prefetch stream (live ingest,
        # disk replay, grouped disk replay) folds in, so overlap_pct is the
        # measured host-prep/device-compute overlap of the WHOLE fit
        pipe_stats = PipelineStats()
        # THE source chokepoint (docs/resilience.md): fault injection +
        # bounded transient-read retries on the prefetch thread; retries
        # count into pipe_stats (the bench line's `retries` field)
        from orange3_spark_tpu.resilience.retry import resilient_source

        source = resilient_source(source, stats=pipe_stats)

        def put_payload(payload):
            """Device-put one chunk payload: the raw [N, cols] array, or
            the encoded block dict via the shared leaf->sharding rule."""
            if codec is None:
                return put_sharded(payload, row_sh)
            return _put_encoded(payload, session)

        def record_arrays(payload, yp, wp):
            """Spill-record field tuple in ``chunk_specs`` declaration
            order."""
            if codec is None:
                return (payload,) if p.label_in_chunk else (payload, yp, wp)
            return tuple(
                yp if name == "yv" else wp if name == "wv"
                else payload[name]
                for name, _, _ in chunk_specs
            )

        def record_to_host(arrays):
            """Typed spill-record views -> (payload, y, w) host arrays —
            the inverse of ``record_arrays``."""
            y_np = w_np = None
            if codec is None:
                payload = np.asarray(arrays[0])
                if not p.label_in_chunk:
                    y_np = np.asarray(arrays[1])
                    w_np = np.asarray(arrays[2])
            else:
                payload = {}
                for (name, _, _), a in zip(chunk_specs, arrays):
                    if name == "yv":
                        y_np = np.asarray(a)
                    elif name == "wv":
                        w_np = np.asarray(a)
                    else:
                        payload[name] = np.asarray(a)
            return payload, y_np, w_np

        def encode_chunk(host_chunk):
            """One host chunk -> (payload, y, w, n_valid) as the cache,
            the spill and the DMA carry it."""
            if p.label_in_chunk:
                X_np = host_chunk if isinstance(
                    host_chunk, np.ndarray) else host_chunk[0]
                y_np = w_np = None
            else:
                X_np, y_np, w_np = (tuple(host_chunk) + (None, None))[:3]
            if X_np.shape[1] != n_cols:
                raise ValueError(
                    f"chunk has {X_np.shape[1]} columns, expected {n_cols}"
                )
            n = X_np.shape[0]
            if p.label_in_chunk:
                if n == pad_rows:
                    Xp = np.ascontiguousarray(X_np, dtype=np.float32)
                else:
                    Xp = np.zeros((pad_rows, n_cols), np.float32)
                    Xp[:n] = X_np
                yp = wp = None
            else:
                Xp, yp, wp = _pad_chunk(X_np, y_np, w_np, pad_rows,
                                        n_cols)
            # encode on the prefetch thread (io/codec.py): bf16 / u8 /
            # bit-packed blocks (the categorical columns hashed here under
            # the packed codec) — the cache, the spill AND the DMA all
            # carry the compressed bytes from here on
            payload = Xp
            if codec is not None:
                payload = _encode_chunk_np(codec, Xp, salts_np)
            return payload, yp, wp, n

        def to_device(host_chunk):
            """parse-thread side: encode (pad, hash, codec) and device_put
            one chunk."""
            with stage("encode", pipe_stats, "encode_s"):
                payload, yp, wp, n = encode_chunk(host_chunk)
            if spill_active[0]:
                # sequential write of the already-encoded chunk — still
                # on the prefetch thread, overlapping device steps
                with staged("spill", "spill_s"):
                    spill.append(record_arrays(payload, yp, wp), n)
            with staged("h2d", "h2d_s"):
                Xd = put_payload(payload)
                if p.label_in_chunk:
                    yd = wd = _ZERO
                else:
                    yd = put_sharded(yp, vec_sh)
                    wd = put_sharded(wp, vec_sh)
            return (Xd, jnp.int32(n), yd, wd)

        _ZERO = jnp.zeros((1,), jnp.float32)

        def host_chunks():
            """Rechunked host stream, each pull one "parse" span (the pull
            that finds the stream's end among them)."""
            if p.label_in_chunk:
                it = _rechunk(((c, None) for c in source()), pad_rows)
            else:
                it = _rechunk(source(), pad_rows)
            while True:
                with staged("parse", "parse_s"):
                    item = next(it, None)
                if item is None:
                    return
                yield item if not p.label_in_chunk else item[0]

        def device_chunk_iter():
            from orange3_spark_tpu.io.streaming import prefetch_map

            if p.prefetch_depth > 0:
                yield from prefetch_map(
                    to_device, host_chunks(), depth=p.prefetch_depth,
                    stats_into=pipe_stats,
                )
            else:
                for c in host_chunks():
                    yield to_device(c)

        from orange3_spark_tpu.io.streaming import _DeviceCache

        # device-resident training chunks; shared budget/degrade rule with
        # the other streaming estimators. Enabled even at epochs=1 because
        # the cache doubles as the model's exposed device_chunks_.
        # may_exclude_tail: an over-budget offer within the last
        # holdout_chunks offers may later be excluded (the un-latch); any
        # earlier miss is final and the cache drops the moment that is
        # known, legacy-style
        cache = _DeviceCache(cache_device, cache_device_bytes,
                             may_exclude_tail=holdout_chunks)
        # Defer-epoch-1 schedule (see the Params docstring): the streaming
        # pass is pure ingest and ALL p.epochs training passes run off the
        # cache/spill/stream afterwards. Bit-identical step sequence; the
        # epoch loop below runs one extra iteration to compensate for the
        # step-free pass 0. Falls back silently when its preconditions
        # don't hold. Computed up here because a defer fit has replay
        # passes even at epochs == 1, so the spill/overflow gates below
        # must read `epochs > 1 or defer`.
        #
        # Checkpointing: per-STEP snapshots need per-chunk dispatches, so a
        # checkpointered fit normally keeps the interleaved schedule — but
        # with replay_granularity='epoch' the replay is one dispatch PER
        # EPOCH, which gives a natural epoch-boundary snapshot cadence:
        # defer + checkpointer compose there (resume re-ingests the cache
        # step-free, fast-forwards whole checkpointed epochs, and resumes
        # dispatching — bit-identical, pinned by the kill-and-resume test).
        ckpt_epoch_ok = p.replay_granularity == "epoch"
        defer = (
            p.defer_epoch1 and cache_device and p.epochs > 0
            and (checkpointer is None or ckpt_epoch_ok)
            and (resume_from == 0 or ckpt_epoch_ok)
        )
        spill: DiskChunkCache | None = None
        spill_active = [False]      # toggled by the epoch loop; read by
        #                             to_device on the prefetch thread
        if (cache_device and cache_spill_dir is not None
                and (p.epochs > 1 or defer)):
            # the spill records carry the SAME encoded fields as the HBM
            # cache (typed, versioned header — io/streaming.DiskChunkCache)
            # so spill I/O shrinks with the cache under a compressed codec
            spill = DiskChunkCache(cache_spill_dir,
                                   tuple(s for _, s, _ in chunk_specs),
                                   tuple(dt for _, _, dt in chunk_specs))
            spill_active[0] = True
        use_disk = False
        holdout: list = []         # device-resident holdout chunks
        n_steps = 0
        last_loss = None

        # dispatch-queue depth coupled to the staging depth: queueing more
        # steps than the prefetcher can stage starves nothing and lets the
        # consumer sprint arbitrarily far ahead of the device — which both
        # un-bounds in-flight memory and blinds the overlap measurement
        # (queue-wait only reflects device pace while the consumer is
        # paced by the device)
        step_period = max(2, 2 * p.prefetch_depth)

        def run_step(dev_chunk):
            nonlocal theta, opt_state, n_steps, last_loss
            Xd, n_valid, yd, wd = dev_chunk
            with span("chunk", n_steps, hbm=True):
                theta, opt_state, loss = _hashed_step(
                    theta, opt_state, Xd, n_valid, yd, wd, salts, reg, lr,
                    l1, **static_kw,
                )
                n_steps += 1
                last_loss = loss
                bound_dispatch(n_steps, loss, period=step_period)
            if checkpointer is not None and not ckpt_epochs:
                checkpointer.maybe_save(
                    n_steps, {"theta": theta, "opt_state": opt_state},
                    meta=ckpt_meta,
                )

        epoch_walls: list = []
        replay_fused_s = None
        # steps whose sort keys a hoisting replay dispatch had built ahead
        # of its scan (feeds otpu_sparse_sorts_total)
        sorts_saved = 0
        # fused replay: epochs 2+ lower to ONE dispatch (see
        # _hashed_replay_epochs). Requires the full cache (same chunk set
        # every epoch) and no per-step checkpoint/resume bookkeeping.
        # The chunk stack is a SECOND device copy of the cache, so fusion
        # only engages while stack+cache fit the cache budget together —
        # past half the budget it falls back to the per-chunk loop.
        fuse_replay = (
            p.fused_replay and cache_device and p.epochs > 1
            and ((checkpointer is None and resume_from == 0)
                 # per-epoch dispatches snapshot/resume at epoch
                 # boundaries — fusion stays available (see defer above)
                 or ckpt_epoch_ok)
        )
        if defer:
            # a defer fit fuses even at epochs == 1 (the single training
            # pass IS the replay)
            fuse_replay = p.fused_replay
        def disk_chunk_iter(start: int = 0):
            """Device feed for an overflow replay epoch: padded records
            straight off the spill memmap (no parsing), prefetch-overlapped
            like the live stream. Skips the holdout tail — those records
            were never trained in epoch 1 either. ``start`` lets the
            grouped path hand its partial tail here."""
            from orange3_spark_tpu.io.streaming import prefetch_map

            def rec_to_device(i):
                arrays, n = spill.read(i)
                payload, y_np, w_np = record_to_host(arrays)
                with staged("h2d", "h2d_s"):
                    Xd = put_payload(payload)
                    if p.label_in_chunk:
                        yd = wd = _ZERO
                    else:
                        yd = put_sharded(y_np, vec_sh)
                        wd = put_sharded(w_np, vec_sh)
                return (Xd, jnp.int32(n), yd, wd)

            idxs = iter(range(start, spill.n_records - holdout_chunks))
            if p.prefetch_depth > 0:
                yield from prefetch_map(rec_to_device, idxs,
                                        depth=p.prefetch_depth,
                                        stats_into=pipe_stats)
            else:
                for i in idxs:
                    yield rec_to_device(i)

        def disk_group_iter(group: int):
            """Grouped feed for fused disk replay: G records stacked into
            one [G, pad_rows, ...] device batch per item — one scan
            dispatch trains the whole group (see the replay branch).
            Yields FULL groups only; the partial tail (a different leading
            shape that would force a second scan compile) goes through the
            per-chunk step, which is already compiled from epoch 1."""
            from orange3_spark_tpu.io.streaming import prefetch_map

            n_train = spill.n_records - holdout_chunks
            n_full = (n_train // group) * group

            def grp_to_device(start):
                g = group
                recs = [spill.read(start + j) for j in range(g)]
                hosts = [record_to_host(r[0]) for r in recs]

                def stack_put(leaves):
                    a = np.stack(leaves)
                    spec = ((None, session.data_axis)
                            + (None,) * (a.ndim - 2))
                    return put_sharded(a, session.sharding(*spec))

                with staged("h2d", "h2d_s"):
                    if codec is None:
                        Xs = stack_put([h[0] for h in hosts])
                    else:
                        Xs = {k2: stack_put([h[0][k2] for h in hosts])
                              for k2 in hosts[0][0]}
                    nv = jnp.asarray([r[1] for r in recs], jnp.int32)
                    if p.label_in_chunk:
                        ys = ws = jnp.zeros((g, 1), jnp.float32)
                    else:
                        ys = stack_put([h[1] for h in hosts])
                        ws = stack_put([h[2] for h in hosts])
                return g, (Xs, nv, ys, ws)

            starts = iter(range(0, n_full, group))
            if p.prefetch_depth > 0:
                yield from prefetch_map(grp_to_device, starts, depth=1,
                                        stats_into=pipe_stats)
            else:
                for s in starts:
                    yield grp_to_device(s)

        for epoch in span_iter("epoch", range(p.epochs + (1 if defer else 0))):
            t_epoch = time.perf_counter()
            if epoch == 0 or not (cache.enabled or use_disk):
                # stream from the source; a look-ahead window keeps the LAST
                # holdout_chunks device batches out of training
                window: list = []
                for dev_chunk in device_chunk_iter():
                    if epoch == 0:
                        cache.offer(dev_chunk)
                    if holdout_chunks > 0:
                        window.append(dev_chunk)
                        if len(window) <= holdout_chunks:
                            continue
                        dev_chunk = window.pop(0)
                    if epoch == 0 and defer:
                        continue        # ingest-only pass: no step dispatch
                    if n_steps < resume_from:
                        n_steps += 1
                        continue
                    run_step(dev_chunk)
                if epoch == 0 and holdout_chunks > 0:
                    holdout = window[-holdout_chunks:]
                    if cache.enabled:
                        # the tail chunks live in the cache too — they must
                        # never be trained on in replay epochs (exclude()
                        # keeps nbytes honest for the fuse_replay gate) —
                        # and misses confined to this excluded tail never
                        # degrade the run (the un-latch)
                        cache.exclude({id(c[0]) for c in holdout})
                        cache.forgive_tail(holdout_chunks)
                if epoch == 0:
                    spill_active[0] = False   # prefetch thread has exited
                    if spill is not None:
                        spill.finalize()
                    # an incomplete cache drops whole here; one whose
                    # misses were all holdout-excluded keeps replaying
                    # from HBM (the un-latch the exclude() covers)
                    cache.settle()
                    if cache.degraded and (p.epochs > 1 or defer):
                        use_disk = (spill is not None
                                    and spill.n_records > holdout_chunks)
                        if not use_disk:
                            warn_cache_overflow(
                                cache_device_bytes,
                                p.epochs - 1 + (1 if defer else 0),
                                detail=(
                                    "The disk spill has no trainable "
                                    "records (fewer chunks than the "
                                    "holdout tail)."
                                    if spill is not None else
                                    "Set cache_spill_dir= to replay "
                                    "parsed chunks at disk bandwidth "
                                    "instead."
                                ),
                            )
            elif cache.enabled:
                # pure-HBM epoch: replay the cached chunks, no host at all
                for dev_chunk in cache.batches:
                    if n_steps < resume_from:
                        n_steps += 1
                        continue
                    run_step(dev_chunk)
            else:
                # overflow epoch off the disk spill: read + DMA, no parse.
                # When no per-step checkpoint granularity is needed, G
                # records stack into one device batch and train as ONE
                # scan dispatch (_hashed_replay_epochs, n_epochs=1) —
                # dispatch count drops G-fold. G is
                # sized so current group + prefetched group + transient
                # scan copies stay inside the cache budget.
                rec_bytes = spill.payload_bytes
                group = max(1, min(spill.n_records,
                                   cache_device_bytes // (4 * rec_bytes)))
                if (p.fused_replay and checkpointer is None
                        and resume_from == 0 and group > 1):
                    if times is not None:
                        times["disk_replay_group"] = group
                    n_groups = 0
                    for g, stacks in disk_group_iter(group):
                        theta, opt_state, losses = _hashed_replay_epochs(
                            theta, opt_state, stacks, salts, reg, lr, l1,
                            n_epochs=1, **static_kw,
                        )
                        n_steps += g
                        n_groups += 1
                        last_loss = losses[-1, -1]
                        # bound by GROUPS, not steps: each in-flight group
                        # dispatch pins a budget/4-byte input stack, so 16
                        # unsynced groups would hold ~4x the cache budget
                        # in HBM; period=2 keeps one executing + one queued
                        # (+ the prefetched next group) <= 3/4 budget
                        bound_dispatch(n_groups, last_loss, period=2)
                    # partial tail group (different leading shape would
                    # recompile the scan): per-chunk steps — compiled in
                    # epoch 1, or on first use here under defer_epoch1
                    n_train_recs = spill.n_records - holdout_chunks
                    for dev_chunk in disk_chunk_iter(
                            start=(n_train_recs // group) * group):
                        run_step(dev_chunk)
                else:
                    for dev_chunk in disk_chunk_iter():
                        if n_steps < resume_from:
                            n_steps += 1
                            continue
                        run_step(dev_chunk)
            # non-finite guard (resilience/numerics.py) BEFORE the save:
            # a divergent epoch raises typed, never checkpoints NaN state
            with span("finite_check", final=False, hbm=True):
                check_finite_training(
                    last_loss, theta, epoch=epoch, chunk=n_steps,
                    estimator="StreamingHashedLinearEstimator")
            # epoch-boundary snapshot (checkpoint_every_epochs cadence):
            # the shared save decision covers every epoch path above
            epoch_boundary_snapshot(
                checkpointer, ckpt_epochs, epoch, defer, n_steps,
                resume_from,
                lambda: {"theta": theta, "opt_state": opt_state},
                ckpt_meta,
            )
            if times is not None:
                if honest_walls and last_loss is not None:
                    with stage("epoch_barrier") as barrier:
                        jax.block_until_ready(last_loss)  # honest epoch wall
                    # an explicit epoch barrier is synchronization, not
                    # device pace (the periodic sync already charged that)
                    prof.note_sync(barrier.seconds, barrier=True)
                epoch_walls.append(time.perf_counter() - t_epoch)
            if acc is not None:
                # close the goodput window: per-epoch stage deltas +
                # hysteresis bottleneck classification (obs/prof.py)
                acc.epoch_boundary(epoch, encode_s=pipe_stats.encode_s)
            if (epoch == 0 and fuse_replay and cache.enabled
                    and cache.batches
                    and 2 * cache.nbytes <= cache_device_bytes
                    # epoch-granular resume can only fast-forward WHOLE
                    # epochs; a snapshot written off an epoch boundary
                    # (e.g. by a per-chunk phase of an earlier run whose
                    # fusion gate differed) must take the per-chunk replay
                    # below, which skips at step grain — entering the
                    # fused path would re-apply the partial epoch's steps
                    and resume_from % len(cache.batches) == 0):
                # remaining epochs in one program: stack the cache (HBM->
                # HBM copy; the per-chunk list stays live for evaluate_device
                # / bench probes) and scan
                n_rep = p.epochs - 1 + (1 if defer else 0)
                spe = len(cache.batches)          # steps per replay epoch
                if n_steps + n_rep * spe <= resume_from:
                    # snapshot already covers every replay epoch: skip
                    # without building the (second-HBM-copy) stack; the
                    # model is complete, final_loss_ stays None, and no
                    # replay wall is recorded for this
                    # resume-at-completion edge
                    n_steps += n_rep * spe
                    break
                with stage("replay_stack", hbm=True) as stacked:
                    # stack the WHOLE chunk tuple as one pytree
                    stacks = jax.tree.map(
                        lambda *xs: jnp.stack(xs), *cache.batches)
                    # the stack is a SECOND device copy of the cache — a
                    # distinct ledger tenant (owner "replay_plans": the
                    # name its readers know it by) for exactly as long as
                    # it lives. Name keyed per FIT (two
                    # concurrent replays must not share one entry); the
                    # guard releases on an aborted replay (device OOM while
                    # holding the copy is THE likely failure here), the
                    # explicit release below makes its firing a no-op
                    rp_key = f"replay_stack-{state_key}"
                    _rp_guard = prof.ledger_guard("replay_plans", rp_key)
                    prof.ledger_set_tree("replay_plans", rp_key, stacks)
                hoist = _hoist_sort_keys(static_kw, pad_rows, p.n_cat, spe,
                                         cache.nbytes, cache_device_bytes)
                with stage("replay", hbm=True, n_epochs=n_rep,
                           steps=n_rep * spe) as replayed:
                    if p.replay_granularity == "epoch":
                        # one n_epochs=1 scan dispatch per epoch over the
                        # same stack (see the Params docstring). Epoch
                        # boundaries are the snapshot/resume grain; the
                        # skip/save protocol is the shared run_epoch_replay.
                        from orange3_spark_tpu.io.streaming import (
                            run_epoch_replay,
                        )

                        def _disp(n_ep):
                            nonlocal theta, opt_state, sorts_saved
                            with span("replay_dispatch", hbm=True,
                                      n_epochs=n_ep):
                                theta, opt_state, chunk_losses = \
                                    _hashed_replay_epochs(
                                        theta, opt_state, stacks, salts,
                                        reg, lr, l1, n_epochs=n_ep,
                                        hoist_keys=hoist, **static_kw,
                                    )
                            sorts_saved += hoist * (n_ep - 1) * spe
                            return chunk_losses[-1, -1]

                        n_steps, last, _ = run_epoch_replay(
                            n_rep, spe, n_steps, resume_from, checkpointer,
                            _disp,
                            lambda: {"theta": theta, "opt_state": opt_state},
                            ckpt_meta,
                            epochs_per_dispatch=p.epochs_per_dispatch,
                            every_epochs=ckpt_epochs,
                        )
                        if last is not None:
                            last_loss = last
                    else:
                        theta, opt_state, chunk_losses = \
                            _hashed_replay_epochs(
                                theta, opt_state, stacks, salts, reg, lr, l1,
                                n_epochs=n_rep, hoist_keys=hoist,
                                **static_kw,
                            )
                        sorts_saved += hoist * (n_rep - 1) * spe
                        count_dispatch()  # one-shot fused scan: no loop ticks
                        last_loss = chunk_losses[-1, -1]
                        n_steps += n_rep * spe
                    del stacks
                    prof.ledger_release("replay_plans", rp_key)
                    with stage("replay_drain", hbm=True) as drained:
                        jax.block_until_ready(last_loss)
                # the drain blocks on the WHOLE fused replay — it is the
                # one place the driver observes the replay's device
                # compute, so it charges device_compute, not sync_wait
                prof.note_sync(drained.seconds)
                replay_fused_s = stacked.seconds + replayed.seconds
                if acc is not None:
                    acc.epoch_boundary(p.epochs - 1,
                                       encode_s=pipe_stats.encode_s)
                if times is not None:
                    epoch_walls.append(replay_fused_s)
                break

        if spill is not None:
            spill.delete()
        # fused replay breaks out past the per-epoch guard: final check
        # (loss AND theta — a last-step divergence only shows in theta)
        with span("finite_check", final=True, hbm=True):
            check_finite_training(
                last_loss, theta, epoch=p.epochs - 1, chunk=n_steps,
                final=True, estimator="StreamingHashedLinearEstimator")
        if is_sparse_update(optim_resolved):
            # settle the lazy decay the table still owes (rows untouched
            # since their last step) so the returned model equals the
            # dense schedule's — predictions/serving read theta directly
            with span("finalize", hbm=True):
                theta = finalize_lazy_decay(
                    theta, opt_state, p.step_size, p.reg_param,
                    optim_resolved)
        if times is not None:
            st = dict(times)
            # the "encode" spans feed the pipeline's counter (the goodput
            # accountant reads it with OTPU_OBS off too): one sum, two readers
            st["encode_s"] = pipe_stats.encode_s
            # what 'auto' resolved to, so records are self-describing
            st["optim_update"] = optim_resolved
            st["sparse_lowering"] = static_kw["sparse_lowering"]
            # cache economics (io/codec.py): what the HBM cache actually
            # held, and what the same chunks would cost at f32 — the
            # bench's compression_ratio/capacity fields read these
            st["cache_dtype"] = codec.mode if codec else "f32"
            if cache_device:
                st["cache_bytes"] = cache.nbytes
                st["cache_chunks"] = len(cache.batches)
                st["cache_raw_bytes"] = (
                    len(cache.batches)
                    * _raw_chunk_bytes(p, pad_rows))
            st["epoch_s"] = [round(t, 3) for t in epoch_walls]
            if pipe_stats.items:
                # measured prefetch overlap (exec/pipeline.py): 100% = all
                # host prep hidden behind device work, 0% = serial
                st["overlap_pct"] = round(pipe_stats.overlap_pct, 1)
                st["prefetch_prep_s"] = round(pipe_stats.prep_s, 3)
                st["prefetch_wait_s"] = round(pipe_stats.wait_s, 3)
            if replay_fused_s is not None:
                # one wall for ALL replay epochs (single fused dispatch)
                st["replay_fused_s"] = round(replay_fused_s, 3)
            st["cache_overflow"] = cache.degraded
            st["replay_source"] = (
                None if (p.epochs <= 1 and not defer)
                else ("fused" if p.replay_granularity != "epoch"
                      else "fused_epoch") if replay_fused_s is not None
                else "disk" if use_disk
                else "hbm" if cache.enabled
                else "stream"
            )
            # ONE stage dict feeds both consumers: the caller's legacy
            # stage_times= plumbing and the structured run report below
            if report is not None:
                report.stage_times.update(st)
            if stage_times is not None:
                stage_times.update(st)
        model = HashedLinearModel(
            p, theta, salts_np,
            class_values or (tuple(str(i) for i in range(p.n_classes))
                             if p.loss == "logistic" else None),
        )
        model.n_steps_ = n_steps
        model.final_loss_ = float(last_loss) if last_loss is not None else None
        if static_kw["sparse_lowering"] == "sort":
            # the loss above has waited for the last step, so these two
            # scalars of the same program are ready: no wait of their own
            steps, blocks = jax.device_get(
                (opt_state["step"], opt_state["blocks"]))
            steps = int(steps) - counted_from[0]
            note_slot_blocks(
                int(blocks) - counted_from[1],
                steps * slot_blocks(pad_rows, p.n_cat, p.n_dims))
            note_sorts(steps - sorts_saved, steps)
        model.device_chunks_ = cache.batches if cache_device else None
        model.holdout_chunks_ = holdout if holdout_chunks > 0 else None
        model.cache_codec_ = codec   # evaluate_device's decode key
        # where the tables stood when the last step handed them back
        model.table_specs_ = _table_specs(theta, opt_state)
        # ledger: the optimizer slots die with the fit — the entry
        # shrinks to the table itself and lives as long as the model
        # (the abort guard hands ownership to the model's finalizer)
        _state_guard.finalizer.detach()
        prof.ledger_set_tree("model_state", state_key, theta)
        prof.hbm_mark("model_handover")
        import weakref

        weakref.finalize(model, prof.ledger_release_on_gc, "model_state",
                         state_key)
        # freeze the goodput decomposition + the ledger view into the
        # report's goodput/device_memory sections (obs/prof.py);
        # cache_key names THIS fit's cache entry so the bench can
        # cross-check it against the legacy cache_bytes stage key
        prof.attach_fit_report(
            report, acc,
            encode_s=pipe_stats.encode_s, cache_key=cache.ledger_key)
        if report is not None:
            model.run_report_ = report.add(n_steps=n_steps).finish()
        if checkpointer is not None:
            checkpointer.delete()
        return model
