"""Touched-row-only optimizer updates for the hashed embedding hot path.

The Criteo-shaped step touches at most ``batch x n_cat`` embedding rows,
yet the legacy dense-adam path rewrites the FULL table every step: the
optax update sweeps parameter + two moment arrays end to end, and the
in-loss L2 term adds a dense ``reg * emb`` gradient pass on top. At 4M+
hashed dims that dense-update tax IS the replay wall (BENCH_r05:
``replay_fused_s`` 91.25 of 94.28 s, ``pure_step_ms`` 216.76) — the
classic fix in every large-scale sparse-feature stack (lazy/sparse
Adagrad and FTRL from the Google ad-click / Criteo CTR literature) is to
update only the rows the step actually touched.

This module is the one home of that machinery:

* **update rules** — ``sgd`` / ``adagrad`` / ``ftrl``, each available as
  a ``sparse_*`` (touched-row) and ``dense_*`` (full-table twin) lowering
  of the SAME math; per-row f32 accumulator slots (adagrad's ``acc``,
  ftrl's ``z``/``n``) are stored alongside the table and touched just as
  sparsely. ``'adam'`` (the legacy optax path with in-loss L2) stays the
  estimator default and is untouched by this module.
* **within-step index dedup** — per-occurrence gradients are sorted by
  bucket and segment-summed so each touched row is gathered, updated and
  written back exactly once. The sort is STABLE, and a sorted scatter-add
  applies a row's occurrence gradients in their original order — the
  per-row sums are therefore bit-identical to the dense backward's
  scatter-add, which is what makes sparse-vs-dense SGD parity exact.
* **lazy L2 / weight decay** — regularization is decoupled weight decay
  (``p <- (1 - lr*reg) * p - update(g)``). An untouched row's step is a
  pure multiply by ``(1 - lr*reg)``, so the sparse path defers it: a
  per-row last-seen step counter ``t`` lets the next touch apply
  ``(1 - lr*reg)^dt`` at gather time, and ``finalize_lazy_decay`` settles
  the remaining decay once at fit end. Mathematically equivalent to the
  dense per-step schedule (exact power of the same factor; float
  tolerance only from pow-vs-repeated-multiply rounding). FTRL carries
  its own L2 inside the closed-form weight recovery and ignores the
  decay path entirely.
* **one dedup lowering, ``'sort'``** — everything in the step: a stable
  key-value sort + segment ids by ``cumsum`` of boundaries, then gather ->
  rule -> sorted unique scatter over the LIVE prefix of the slots only,
  ``SLOT_BLOCK`` slots a loop trip. Nothing rides the chunk cache. What
  the chip read at 2^29 rows and M = 6.8M occurrences a step (v5e,
  PERF.md §5, §6): a gather out of the 2 GB table costs per INDEX
  (~14 ns) and nothing else, with or without locality — hence the live
  prefix (PR 27), and hence **the forward reads each DISTINCT row once**
  (PR 36: ~0.93M of a Zipf chunk's 6.8M occurrences; the occurrence
  gather was 92 ms of a 243 ms step). **What moves M values is a sort's
  payload, never an M-index gather or an unsorted M-index scatter:**
  XLA's gather costs 7 ns an index from a 1 MB source as from a 27 MB one
  (49 ms for M), a sort of M ``(i32 key, 32-bit payload)`` pairs 12–13 ms
  (PR 31). So the sorted keys are the key sort's own first output; the
  key half hands on ``inv`` — each occurrence's rank, one more sort — so
  that the gradient half carries its M per-occurrence gradients to sorted
  order as the payload of a sort keyed by ``inv`` (a multiclass fit's
  ``k`` columns are ``k`` payloads of the one sort), and ``order``, the
  permutation itself, so that the forward carries the rows' bits the
  other way (``touched_rows``: the distinct rows gathered block by block,
  their bit patterns' differences scattered at the segments' first sorted
  places, a ``uint32`` prefix sum, one sort keyed by ``order`` — exact,
  because sums mod 2^32 are); the update's block loop takes its weight
  rows from that same read. ``uniq`` and ``head`` (a segment's table row
  and first sorted place) come out of a third sort of the key half, which
  compacts the segments' starts to the front.
  The part of the dedup that reads the keys alone (``sort_keys``: the
  three sorts, segment ids) does not change between the
  epochs of a cached chunk, so the fused replay builds that half once per
  chunk and dispatch and hands it to its steps
  (``keys=``): ``sort_keys_bytes`` a chunk of temp in that one program,
  for as long as it runs, taken only where the caller's cache budget
  holds it (``models/hashed_linear._hoist_sort_keys``). ``_hashed_step``
  and a replay without the room sort in the step.
  XLA:CPU prices these the other way round (sorts ~0.3 us a pair,
  gathers nearly free): a full-size step pays five ~2 s sorts there
  (README, sparse-optimizer entry); the CPU runs the chip's program all
  the same.

Layering: this module knows nothing about chunks, hashing or streams —
``models/hashed_linear`` composes it into the step.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from orange3_spark_tpu.obs.registry import REGISTRY

__all__ = [
    "OPTIM_UPDATES", "SPARSE_UPDATES", "DENSE_UPDATES",
    "resolve_optim_update",
    "resolve_sparse_lowering", "optim_kind", "is_sparse_update",
    "init_optim_state", "adopt_optim_state", "plan_slots", "slot_blocks",
    "occurrence_dead", "apply_rule", "dense_update",
    "sort_keys", "sort_slots", "sort_keys_bytes", "touched_rows",
    "sparse_embedding_update", "note_slot_blocks", "note_sorts",
    "finalize_lazy_decay",
]

SPARSE_UPDATES = ("sparse_sgd", "sparse_adagrad", "sparse_ftrl")
DENSE_UPDATES = ("dense_sgd", "dense_adagrad", "dense_ftrl")
OPTIM_UPDATES = ("adam",) + DENSE_UPDATES + SPARSE_UPDATES

#: adagrad denominator floor: sqrt(acc + eps). First touch of a row gives
#: |update| <= lr * |g| / sqrt(g^2) = lr — the standard bounded first step.
ADAGRAD_EPS = 1e-10
#: FTRL-proximal beta (McMahan et al. 2013); alpha is the fit's step_size.
FTRL_BETA = 1.0
#: slots one trip of the 'sort' lowering's gather -> rule -> write-back
#: loop takes. What the chip read (v5e, 2^29-row tables, PERF.md §6 PR 27):
#: a gather costs ~14 ns an index and nothing else, but a scatter costs
#: ~7 ms a CALL — a pass over the 2 GB table, whatever it writes, in every
#: form XLA was given — plus ~5 ns an index. A trip therefore pays ~21 ms
#: for its three write-backs, and the last, partly live block wastes ~55 ns
#: a dead slot: the sum is least near sqrt(0.76e6 x n_live) slots, 2^20
#: for the ~0.93M live slots of a 2^18-row Criteo chunk (1 trip of 7).
SLOT_BLOCK = 1 << 20

_M_SLOT_BLOCKS = REGISTRY.counter(
    "otpu_sparse_slot_blocks_total",
    "'sort'-lowering slot blocks of finished fits: which=run (trips the "
    "steps took, from the chunks' live slots) | possible (steps x "
    "slot_blocks, the static bound). run/possible = share of the slot "
    "bound the steps really gathered, updated and wrote back")
_M_SORTS = REGISTRY.counter(
    "otpu_sparse_sorts_total",
    "'sort'-lowering key halves (sort, segment ids, uniq) of finished "
    "fits: which=run (built: one a step, but one a cached chunk and "
    "dispatch where the fused replay hoists them) | steps (optimizer "
    "steps). run/steps = share of the steps that paid for their own sort")


def resolve_optim_update(value: str) -> str:
    """The concrete update rule for this fit — THE one resolver, applied
    ONCE at fit entry so the resolved value is a static jit argument. The
    ``dense_*`` twins are chosen by name and by nothing else."""
    if value not in OPTIM_UPDATES:
        raise ValueError(
            f"optim_update must be one of {OPTIM_UPDATES}, got {value!r}"
        )
    return value


def resolve_sparse_lowering(value: str) -> str:
    """``'sort'``, on every backend: the one dedup lowering there is (the
    module docstring; on a v5e at 2^29 rows the in-step sort reads 0.06 s
    of a step, PERF.md §5)."""
    if value == "plan":
        raise ValueError(
            "sparse_lowering='plan' was removed in PR 30: 'sort' is the "
            "one dedup lowering there is (pass 'auto' or 'sort')"
        )
    if value not in ("auto", "sort"):
        raise ValueError(
            f"sparse_lowering must be 'auto' | 'sort', got {value!r}"
        )
    return "sort"


def optim_kind(resolved: str) -> str:
    """'adam' | 'sgd' | 'adagrad' | 'ftrl' from a resolved optim_update."""
    if resolved == "adam":
        return "adam"
    return resolved.split("_", 1)[1]


def is_sparse_update(resolved: str) -> bool:
    return resolved in SPARSE_UPDATES


def _rule_slots(kind: str, param):
    if kind == "adagrad":
        return {"acc": jnp.zeros_like(param)}
    if kind == "ftrl":
        return {"z": jnp.zeros_like(param), "n": jnp.zeros_like(param)}
    return {}


def init_optim_state(resolved: str, theta: dict) -> dict:
    """Fresh optimizer state for a non-adam rule: a global step counter,
    the per-row last-seen step vector ``t`` (the lazy-decay timestamps;
    zeros and unused for dense twins and ftrl), per-parameter slot dicts,
    and ``blocks`` — the slot blocks the 'sort' lowering has run so far
    (``sparse_embedding_update``; stays 0 under every other path), summed
    on the device and read once at fit end. ``zeros_like`` inherits each
    parameter's GSPMD placement, so a model-axis-sharded table gets
    sharded slots/timestamps for free."""
    kind = optim_kind(resolved)
    if kind == "adam":
        raise ValueError("'adam' keeps its optax state; no optim state here")
    emb = theta["emb"]
    return {
        "step": jnp.int32(0),
        "blocks": jnp.int32(0),
        # timestamps ride a column slice of zeros_like(emb) so they share
        # the table's sharding (P('model') rows under model parallelism)
        "t": jnp.zeros_like(emb[:, 0], dtype=jnp.int32),
        "slots": {name: _rule_slots(kind, p) for name, p in theta.items()},
    }


def adopt_optim_state(saved: dict) -> dict:
    """A checkpointed non-adam optimizer state as this program's steps
    read it — the one place every resume path (``fit_stream``, the online
    trainer) passes a snapshot through. A snapshot written before the
    state carried ``blocks`` resumes counting from zero."""
    return {"blocks": np.zeros((), np.int32), **saved}


# --------------------------------------------------------------- the rules

def apply_rule(kind: str, p, slots: dict, g, lr, reg, l1):
    """One optimizer-rule application — shared verbatim by the sparse
    touched-row engines (``p``/``slots``/``g`` are gathered [U, k] rows)
    and the dense twins ([D, k] full arrays). Decoupled weight decay is
    the CALLER's job (applied to ``p`` beforehand); ``reg``/``l1`` only
    feed FTRL's closed form. A zero gradient is a no-op for every rule
    (FTRL by induction: the stored weight always equals the closed form
    of its ``z``/``n``), which is what makes dense-twin untouched rows
    and sparse pad slots inert."""
    if kind == "sgd":
        return p - lr * g, slots
    if kind == "adagrad":
        acc = slots["acc"] + g * g
        return p - lr * g * jax.lax.rsqrt(acc + ADAGRAD_EPS), {"acc": acc}
    if kind == "ftrl":
        n, z = slots["n"], slots["z"]
        n2 = n + g * g
        sigma = (jnp.sqrt(n2) - jnp.sqrt(n)) / lr
        z2 = z + g - sigma * p
        shrunk = jnp.sign(z2) * jnp.maximum(jnp.abs(z2) - l1, 0.0)
        p2 = -shrunk / ((FTRL_BETA + jnp.sqrt(n2)) / lr + 2.0 * reg)
        return p2, {"n": n2, "z": z2}
    raise ValueError(f"unknown rule kind {kind!r}")


def dense_update(kind: str, p, slots: dict, g, lr, decay, reg, l1, *,
                 use_decay: bool):
    """Dense twin / small-parameter update: per-step decoupled decay then
    the rule over the full array. The parity baseline every ``sparse_*``
    rule is measured against."""
    if use_decay and kind != "ftrl":
        p = p * decay
    return apply_rule(kind, p, slots, g, lr, reg, l1)


# ------------------------------------------------------- slot arithmetic

def plan_slots(pad_rows: int, n_cat: int, n_dims: int) -> int:
    """Static bound on the per-chunk unique-row count, plus ONE spare slot
    that absorbs the dead-occurrence segment (padding rows / vw idx=-1):
    live segments can number at most min(occurrences, table rows)."""
    return min(pad_rows * n_cat, n_dims) + 1


def slot_blocks(pad_rows: int, n_cat: int, n_dims: int) -> int:
    """Blocks of ``SLOT_BLOCK`` slots that cover a chunk's static slot
    bound: the most trips one 'sort'-lowering step can take (a chunk of
    all-distinct keys), and the denominator of ``otpu_sparse_slot_blocks``.
    A bound under one block is one block of its own size."""
    U = plan_slots(pad_rows, n_cat, n_dims)
    return -(-U // min(SLOT_BLOCK, U))


def note_slot_blocks(run: int, possible: int) -> None:
    """Add one finished fit's block counts to the registry (the fit reads
    them off ``opt_state`` where it already waits for its last loss)."""
    _M_SLOT_BLOCKS.inc(run, which="run")
    _M_SLOT_BLOCKS.inc(possible, which="possible")


def note_sorts(run: int, steps: int) -> None:
    """Add one finished fit's key-half count to the registry: static
    counts the fit keeps on the host as it dispatches."""
    _M_SORTS.inc(run, which="run")
    _M_SORTS.inc(steps, which="steps")


def occurrence_dead(n_rows: int, n_cat: int, n_valid, raw_cats=None):
    """In-jit dead-occurrence mask: rows at or past ``n_valid`` (padding)
    and, in value-weighted mode, pairs whose raw index is negative."""
    dead = (jnp.arange(n_rows, dtype=jnp.int32)[:, None] >= n_valid)
    dead = jnp.broadcast_to(dead, (n_rows, n_cat))
    if raw_cats is not None:
        dead = dead | (raw_cats < 0)
    return dead


# ------------------------------------------------- the touched-row engines

def _touched_rows_update(kind, p_rows, t, slots, sums, rid, lr, decay, reg,
                         l1, step, *, use_decay):
    """Gather the touched rows' slots and timestamps, apply catch-up lazy
    decay and the rule. ``rid`` is one block of the live prefix of the
    touched-row list (-1 on dead slots; gathers clamp, writeback masks),
    ``p_rows`` the same block of the weight rows as the forward read them
    (``touched_rows``: the table is not written between the two). Returns
    the updated rows and slot rows."""
    with jax.named_scope("step/gather"):
        rsafe = jnp.maximum(rid, 0)
        slot_rows = {n: jnp.take(v, rsafe, axis=0) for n, v in slots.items()}
        if use_decay:
            t_rows = jnp.take(t, rsafe)
    with jax.named_scope("step/rule"):
        if use_decay:
            # catch-up for the steps the row sat untouched, PLUS this
            # step's own decay: (1-lr*reg)^(step+1-t) — the exact product
            # the dense schedule applies one factor at a time
            fac = jnp.power(decay, (step + 1 - t_rows).astype(jnp.float32))
            p_rows = p_rows * fac[:, None]
        return apply_rule(kind, p_rows, slot_rows, sums, lr, reg, l1)


def _segment_sums(g_sorted, seg, n_slots: int):
    """Per-segment gradient sums from SORTED per-occurrence gradients —
    a sorted scatter-add, which applies each row's occurrences in their
    original (stable-sort-preserved) order: bit-identical to the dense
    backward's scatter."""
    return jnp.zeros((n_slots,) + g_sorted.shape[1:], g_sorted.dtype).at[
        seg].add(g_sorted, indices_are_sorted=True)


def _to_slots(v, n_slots: int, fill):
    """A compacted ``[M]`` vector as a slot array: cut or padded (with
    ``fill``, the dead value) to the slots' static length."""
    pad = jnp.full((max(n_slots - v.shape[0], 0),), fill, v.dtype)
    return jnp.concatenate([v, pad])[:n_slots]


def sort_keys(idx, n_dims: int, n_slots: int, n_valid, raw_cats=None):
    """The key half of the 'sort' lowering's in-jit dedup — everything
    that reads the chunk's hashed keys and ``n_valid`` and nothing else:
    sort the occurrences (dead ones behind the sentinel ``n_dims``) and
    number the segments in sorted order. Returns ``{'inv': i32[M] each
    occurrence's rank in the stable sort (the inverse of its
    permutation), 'order': i32[M] that permutation itself, sorted place
    -> occurrence, the occurrence named by its place in COLUMN-major
    order (``c * N + i`` for row ``i``, column ``c``: the forward sums
    over the columns, and wants a column's rows side by side),
    'seg': i32[M] each sorted occurrence's segment,
    'uniq': i32[n_slots] table row per segment (-1 on dead/unused slots),
    'head': i32[n_slots] the sorted place at which the segment starts
    (M, out of range, on dead/unused slots), 'n_live': i32[]}``: the dead
    sentinel sorts last, so the live slots are exactly the prefix
    ``[0, n_live)`` of ``uniq`` and ``head``. The sorted keys and the
    permutation are the key sort's own two outputs, ``inv`` is one more sort
    (of the permutation, carrying an iota), and ``uniq`` / ``head`` come
    out of a third: the places in sorted order, those that are not a live
    segment's start pushed behind by their top bit, carrying the sorted
    keys — so its first ``n_live`` pairs are (where the segment starts,
    its table row). On the chip a sort moves M values for a quarter of
    what an M-index gather or an unsorted M-index scatter costs (module
    docstring).
    A cached chunk's keys do not change between epochs, so the fused
    replay builds this once per chunk and dispatch (``sort_keys_bytes``
    is what it then holds) and every step reuses it."""
    N, C = idx.shape
    M = N * C
    with jax.named_scope("step/sort"):
        dead = occurrence_dead(N, C, n_valid, raw_cats)
        flat = jnp.where(dead, jnp.int32(n_dims), idx).reshape(-1)
        iota = jnp.arange(M, dtype=jnp.int32)
        s_idx, order = jax.lax.sort_key_val(flat, iota)   # stable sort
        # a permutation's keys are unique: nothing rests on stability
        _, inv = jax.lax.sort_key_val(order, iota, is_stable=False)
    with jax.named_scope("step/segment"):
        start = jnp.concatenate(
            [jnp.ones((1,), bool), s_idx[1:] != s_idx[:-1]])
        seg = jnp.cumsum(start.astype(jnp.int32)) - 1
        # one slot per live segment, in sorted order: the starts keep
        # their place as their key, every other place (and the dead
        # sentinel's start) sorts behind them all; keys unique again
        behind = jnp.uint32(1 << 31)
        place, row = jax.lax.sort_key_val(
            iota.astype(jnp.uint32) | jnp.where(
                start & (s_idx < n_dims), jnp.uint32(0), behind),
            s_idx, is_stable=False)
        live = place < behind
        head = _to_slots(jnp.where(live, place, M).astype(jnp.int32),
                         n_slots, M)
        uniq = _to_slots(jnp.where(live, row, -1), n_slots, -1)
        # every segment but the dead one
        n_live = seg[-1] + 1 - (s_idx[-1] >= n_dims).astype(jnp.int32)
    return {"inv": inv, "order": order % C * N + order // C, "seg": seg,
            "uniq": uniq, "head": head, "n_live": n_live}


def _slot_block(n_slots: int) -> int:
    """Slots a trip of a loop over the live prefix takes (``sort_slots``
    is a whole number of them)."""
    return min(SLOT_BLOCK, n_slots)


def touched_rows(emb, keys: dict, n_rows: int, n_cat: int):
    """The sparse step's forward read: each DISTINCT touched row of
    ``emb`` (f32) gathered ONCE, and carried to the chunk's ``M = n_rows x
    n_cat`` occurrences with no M-index gather. Returns ``(rows
    f32[n_slots, k], occ f32[n_cat, n_rows, k])``: ``rows[s]`` is
    ``emb[uniq[s]]`` over the live prefix of the slots (the update's
    block loop takes its weight rows from here instead of gathering them
    again), ``occ[c, i]`` holds the bits of ``emb[idx[i, c]]`` for every
    live occurrence — what ``jnp.take(emb, idx.T, axis=0)`` gave, -0.0 and
    denormals included.

    Block by block over the live prefix, as the update walks it: gather
    the block's rows, take their bit patterns' differences from the slot
    before (uint32, wrapping; the previous block's last pattern is the
    loop's carry) and scatter those at the segments' first sorted places
    (``head``: sorted, unique, dead slots dropped) into a zero
    ``uint32[M]`` a logit column. A prefix sum mod 2^32 then leaves at
    every sorted place exactly the bits of its segment's row, and one
    sort keyed by ``order`` (a permutation: unstable) carries them to the
    occurrences' own places, the ``k`` columns as its ``k`` payloads. Dead
    occurrences sort behind every live one and inherit the last live
    row's bits (finite, like the table); their rows weigh nothing."""
    uniq, head = keys["uniq"], keys["head"]
    n_slots, k = uniq.shape[0], emb.shape[1]
    B = _slot_block(n_slots)
    sc = dict(mode="drop", unique_indices=True, indices_are_sorted=True)

    def block(i, carry):
        rows, deltas, last = carry
        rid = jax.lax.dynamic_slice_in_dim(uniq, i * B, B)
        w = jnp.take(emb, jnp.maximum(rid, 0), axis=0)
        bits = jax.lax.bitcast_convert_type(w, jnp.uint32)
        delta = bits - jnp.concatenate([last[None], bits[:-1]])
        at = jax.lax.dynamic_slice_in_dim(head, i * B, B)
        deltas = tuple(d.at[at].set(delta[:, j], **sc)
                       for j, d in enumerate(deltas))
        rows = jax.lax.dynamic_update_slice_in_dim(rows, w, i * B, axis=0)
        return rows, deltas, bits[-1]

    rows, deltas, _ = jax.lax.fori_loop(
        0, (keys["n_live"] + (B - 1)) // B, block,
        (jnp.zeros((n_slots, k), emb.dtype),
         tuple(jnp.zeros((n_rows * n_cat,), jnp.uint32) for _ in range(k)),
         jnp.zeros((k,), jnp.uint32)))
    _, *cols = jax.lax.sort(
        (keys["order"], *(jnp.cumsum(d, dtype=jnp.uint32) for d in deltas)),
        num_keys=1, is_stable=False)
    occ = jax.lax.bitcast_convert_type(jnp.stack(cols, axis=1), emb.dtype)
    return rows, occ.reshape(n_cat, n_rows, k)


def _sorted_sums(dl, vals, keys: dict, n_cat: int):
    """The gradient half: the per-occurrence gradients ``dl[row] (* val)``
    carried to ``sort_keys``' order and summed per segment. ``inv`` is a
    permutation of ``0..M-1``, so a sort keyed by it is that permutation
    applied to its payloads, one per logit column: ``g[j]`` is the value
    of the occurrence the stable sort put in place ``j``, what
    ``jnp.take(dl, order // n_cat)`` gave, bit for bit."""
    with jax.named_scope("step/segment"):
        n, k = dl.shape
        # the occurrences in their ORIGINAL order need no index: row i's
        # gradient stands at [i, :, j]
        g = jnp.broadcast_to(dl[:, None, :], (n, n_cat, k))
        if vals is not None:
            g = g * vals[:, :, None]
        _, *cols = jax.lax.sort(
            (keys["inv"], *(g[:, :, j].reshape(-1) for j in range(k))),
            num_keys=1, is_stable=False)
        g = jnp.stack(cols, axis=1)
        return _segment_sums(g, keys["seg"], keys["uniq"].shape[0])


def sort_slots(pad_rows: int, n_cat: int, n_dims: int) -> int:
    """Length of the 'sort' lowering's slot arrays: the static bound
    ``plan_slots`` rounded up to a whole number of blocks, so the last
    block's slice never has to be clamped back over its neighbour (the
    pad slots are -1 / zero: dead like any other)."""
    return slot_blocks(pad_rows, n_cat, n_dims) * min(
        SLOT_BLOCK, plan_slots(pad_rows, n_cat, n_dims))


def sort_keys_bytes(pad_rows: int, n_cat: int, n_dims: int) -> int:
    """Device bytes of one chunk's ``sort_keys`` (its five i32 arrays:
    ``inv``, ``order``, ``seg`` of the occurrences' length, ``uniq`` and
    ``head`` of the slots') — what the fused replay holds per cached chunk
    while it runs, and what its caller's budget is asked for."""
    return 4 * (3 * pad_rows * n_cat
                + 2 * sort_slots(pad_rows, n_cat, n_dims))


def sparse_embedding_update(kind, emb, t, slots, dl, idx, lr, decay, reg, l1,
                            step, *, use_decay: bool, n_valid=None,
                            raw_cats=None, vals=None, keys=None, rows=None):
    """One touched-row-only table update. ``dl`` is the [N, k] logits
    gradient; per-occurrence gradients are ``dl[row] (* val)``.

    The dedup is derived in-jit (key-value sort + cumsum-of-boundaries) — or,
    where the caller has already run ``sort_keys`` over this chunk (the
    step, ahead of its forward; the fused replay, once per chunk and
    dispatch), handed in as ``keys`` and only the gradient half computed
    here: the same operations on the same values either way. Likewise
    ``rows``: the touched weight rows as the step's forward read them
    (``touched_rows``) — the table is not written between that read and
    this update, so the block loop slices its weight rows out of them and
    gathers only the rule's slots and the timestamps. The slot arrays keep
    the static bound ``plan_slots`` (a chunk of all-distinct keys fills
    it), but only their live prefix is gathered, run through the rule and
    written back: a ``fori_loop`` over blocks of
    ``SLOT_BLOCK`` slots whose trip count ``ceil(n_live / SLOT_BLOCK)`` is
    computed on the device from the chunk's own keys. Each trip's
    writeback is a sorted unique scatter with out-of-range dead slots
    dropped; the tables are the loop's carries, updated in place.

    Returns ``(emb, t, slots, n_blocks)``: ``n_blocks`` is the i32 count
    of trips this update ran.

    Phases, as ``jax.named_scope``s a device trace is read by:
    ``step/sort``, ``step/segment``, ``step/gather``, ``step/rule``,
    ``step/scatter``; the last three sit inside the block loop, so a trace
    shows them once per trip (``.../while/body/step/gather/...``)."""
    D = emb.shape[0]
    N, C = idx.shape
    if keys is None:
        keys = sort_keys(idx, D, sort_slots(N, C, D), n_valid, raw_cats)
    if rows is None:
        rows, _ = touched_rows(emb, keys, N, C)
    sums = _sorted_sums(dl, vals, keys, C)
    uniq = keys["uniq"]
    B = _slot_block(uniq.shape[0])
    n_blocks = (keys["n_live"] + (B - 1)) // B
    sc = dict(mode="drop", unique_indices=True, indices_are_sorted=True)

    def block(i, tables):
        emb, t, slots = tables
        rid = jax.lax.dynamic_slice_in_dim(uniq, i * B, B)
        p_rows, slot_rows = _touched_rows_update(
            kind, jax.lax.dynamic_slice_in_dim(rows, i * B, B), t, slots,
            jax.lax.dynamic_slice_in_dim(sums, i * B, B),
            rid, lr, decay, reg, l1, step, use_decay=use_decay)
        with jax.named_scope("step/scatter"):
            wb = jnp.where(rid >= 0, rid, D)              # D drops
            emb = emb.at[wb].set(p_rows, **sc)
            slots = {n: slots[n].at[wb].set(v, **sc)
                     for n, v in slot_rows.items()}
            if use_decay:
                t = t.at[wb].set(step + 1, **sc)
        return emb, t, slots

    # slots -> rule -> write-back over the live prefix only, B slots a
    # trip, the trip count read off the chunk itself; the tables are the
    # loop's carries and are updated in place
    emb, t, slots = jax.lax.fori_loop(0, n_blocks, block, (emb, t, slots))
    return emb, t, slots, n_blocks


def finalize_lazy_decay(theta: dict, state: dict, lr: float, reg: float,
                        resolved: str) -> dict:
    """Settle the decay a sparse-trained table still owes: rows untouched
    since step ``t`` get their trailing ``(1-lr*reg)^(step-t)`` in one
    pass at fit end, after which the table equals the dense schedule's.
    No-op for dense twins (they decay every step), FTRL (closed-form L2),
    and reg == 0."""
    kind = optim_kind(resolved)
    if (not is_sparse_update(resolved) or kind == "ftrl" or reg == 0
            or lr == 0):
        return theta
    theta = dict(theta)
    theta["emb"] = _finalize_emb(
        theta["emb"], state["t"], state["step"],
        jnp.float32(1.0 - lr * reg))
    return theta


@jax.jit
@jax.named_scope("finalize/decay")
def _finalize_emb(emb, t, step, decay):
    fac = jnp.power(decay, (step - t).astype(jnp.float32))
    return emb * fac[:, None]
