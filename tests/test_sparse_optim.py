"""Sparse touched-row optimizer subsystem (optim/) — parity against the
dense twins, lazy-decay equivalence, edge cases, replay-path parity, and
the recompile-regression guard.

Parity contract (docs/optim.md): the sparse and dense lowerings of one
rule are the SAME math. The stable sort + ordered segment scatter make
the per-row gradient sums bit-identical to the dense backward's
scatter-add, so sparse-vs-dense SGD without decay agrees to XLA fusion
rounding (<= a few ulps; observed ~1e-9 after dozens of steps — bitwise
equality across two different XLA programs is not guaranteed). Lazy decay
replaces N per-step multiplies by one pow of the same factor, so the
decay'd comparisons carry a slightly looser tolerance."""

import os
import re
import zlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from orange3_spark_tpu.io.streaming import array_chunk_source
from orange3_spark_tpu.models.hashed_linear import (
    StreamingHashedLinearEstimator,
)
from orange3_spark_tpu.obs.registry import REGISTRY
from orange3_spark_tpu.ops.hashing import (
    column_salts, hash_columns, hash_columns_np,
)
from orange3_spark_tpu.optim import sparse as sparse_mod
from orange3_spark_tpu.optim.sparse import (
    plan_slots, resolve_optim_update, resolve_sparse_lowering,
    slot_blocks, sparse_embedding_update,
)

from tests.test_hashed_linear import _criteo_shaped

BASE = dict(n_dims=1 << 12, n_dense=4, n_cat=6, epochs=4, step_size=0.05,
            chunk_rows=1024)


def _fit(session, Xall, y, **kw):
    params = dict(BASE)
    params.update(kw)
    fit_kw = {k: params.pop(k) for k in
              ("cache_device_bytes", "cache_spill_dir", "stage_times",
               "checkpointer") if k in params}
    est = StreamingHashedLinearEstimator(**params)
    return est.fit_stream(
        array_chunk_source(Xall, y, chunk_rows=1000),
        session=session, cache_device=True, **fit_kw)


@pytest.fixture(scope="module")
def data():
    return _criteo_shaped(4096, seed=21)


# ------------------------------------------------------------ host hashing

def test_host_hash_matches_device_hash():
    """The packed codec hashes on the HOST; one bit of drift against the
    in-jit hash silently updates the wrong table rows."""
    rng = np.random.default_rng(3)
    salts = column_salts(5, seed=7)
    # exercise negatives (vw -1 padding), zero (the reserved missing
    # code), and the f32 carrier dtype the chunk pipeline ships
    cats = rng.integers(-2, 200_000, size=(500, 5)).astype(np.float32)
    cats[0] = 0.0
    for D in (1, 256, 1 << 20):
        np.testing.assert_array_equal(
            hash_columns_np(cats, salts, D),
            np.asarray(hash_columns(jnp.asarray(cats), salts, D)))


# ------------------------------------------------------- parity vs twins

def _emb_diff(a, b):
    return float(np.max(np.abs(
        np.asarray(a.theta["emb"]) - np.asarray(b.theta["emb"]))))


@pytest.mark.parametrize("optim", ["sgd", "adagrad"])
def test_sparse_sgd_matches_dense_sgd_no_decay(session, data, optim):
    """The headline exactness claim: without decay, the sparse step's
    per-row sums are the dense backward's sums in the same order — so SGD
    agrees to fusion rounding, and Adagrad (the same sums through one
    rsqrt) nearly as closely."""
    Xall, y = data
    dense = _fit(session, Xall, y, optim_update=f"dense_{optim}")
    sparse = _fit(session, Xall, y, optim_update=f"sparse_{optim}")
    assert _emb_diff(sparse, dense) <= {"sgd": 5e-9, "adagrad": 1e-7}[optim]
    np.testing.assert_allclose(
        np.asarray(sparse.theta["coef"]), np.asarray(dense.theta["coef"]),
        rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("optim", ["sgd", "adagrad"])
def test_lazy_decay_equivalence(session, data, optim):
    """reg > 0: the sparse path applies (1-lr*reg)^dt lazily + a finalize
    sweep; the dense twin multiplies per step. Same product, pow-rounding
    tolerance only."""
    Xall, y = data
    dense = _fit(session, Xall, y, optim_update=f"dense_{optim}",
                 reg_param=1e-3)
    sparse = _fit(session, Xall, y, optim_update=f"sparse_{optim}",
                  reg_param=1e-3)
    assert _emb_diff(sparse, dense) < 1e-6


def test_sparse_ftrl_matches_dense_ftrl(session, data):
    Xall, y = data
    dense = _fit(session, Xall, y, optim_update="dense_ftrl",
                 reg_param=1e-3, l1_param=1e-4)
    sparse = _fit(session, Xall, y, optim_update="sparse_ftrl",
                  reg_param=1e-3, l1_param=1e-4)
    assert _emb_diff(sparse, dense) < 1e-7
    # l1 shrinkage really produces exact zeros on rarely-hit rows
    emb = np.asarray(sparse.theta["emb"])
    assert (emb == 0.0).any()


def test_sparse_learns_like_dense(session, data):
    """Quality smoke: the sparse path is not just self-consistent — it
    trains a model as good as its dense twin's."""
    Xall, y = data
    m = _fit(session, Xall, y, optim_update="sparse_adagrad", epochs=6,
             step_size=0.1)
    acc = np.mean(m.predict(Xall) == y)
    assert acc > 0.85, acc


# ------------------------------------------------------------- edge cases

def test_all_pad_batch_is_inert(session):
    """A chunk with n_valid=0 (all padding) must be a training no-op under
    the sparse path — same final table as the stream without it. The empty
    trailing batch exercises the 'empty batch' edge at ingest."""
    Xall, y = _criteo_shaped(2048, seed=22)
    kw = dict(optim_update="sparse_adagrad", reg_param=1e-3, epochs=3)

    def with_pad_gap():
        # a source whose middle chunk is 0 live rows: _rechunk drops empty
        # arrays, so emulate via an all-zero-weight chunk
        yield Xall[:1024], y[:1024], np.ones(1024, np.float32)
        yield Xall[:8], y[:8], np.zeros(8, np.float32)
        yield Xall[1024:2048], y[1024:2048], np.ones(1024, np.float32)

    est = StreamingHashedLinearEstimator(**{**BASE, **kw})
    m1 = est.fit_stream(lambda: with_pad_gap(), session=session,
                        cache_device=True)
    est2 = StreamingHashedLinearEstimator(**{**BASE, **kw})
    m2 = est2.fit_stream(
        array_chunk_source(Xall[:2048], y[:2048], chunk_rows=1024),
        session=session, cache_device=True)
    # the zero-weight rows contribute zero gradient; step counts differ
    # (the dead chunk still ticks the decay clock) so compare against the
    # dense twin of the SAME stream instead of bitwise across streams
    est3 = StreamingHashedLinearEstimator(
        **{**BASE, **kw, "optim_update": "dense_adagrad"})
    m3 = est3.fit_stream(lambda: with_pad_gap(), session=session,
                         cache_device=True)
    assert _emb_diff(m1, m3) < 1e-6
    assert m1.n_steps_ == m2.n_steps_ + 3  # the w=0 chunk did dispatch


def test_every_index_colliding_into_one_bucket(session):
    """n_dims=1: every occurrence lands in bucket 0 — one segment of
    maximal length, the degenerate end of the dedup."""
    Xall, y = _criteo_shaped(1024, seed=23)
    for optim in ("dense_adagrad", "sparse_adagrad"):
        m = _fit(session, Xall, y, n_dims=1, optim_update=optim,
                 reg_param=1e-3, epochs=2)
        assert np.isfinite(np.asarray(m.theta["emb"])).all()
        if optim == "sparse_adagrad":
            sparse = m
        else:
            dense = m
    assert _emb_diff(sparse, dense) < 1e-6


def test_value_weighted_idx_minus_one_inert(session):
    """vw mode: (idx=-1, val=0) padding pairs must update nothing — parity
    with the dense twin, and with the same data minus the pad pairs."""
    rng = np.random.default_rng(24)
    n, C, D = 2000, 4, 1 << 10
    idxs = rng.integers(0, 40, (n, C)).astype(np.float32)
    vals = rng.uniform(0.5, 1.5, (n, C)).astype(np.float32)
    idxs[: n // 2, -1] = -1.0
    vals[: n // 2, -1] = 0.0
    y = (idxs[:, 0] % 3 == 0).astype(np.float32)
    X = np.concatenate([idxs, vals], axis=1)
    kw = dict(n_dims=D, n_dense=0, n_cat=C, value_weighted=True,
              epochs=3, step_size=0.1, chunk_rows=512, reg_param=1e-3)
    out = {}
    for optim in ("dense_adagrad", "sparse_adagrad"):
        est = StreamingHashedLinearEstimator(**kw, optim_update=optim)
        out[optim] = est.fit_stream(
            array_chunk_source(X, y, chunk_rows=512), session=session,
            cache_device=True)
    assert _emb_diff(out["sparse_adagrad"], out["dense_adagrad"]) < 1e-6
    # the hash bucket of raw -1 gained nothing but (possibly) decay: its
    # adagrad accumulator must be exactly zero in both paths
    pad_bucket = int(hash_columns_np(
        np.full((1, C), -1.0, np.float32), out["sparse_adagrad"].salts,
        D)[0, -1])
    live = set(hash_columns_np(
        idxs, out["sparse_adagrad"].salts, D)[idxs >= 0].ravel().tolist())
    if pad_bucket not in live:
        emb = np.asarray(out["sparse_adagrad"].theta["emb"])
        dense_emb = np.asarray(out["dense_adagrad"].theta["emb"])
        np.testing.assert_allclose(emb[pad_bucket], dense_emb[pad_bucket],
                                   atol=1e-7)


# ------------------------------------------- the 'sort' lowering's block loop

def _sorted_slots_whole(dl, idx, n_dims, n_slots, n_valid, raw_cats, vals):
    """The 'sort' lowering's dedup as ONE function of keys and gradients,
    as it was before its key half (``sort_keys``) and its gradient half
    (``_sorted_sums``) were told apart so that a replay can build the
    first once per chunk. Kept here, not in the package, as what the two
    halves composed must equal bit for bit."""
    N, C = idx.shape
    dead = sparse_mod.occurrence_dead(N, C, n_valid, raw_cats)
    flat = jnp.where(dead, jnp.int32(n_dims), idx).reshape(-1)
    order = jnp.argsort(flat)
    s_idx = jnp.take(flat, order)
    g = jnp.take(dl, order // C, axis=0)
    if vals is not None:
        g = g * jnp.take(vals.reshape(-1), order)[:, None]
    start = jnp.concatenate([jnp.ones((1,), bool), s_idx[1:] != s_idx[:-1]])
    seg = jnp.cumsum(start.astype(jnp.int32)) - 1
    sums = sparse_mod._segment_sums(g, seg, n_slots)
    uniq = jnp.full((n_slots,), -1, jnp.int32).at[
        jnp.where(start & (s_idx < n_dims), seg, n_slots)
    ].set(s_idx.astype(jnp.int32), mode="drop")
    n_live = seg[-1] + 1 - (s_idx[-1] >= n_dims).astype(jnp.int32)
    return sums, uniq, n_live


def _unblocked_sort_update(kind, emb, t, slots, dl, idx, lr, decay, reg, l1,
                           step, *, use_decay, n_valid, raw_cats, vals):
    """The 'sort' lowering as it was before the block loop: one gather ->
    rule -> write-back over the whole static slot bound. Kept here, not in
    the package, as what the blocked form must equal bit for bit."""
    D = emb.shape[0]
    sums, uniq, _n_live = _sorted_slots_whole(
        dl, idx, D, plan_slots(*idx.shape, D), n_valid, raw_cats, vals)
    p_rows, slot_rows = sparse_mod._touched_rows_update(
        kind, jnp.take(emb, jnp.maximum(uniq, 0), axis=0), t, slots, sums,
        uniq, lr, decay, reg, l1, step, use_decay=use_decay)
    wb = jnp.where(uniq >= 0, uniq, D)
    sc = dict(mode="drop", unique_indices=True, indices_are_sorted=True)
    emb = emb.at[wb].set(p_rows, **sc)
    slots = {n: slots[n].at[wb].set(v, **sc) for n, v in slot_rows.items()}
    if use_decay:
        t = t.at[wb].set(step + 1, **sc)
    return emb, t, slots


_BLOCK = 8                        # the injected SLOT_BLOCK
#: case -> (n_dims, n_valid, distinct live rows asked for); 12 rows x 4
#: columns, 2 of the rows padding and 4 pairs dead by raw index < 0, so 36
#: live occurrences. U = min(48, n_dims) + 1.
_LIVE_CASES = {
    "n_live=0": (64, 0, 0),
    "n_live=1": (64, 10, 1),
    "n_live=2B-1": (64, 10, 2 * _BLOCK - 1),
    "n_live=2B": (64, 10, 2 * _BLOCK),
    "n_live=2B+1": (64, 10, 2 * _BLOCK + 1),
    # every table row touched: n_dims < occurrences, n_live = U - 1
    "n_live=U-1": (30, 10, 30),
}


@pytest.mark.parametrize("case", list(_LIVE_CASES))
@pytest.mark.parametrize("use_decay", [False, True], ids=["nodecay", "decay"])
@pytest.mark.parametrize("kind", ["sgd", "adagrad", "ftrl"])
def test_blocked_update_equals_unblocked_bitwise(monkeypatch, kind,
                                                 use_decay, case):
    """The block loop over the live prefix changes which slots are
    visited, never what a live slot computes: emb, every slot table and t
    equal the whole-bound form bit for bit, and the trip count is
    ceil(n_live / SLOT_BLOCK) — with padding rows (n_valid < N) and
    value-weighted dead pairs (raw index < 0) among the occurrences."""
    monkeypatch.setattr(sparse_mod, "SLOT_BLOCK", _BLOCK)
    D, n_valid, n_live = _LIVE_CASES[case]
    N, C, k = 12, 4, 2
    rng = np.random.default_rng(
        zlib.crc32(f"{kind}/{use_decay}/{case}".encode()))
    raw = rng.integers(0, 1000, (N, C)).astype(np.float32)
    raw[rng.permutation(10)[:4], rng.integers(0, C, 4)] = -1.0   # vw dead
    live = (np.arange(N)[:, None] < n_valid) & (raw >= 0)
    idx = rng.integers(0, D, (N, C)).astype(np.int32)   # dead: anything
    if n_live:
        pool = rng.permutation(D)[:n_live]
        # each pool row at least once, the rest drawn from the pool
        occ = np.concatenate(
            [pool, rng.choice(pool, int(live.sum()) - n_live)])
        idx[live] = rng.permutation(occ)
        assert len(set(idx[live].tolist())) == n_live
    step = jnp.int32(7)
    emb = jnp.asarray(rng.normal(size=(D, k)), jnp.float32)
    t = jnp.asarray(rng.integers(0, 8, D), jnp.int32)
    slots = {n: jnp.asarray(rng.uniform(0.1, 2.0, (D, k)), jnp.float32)
             for n in {"sgd": (), "adagrad": ("acc",),
                       "ftrl": ("z", "n")}[kind]}
    dl = jnp.asarray(rng.normal(size=(N, k)), jnp.float32)
    vals = jnp.asarray(rng.uniform(0.5, 1.5, (N, C)), jnp.float32)
    args = (emb, t, slots, dl, jnp.asarray(idx), jnp.float32(0.05),
            jnp.float32(1 - 0.05 * 1e-2), jnp.float32(1e-2),
            jnp.float32(1e-3), step)
    kw = dict(use_decay=use_decay, n_valid=jnp.int32(n_valid),
              raw_cats=jnp.asarray(raw), vals=vals)
    got = jax.jit(lambda *a: sparse_embedding_update(
        kind, *a, **kw))(*args)
    want = jax.jit(lambda *a: _unblocked_sort_update(kind, *a, **kw))(*args)
    assert int(got[3]) == -(-n_live // _BLOCK)
    assert int(got[3]) <= slot_blocks(N, C, D) == -(-plan_slots(N, C, D)
                                                    // _BLOCK)
    for a, b in zip(jax.tree.leaves(got[:3]), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if n_live:          # something moved, and only on touched rows
        moved = np.any(np.asarray(got[0]) != np.asarray(emb), axis=1)
        assert moved.any() and set(np.where(moved)[0]) <= set(
            idx[live].tolist())


#: case -> (n_dims, n_valid of 12 rows, how the 12 x 4 keys are drawn,
#: value-weighted pairs with dead raw indices)
_KEY_CASES = {
    "padding-rows": (64, 7, "random", False),
    "all-padding": (64, 0, "random", False),
    "vw-dead-pairs": (64, 10, "random", True),
    "vw-dead-and-padding": (64, 5, "random", True),
    "all-distinct": (256, 12, "distinct", False),
    "all-distinct-vw": (256, 12, "distinct", True),
    "all-equal": (64, 12, "equal", False),
    "all-equal-padding-vw": (64, 9, "equal", True),
    "table-smaller-than-chunk": (16, 12, "random", False),
}


@pytest.mark.parametrize("k", [1, 3], ids=["k=1", "k=3"])
@pytest.mark.parametrize("case", list(_KEY_CASES))
def test_key_half_and_gradient_half_equal_the_whole_bitwise(case, k):
    """``sort_keys`` (keys and n_valid only) then ``_sorted_sums``
    (gradients, carried to the keys' order as the k payloads of one sort)
    is the dedup that used to be one function of gathers (``jnp.take(dl, order // C)``, kept in
    ``_sorted_slots_whole`` as the oracle): sums, uniq and n_live equal
    it bit for bit, with the keys built in the step or handed in (as the
    fused replay builds them, in a program part of their own ahead of its
    scan)."""
    D, n_valid, draw, vw = _KEY_CASES[case]
    N, C = 12, 4
    rng = np.random.default_rng(zlib.crc32(f"{case}/{k}".encode()))
    idx = {"random": lambda: rng.integers(0, D, (N, C)),
           "distinct": lambda: rng.permutation(D)[:N * C].reshape(N, C),
           "equal": lambda: np.full((N, C), 5)}[draw]().astype(np.int32)
    raw = vals = None
    if vw:
        raw = rng.integers(0, 1000, (N, C)).astype(np.float32)
        raw[rng.permutation(N)[:5], rng.integers(0, C, 5)] = -1.0
        raw = jnp.asarray(raw)
        vals = jnp.asarray(rng.uniform(0.5, 1.5, (N, C)), jnp.float32)
    dl = jnp.asarray(rng.normal(size=(N, k)), jnp.float32)
    idx, nv = jnp.asarray(idx), jnp.int32(n_valid)
    n_slots = sparse_mod.sort_slots(N, C, D)
    assert n_slots == plan_slots(N, C, D)    # under one block: no pad slots
    want = jax.jit(lambda dl, idx, nv: _sorted_slots_whole(
        dl, idx, D, n_slots, nv, raw, vals))(dl, idx, nv)

    def halves(dl, idx, nv, keys=None):
        if keys is None:
            keys = sparse_mod.sort_keys(idx, D, n_slots, nv, raw)
        return (sparse_mod._sorted_sums(dl, vals, keys, C), keys["uniq"],
                keys["n_live"])

    keys = jax.jit(lambda idx, nv: sparse_mod.sort_keys(
        idx, D, n_slots, nv, raw))(idx, nv)
    assert {n: (v.shape, v.dtype.name) for n, v in keys.items()} == {
        "inv": ((N * C,), "int32"), "order": ((N * C,), "int32"),
        "seg": ((N * C,), "int32"), "uniq": ((n_slots,), "int32"),
        "head": ((n_slots,), "int32"), "n_live": ((), "int32")}
    for got in (jax.jit(halves)(dl, idx, nv),
                jax.jit(halves)(dl, idx, nv, keys)):
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    live = np.asarray(idx)[:n_valid][
        np.asarray(raw)[:n_valid] >= 0 if vw else slice(None)]
    assert int(want[2]) == len(set(live.ravel().tolist()))


@pytest.mark.parametrize("case", list(_KEY_CASES))
def test_sort_keys_invariants(case):
    """``sort_keys`` against a numpy oracle of the same keys: ``inv`` is
    each occurrence's rank in the STABLE sort (occurrences of one row keep
    their original order — the exactness contract) and ``order`` the
    permutation it inverts, dead occurrences come last, segment ids are
    dense and non-decreasing, ``uniq`` is strictly increasing over
    ``[0, n_live)`` and -1 after, ``head`` is each live segment's first
    sorted place and M (out of range) after, ``n_live`` is the number of
    distinct live keys, and ``sort_keys_bytes`` is what the dict's arrays
    hold."""
    D, n_valid, draw, vw = _KEY_CASES[case]
    N, C = 12, 4
    rng = np.random.default_rng(zlib.crc32(f"inv/{case}".encode()))
    idx = {"random": lambda: rng.integers(0, D, (N, C)),
           "distinct": lambda: rng.permutation(D)[:N * C].reshape(N, C),
           "equal": lambda: np.full((N, C), 5)}[draw]().astype(np.int32)
    dead = np.broadcast_to(np.arange(N)[:, None] >= n_valid, (N, C)).copy()
    raw = None
    if vw:
        raw = rng.integers(0, 1000, (N, C)).astype(np.float32)
        raw[rng.permutation(N)[:5], rng.integers(0, C, 5)] = -1.0
        dead |= raw < 0
    n_slots = sparse_mod.sort_slots(N, C, D)
    keys = jax.device_get(jax.jit(lambda idx, nv: sparse_mod.sort_keys(
        idx, D, n_slots, nv, None if raw is None else jnp.asarray(raw)))(
        jnp.asarray(idx), jnp.int32(n_valid)))
    assert set(keys) == {"inv", "order", "seg", "uniq", "head", "n_live"}
    assert sparse_mod.sort_keys_bytes(N, C, D) == sum(
        v.nbytes for n, v in keys.items() if n != "n_live")
    inv, seg, uniq, n_live = (keys[n] for n in
                              ("inv", "seg", "uniq", "n_live"))
    flat = np.where(dead, D, idx).reshape(-1)
    order = np.argsort(flat, kind="stable")
    np.testing.assert_array_equal(inv, np.argsort(order))
    # ... each occurrence (row i, column c) named by its column-major place
    np.testing.assert_array_equal(keys["order"], order % C * N + order // C)
    s_idx = flat[order]
    n_dead = int(dead.sum())
    assert (s_idx[len(s_idx) - n_dead:] == D).all()         # dead ones last
    assert (s_idx[:len(s_idx) - n_dead] < D).all()
    # one segment a distinct key, numbered in sorted order from 0
    assert seg[0] == 0 and set(np.diff(seg).tolist()) <= {0, 1}
    np.testing.assert_array_equal(np.diff(seg) == 1, s_idx[1:] != s_idx[:-1])
    for k in range(seg[-1] + 1):          # stable inside every segment
        assert (np.diff(order[seg == k]) > 0).all()
    live = np.unique(idx[~dead])
    assert n_live == len(live)
    np.testing.assert_array_equal(uniq[:n_live], live)   # strictly increasing
    assert (uniq[n_live:] == -1).all()
    head = keys["head"]
    np.testing.assert_array_equal(
        head[:n_live], np.searchsorted(seg, np.arange(n_live)))
    assert (head[n_live:] == N * C).all()


#: weights the bit-delta forward must carry untouched: both zeros,
#: denormals, the largest and smallest normals, +-3e38 — drawn at random
#: beside ordinary values, so that neighbouring slots' bit patterns differ
#: by more than 2^31 either way and the running sum wraps past 2^32
_SPECIAL = np.array(
    [-0.0, 0.0, 1e-45, -1e-45, 1e-40, -1e-40, 3e38, -3e38,
     np.finfo(np.float32).tiny, -np.finfo(np.float32).tiny,
     np.finfo(np.float32).max, -np.finfo(np.float32).max], np.float32)


def _bits(a):
    return np.ascontiguousarray(np.asarray(a, np.float32)).view(np.uint32)


def _gathered_rows(emb, keys, n_rows, n_cat):
    """``touched_rows`` as gathers: the distinct rows by ``uniq``, then
    every sorted occurrence's row by its segment, put at the occurrence's
    own place — kept here, not in the package, as what the scatter, the
    prefix sum and the sort must equal bit for bit."""
    rows = jnp.take(emb, jnp.maximum(keys["uniq"], 0), axis=0)
    occ = jnp.zeros((n_rows * n_cat, emb.shape[1]), emb.dtype).at[
        keys["order"]].set(jnp.take(rows, keys["seg"], axis=0))
    return rows, occ.reshape(n_cat, n_rows, -1)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("k", [1, 3], ids=["k=1", "k=3"])
@pytest.mark.parametrize("case", list(_KEY_CASES))
def test_forward_from_one_read_of_each_row_equals_the_gather(monkeypatch,
                                                            case, k, dtype):
    """The sparse step's forward (``_touched_logits``: each distinct row
    read once, its bits carried to the occurrences by a delta scatter, a
    uint32 prefix sum and a sort) against the gather it replaces. Every
    live occurrence holds its row's bits — -0.0, denormals, +-3e38 and
    neighbours whose patterns wrap when differenced included — the rows
    handed to the update are the table's, and the logits equal bit for
    bit those of the same sums over GATHERED rows (``_gathered_rows``).
    Against ``_hashed_logits`` itself (what ``predict`` and the dense
    twins run: another program, whose fused gather-and-reduce XLA:CPU
    adds in its own order, with its own multiply-adds) the live rows'
    logits agree to float32 rounding. ``SLOT_BLOCK`` is 16, so the live
    prefix of the larger cases spans 2-3 trips and the delta's carry
    crosses a block."""
    import orange3_spark_tpu.models.hashed_linear as hl

    monkeypatch.setattr(sparse_mod, "SLOT_BLOCK", 16)
    D, n_valid, draw, vw = _KEY_CASES[case]
    N, C, n_dense = 12, 4, 0 if vw else 3
    rng = np.random.default_rng(zlib.crc32(f"fwd/{case}/{k}".encode()))
    idx = {"random": lambda: rng.integers(0, D, (N, C)),
           "distinct": lambda: rng.permutation(D)[:N * C].reshape(N, C),
           "equal": lambda: np.full((N, C), 5)}[draw]().astype(np.int32)
    live = np.broadcast_to(np.arange(N)[:, None] < n_valid, (N, C)).copy()
    raw = vals = None
    if vw:
        raw = rng.integers(0, 1000, (N, C)).astype(np.float32)
        raw[rng.permutation(N)[:5], rng.integers(0, C, 5)] = -1.0
        vals = rng.uniform(0.5, 1.5, (N, C)).astype(np.float32)
        vals[raw < 0] = 0.0               # the (-1, 0) padding convention
        live &= raw >= 0
        raw, vals = jnp.asarray(raw), jnp.asarray(vals)
    tame = rng.normal(size=(D, k)).astype(np.float32)
    emb = tame.copy()
    at = rng.random((D, k)) < 0.5
    emb[at] = rng.choice(_SPECIAL, int(at.sum()))
    theta = {"emb": jnp.asarray(emb),
             "coef": jnp.asarray(rng.normal(size=(n_dense, k)), jnp.float32),
             "intercept": jnp.asarray(rng.normal(size=(k,)), jnp.float32)}
    dense = jnp.asarray(rng.normal(size=(N, n_dense)), jnp.float32)
    idx_d, nv = jnp.asarray(idx), jnp.int32(n_valid)
    n_slots = sparse_mod.sort_slots(N, C, D)
    assert n_slots % 16 == 0 and n_slots > 16        # a loop of blocks
    cd = jnp.dtype(dtype)

    def forward(theta, dense, idx, nv):
        keys = sparse_mod.sort_keys(idx, D, n_slots, nv, raw)
        rows, occ = hl.touched_rows(theta["emb"], keys, N, C)
        _, logits = hl._touched_logits(theta, dense, keys, (N, C), cd, vals)
        return keys, rows, occ, logits

    keys, rows, occ, logits = jax.device_get(
        jax.jit(forward)(theta, dense, idx_d, nv))
    n_live = int(keys["n_live"])
    assert n_live == len(set(idx[live].tolist()))
    assert -(-n_live // 16) >= {"all-distinct": 3, "all-distinct-vw": 3,
                                "padding-rows": 2, "vw-dead-pairs": 2,
                                }.get(case, 0)        # trips of the loop
    np.testing.assert_array_equal(_bits(rows[:n_live]),
                                  _bits(emb[keys["uniq"][:n_live]]))
    assert occ.shape == (C, N, k)
    np.testing.assert_array_equal(_bits(occ)[live.T],
                                  _bits(emb[idx.T])[live.T])
    assert np.isfinite(occ).all()           # dead ones inherit a live row
    with monkeypatch.context() as m:
        m.setattr(hl, "touched_rows", _gathered_rows)
        _, rows_g, occ_g, logits_g = jax.device_get(
            jax.jit(forward)(theta, dense, idx_d, nv))
    np.testing.assert_array_equal(_bits(rows_g[:n_live]),
                                  _bits(rows[:n_live]))
    np.testing.assert_array_equal(_bits(occ_g)[live.T], _bits(occ)[live.T])
    np.testing.assert_array_equal(_bits(logits[:n_valid]),
                                  _bits(logits_g[:n_valid]))
    # ... and _hashed_logits itself, on weights whose sums do not overflow
    theta = {**theta, "emb": jnp.asarray(tame)}
    got = jax.jit(forward)(theta, dense, idx_d, nv)[3]
    want = jax.jit(lambda theta, dense, idx: hl._hashed_logits(
        theta, dense, idx, cd, vals))(theta, dense, idx_d)
    np.testing.assert_allclose(np.asarray(got)[:n_valid],
                               np.asarray(want)[:n_valid],
                               rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("kind", ["adagrad", "ftrl"])
def test_small_fit_equals_the_gather_form_bitwise(monkeypatch, kind):
    """A whole small fit as ``fit_stream`` dispatches it — a streamed step
    a chunk, then the fused replay with its keys hoisted — leaves ``emb``,
    the rule's slot tables, ``t``, ``coef``, the intercept, the block
    count and every loss with the bits of the same fit whose forward
    GATHERS (``_gathered_rows``: two gathers where ``touched_rows`` has
    a scatter, a prefix sum and a sort), in blocks of 256 slots so that
    the forward's loop and the update's take four trips."""
    import orange3_spark_tpu.models.hashed_linear as hl

    monkeypatch.setattr(sparse_mod, "SLOT_BLOCK", 256)
    session = _layout_session("one-device")
    p = StreamingHashedLinearEstimator(
        n_dims=1 << 10, n_dense=4, n_cat=6, epochs=4, step_size=0.05,
        chunk_rows=1024, reg_param=1e-3, l1_param=1e-4,
        loss="squared_hinge", label_in_chunk=True,
        optim_update=f"sparse_{kind}", sparse_lowering="sort").params
    jits = [f for j in (hl._hashed_step, hl._hashed_replay_epochs)
            for f in (j.donated, j.plain)]

    def fit():
        for f in jits:                # trace anew: the forward is patched
            f.clear_cache()
        theta, opt, stacks, salts, kw = _replay_state(
            p, session, n_chunks=3, n_valid_last=700, seed=36)
        hyper = (jnp.float32(p.reg_param), jnp.float32(p.step_size),
                 jnp.float32(p.l1_param))
        losses = []
        for c in range(3):
            theta, opt, loss = hl._hashed_step(
                theta, opt, *jax.tree.map(lambda a: a[c], stacks), salts,
                *hyper, **kw)
            losses.append(loss)
        theta, opt, replayed = hl._hashed_replay_epochs(
            theta, opt, stacks, salts, *hyper, n_epochs=3, hoist_keys=True,
            **kw)
        return jax.device_get((theta, opt, losses, replayed))

    new = fit()
    with monkeypatch.context() as m:
        m.setattr(hl, "touched_rows", _gathered_rows)
        old = fit()
    for f in jits:
        f.clear_cache()
    theta, opt = new[:2]
    assert int(opt["step"]) == 12 and int(opt["blocks"]) >= 12 * 4 - 3
    assert np.abs(theta["emb"]).max() > 1e-3
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(old)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_hoist_is_admitted_at_the_cells_sizes():
    """``sort_keys_bytes`` counts all five arrays of ``sort_keys`` (the
    forward's ``order`` and ``head`` among them: 140.5 MB a chunk at the
    Criteo cells' shape, 84 before), and the default cache budget still
    holds the six cached chunks' keys beside the cache and its stack at
    2^29 and 2^30 rows — so both replay cells keep ``step_sort_share``
    12/48 — while a budget that ends one byte short of the keys sends
    every step back to sorting for itself."""
    from orange3_spark_tpu.models.hashed_linear import _hoist_sort_keys
    from orange3_spark_tpu.optim.sparse import sort_keys_bytes, sort_slots

    rows, C, chunks, budget = 1 << 18, 26, 6, 8 << 30   # fit_stream's default
    cache = 100 << 20             # six packed chunks, rounded up (PERF.md)
    for n_dims in (1 << 29, 1 << 30):
        one = sort_keys_bytes(rows, C, n_dims)
        assert sort_slots(rows, C, n_dims) == 7 << 20
        assert one == 4 * (3 * rows * C + 2 * (7 << 20)) == 140_509_184
        kw = {"sparse_lowering": "sort", "n_dims": n_dims}
        assert _hoist_sort_keys(kw, rows, C, chunks, cache, budget)
        tight = 2 * cache + chunks * one
        assert _hoist_sort_keys(kw, rows, C, chunks, cache, tight)
        assert not _hoist_sort_keys(kw, rows, C, chunks, cache, tight - 1)


def test_slot_blocks_are_counted_per_fit(session, data):
    """The fit reads the device-side trip count once at its end into the
    registry: run <= possible = steps x slot_blocks, and a dense twin
    (no loop) counts nothing."""
    Xall, y = data
    c = REGISTRY.get("otpu_sparse_slot_blocks_total")
    before = (c.value(which="run"), c.value(which="possible"))
    m = _fit(session, Xall, y, optim_update="sparse_adagrad",
             sparse_lowering="sort", reg_param=1e-3)
    run = c.value(which="run") - before[0]
    possible = c.value(which="possible") - before[1]
    per_step = slot_blocks(session.pad_rows(BASE["chunk_rows"]),
                           BASE["n_cat"], BASE["n_dims"])
    assert possible == m.n_steps_ * per_step
    # these chunks' slot bound is under one SLOT_BLOCK: one trip a step
    assert per_step == 1 and run == m.n_steps_
    _fit(session, Xall, y, optim_update="dense_adagrad", reg_param=1e-3)
    assert c.value(which="possible") - before[1] == possible


@pytest.mark.parametrize("vw", [False, True], ids=["plain", "valued"])
@pytest.mark.parametrize("k", [1, 3], ids=["k=1", "k=3"])
def test_gradient_half_permutes_by_one_sort_and_no_gather(k, vw):
    """The program itself, at every ``k``: ``_sorted_sums`` carries its
    ``[M, k]`` per-occurrence gradients to sorted order as the ``k``
    payloads of ONE sort keyed by ``inv`` — no gather in it, value-weighted
    or not (``_segment_sums`` scatters)."""
    N, C, D = 64, 4, 97
    n_slots = N * C + 1
    keys = {"inv": jnp.zeros(N * C, jnp.int32),
            "seg": jnp.zeros(N * C, jnp.int32),
            "uniq": jnp.zeros(n_slots, jnp.int32),
            "n_live": jnp.int32(0)}
    vals = jnp.ones((N, C), jnp.float32) if vw else None
    text = jax.jit(lambda dl, vals, keys: sparse_mod._sorted_sums(
        dl, vals, keys, C)).lower(
        jnp.zeros((N, k), jnp.float32), vals, keys).compile().as_text()
    sorts = re.findall(r"= \(?([^=]*?)\)? sort\(", text)
    assert len(sorts) == 1 and sorts[0].count(f"[{N * C}]") == 1 + k, sorts
    assert " gather(" not in text


# ------------------------------------------- the replay's hoisted sort keys

def _layout_session(layout):
    from orange3_spark_tpu.core.session import TpuSession
    from orange3_spark_tpu.parallel.partitioner import SPMDPartitioner

    if layout == "2x2":      # the four-chip cell's mesh (tests/test_spmd_h30)
        part = SPMDPartitioner(jax.devices()[:4], model_parallel=2)
        assert dict(part.mesh.shape) == {"data": 2, "model": 2}
        return part.session
    return TpuSession(TpuSession.default_mesh(jax.devices()[:1]))


def _replay_state(p, session, n_chunks, n_valid_last, seed):
    """A fresh fit state and a stack of ``n_chunks`` random chunks as
    fit_stream caches and stacks them (encoded by the fit's own codec),
    the last one part padding."""
    from orange3_spark_tpu.io.multihost import put_sharded
    from orange3_spark_tpu.models.hashed_linear import (
        _encode_chunk_np, _init_fit_state, _put_encoded,
    )

    theta, opt, salts_np, salts, kw = _init_fit_state(p, session)
    rng = np.random.default_rng(seed)
    rows = session.pad_rows(p.chunk_rows)
    chunks = []
    for _ in range(n_chunks):
        X = np.concatenate([
            (rng.random((rows, 1)) < 0.3).astype(np.float32),
            rng.standard_normal((rows, p.n_dense)).astype(np.float32),
            rng.integers(0, 5000, (rows, p.n_cat)).astype(np.float32),
        ], axis=1)
        X[rng.random(X.shape) < 0.02] = np.nan    # missing cells, imputed
        X[:, 0] = np.nan_to_num(X[:, 0])
        chunks.append(
            put_sharded(X, session.row_sharding) if kw["codec"] is None
            else _put_encoded(_encode_chunk_np(kw["codec"], X, salts_np),
                              session))
    one = jnp.zeros((1,), jnp.float32)
    stacks = (jax.tree.map(lambda *xs: jnp.stack(xs), *chunks),
              jnp.asarray([rows] * (n_chunks - 1) + [n_valid_last],
                          jnp.int32),
              jnp.stack([one] * n_chunks), jnp.stack([one] * n_chunks))
    return theta, opt, stacks, salts, kw


@pytest.mark.parametrize("layout", ["one-device", "2x2"])
@pytest.mark.parametrize("trips", [1, 4], ids=["1trip", "4trips"])
@pytest.mark.parametrize("cache_dtype", ["packed", "f32"])
@pytest.mark.parametrize("kind", ["adagrad", "sgd", "ftrl"])
def test_replay_with_hoisted_keys_equals_replay_without(monkeypatch, kind,
                                                        cache_dtype, trips,
                                                        layout):
    """``_hashed_replay_epochs(hoist_keys=True)`` — every chunk's sort
    keys built once ahead of the epoch scan — leaves the tables, every
    slot table, the last-seen steps, the dense leaf, the block count and
    every loss as the same replay leaves them with the sort inside each
    step: bit for bit on one device; on the (2,2) mesh GSPMD partitions
    two different programs, and they agree within float32 rounding of the
    cross-shard sums."""
    from orange3_spark_tpu.models.hashed_linear import _hashed_replay_epochs

    n_dims = 1 << 12
    if trips > 1:
        # a table size no other test compiles, so the patched block is
        # traced: ~1000 live slots of a 1025-slot bound, in blocks of 256
        monkeypatch.setattr(sparse_mod, "SLOT_BLOCK", 256)
        n_dims = 1 << 10
    session = _layout_session(layout)
    p = StreamingHashedLinearEstimator(
        n_dims=n_dims, n_dense=4, n_cat=6, epochs=4, step_size=0.05,
        chunk_rows=1024, reg_param=1e-3, l1_param=1e-4,
        loss="squared_hinge", label_in_chunk=True,
        optim_update=f"sparse_{kind}", sparse_lowering="sort",
        cache_dtype=cache_dtype).params
    out = {}
    for hoist in (False, True):
        theta, opt, stacks, salts, kw = _replay_state(
            p, session, n_chunks=3, n_valid_last=700, seed=11)
        assert (kw["codec"] is None) == (cache_dtype == "f32")
        out[hoist] = jax.device_get(_hashed_replay_epochs(
            theta, opt, stacks, salts, jnp.float32(p.reg_param),
            jnp.float32(p.step_size), jnp.float32(p.l1_param), n_epochs=3,
            hoist_keys=hoist, **kw))
    (theta, opt, losses), (theta_h, opt_h, losses_h) = out[False], out[True]
    assert int(opt["step"]) == int(opt_h["step"]) == 9
    assert int(opt["blocks"]) == int(opt_h["blocks"]) >= 9 * trips - 2
    assert np.isfinite(losses).all() and losses.shape == (3, 3)
    assert np.abs(theta["emb"]).max() > 1e-3
    same = (np.testing.assert_array_equal if layout == "one-device" else
            lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-6,
                                                    atol=1e-8))
    for a, b in zip(jax.tree.leaves((theta, opt, losses)),
                    jax.tree.leaves((theta_h, opt_h, losses_h))):
        same(a, b)


def test_hoisted_keys_are_gated_by_the_cache_budget(session, data):
    """The stacked sort keys are a temp of the replay program, so the
    replay takes them only where ``cache_device_bytes`` holds the cache,
    its stack AND them; otherwise it runs as before, a sort a step — to
    the same answer. ``otpu_sparse_sorts_total`` says which it was."""
    from orange3_spark_tpu.optim.sparse import sort_keys_bytes

    Xall, y = data
    sorts = REGISTRY.get("otpu_sparse_sorts_total")

    def fit(**kw):
        before = (sorts.value(which="run"), sorts.value(which="steps"))
        st: dict = {}
        m = _fit(session, Xall, y, optim_update="sparse_adagrad",
                 sparse_lowering="sort", reg_param=1e-3, stage_times=st,
                 **kw)
        assert m.n_steps_ == 16                  # 4 chunks x 4 epochs
        assert sorts.value(which="steps") - before[1] == m.n_steps_
        return m, st, sorts.value(which="run") - before[0]

    roomy, st, run = fit()
    assert st["replay_source"] == "fused" and run == 4 * (1 + 1)
    key_bytes = 4 * sort_keys_bytes(session.pad_rows(BASE["chunk_rows"]),
                                    BASE["n_cat"], BASE["n_dims"])
    # admits the cache and its stack, not the keys beside them
    tight, st, run = fit(cache_device_bytes=2 * st["cache_bytes"]
                         + key_bytes - 1)
    assert st["replay_source"] == "fused" and run == tight.n_steps_
    assert _emb_diff(tight, roomy) == 0.0
    # ... and one byte more does
    _, _, run = fit(cache_device_bytes=2 * st["cache_bytes"] + key_bytes)
    assert run == 4 * (1 + 1)
    # one dispatch an epoch builds the keys and uses them once; K = 2
    # epochs a dispatch: 3 replay epochs in 2 dispatches
    epoch, st, run = fit(replay_granularity="epoch")
    assert st["replay_source"] == "fused_epoch" and run == epoch.n_steps_
    assert _emb_diff(epoch, roomy) == 0.0
    grouped, _, run = fit(replay_granularity="epoch", epochs_per_dispatch=2)
    assert run == 4 * (1 + 2) and _emb_diff(grouped, roomy) == 0.0
    # no replay, no hoist; a dense twin builds no sort keys at all
    _, _, run = fit(fused_replay=False)
    assert run == 16
    before = sorts.value(which="steps")
    _fit(session, Xall, y, optim_update="dense_adagrad", reg_param=1e-3)
    assert sorts.value(which="steps") == before


@pytest.mark.parametrize("room", ["roomy", "tight"])
def test_warm_replay_compiles_the_replay_the_fit_dispatches(session, data,
                                                            room):
    """``warm_replay`` resolves the hoist from the same budget by the same
    rule as the fit, so the fit's replay program is the warmed one: the
    timed fit traces no second ``_hashed_replay_epochs``."""
    from orange3_spark_tpu.models.hashed_linear import (
        _hashed_replay_epochs, estimate_cached_chunk_bytes,
    )
    from orange3_spark_tpu.optim.sparse import sort_keys_bytes

    Xall, y = data
    kw = dict(BASE, optim_update="sparse_adagrad", sparse_lowering="sort",
              reg_param=1e-3, n_dims=1 << 13)
    est = StreamingHashedLinearEstimator(**kw)
    budget = 8 << 30
    if room == "tight":     # the cache and its stack, half the keys
        budget = (2 * 4 * estimate_cached_chunk_bytes(est.params, session)
                  + 2 * sort_keys_bytes(session.pad_rows(kw["chunk_rows"]),
                                        kw["n_cat"], kw["n_dims"]))
    sorts = REGISTRY.get("otpu_sparse_sorts_total")
    run0 = sorts.value(which="run")
    assert est.warm_replay(4, session=session,
                           cache_device_bytes=budget) is not None
    traced = _hashed_replay_epochs.donated._cache_size()
    st: dict = {}
    m = est.fit_stream(array_chunk_source(Xall, y, chunk_rows=1000),
                       session=session, cache_device=True,
                       cache_device_bytes=budget, stage_times=st)
    assert st["replay_source"] == "fused"
    assert _hashed_replay_epochs.donated._cache_size() == traced
    assert sorts.value(which="run") - run0 == (
        4 * 2 if room == "roomy" else m.n_steps_)


# ------------------------------------------------- replay-path parity triple

def test_fused_epoch_spill_replay_parity(session, tmp_path, data):
    """The acceptance triple: fused('all') vs epoch-granular vs disk-spill
    replay under sparse_adagrad must produce the same table."""
    Xall, y = data
    kw = dict(optim_update="sparse_adagrad", reg_param=1e-3, epochs=4)
    fused = _fit(session, Xall, y, **kw)
    st_ep: dict = {}
    epoch = _fit(session, Xall, y, **kw, replay_granularity="epoch",
                 epochs_per_dispatch=2, stage_times=st_ep)
    st_sp: dict = {}
    spill = _fit(session, Xall, y, **kw, fused_replay=False,
                 cache_device_bytes=1, cache_spill_dir=str(tmp_path),
                 stage_times=st_sp)
    assert st_ep["replay_source"] == "fused_epoch"
    assert st_sp["replay_source"] == "disk"
    assert _emb_diff(epoch, fused) == 0.0
    assert _emb_diff(spill, fused) < 5e-9   # different program, same math
    # grouped disk-scan replay (fused_replay=True over the spill): 16
    # chunks of 12,288 B overflow the budget, which holds 2 records a
    # group (a group takes a quarter of it)
    small = _fit(session, Xall, y, **kw, chunk_rows=256)
    st_gr: dict = {}
    grouped = _fit(session, Xall, y, **kw, chunk_rows=256,
                   cache_device_bytes=120_000,
                   cache_spill_dir=str(tmp_path / "g"), stage_times=st_gr)
    assert st_gr["replay_source"] == "disk" and st_gr["cache_overflow"]
    assert st_gr["disk_replay_group"] == 2
    assert _emb_diff(grouped, small) < 5e-9


def test_checkpoint_resume_sparse_state(session, tmp_path, data,
                                        make_killing_checkpointer):
    """Kill-and-resume with the sparse optimizer: the (slots, timestamps,
    step) state round-trips through the checkpoint and the resumed fit
    matches the uninterrupted one."""
    from orange3_spark_tpu.utils.fault import StreamCheckpointer

    Xall, y = data
    kw = dict(optim_update="sparse_adagrad", reg_param=1e-3, epochs=3,
              fused_replay=False)
    ref = _fit(session, Xall, y, **kw)
    path = str(tmp_path / "ck")
    killer = make_killing_checkpointer(path, every_steps=4, die_after=2)
    with pytest.raises(RuntimeError, match="injected fault"):
        _fit(session, Xall, y, **kw, checkpointer=killer)
    resumed = _fit(session, Xall, y, **kw,
                   checkpointer=StreamCheckpointer(path, every_steps=4))
    assert _emb_diff(resumed, ref) < 1e-6
    assert resumed.n_steps_ == ref.n_steps_


def _as_before_block_counter(blob):
    assert blob["state"]["opt_state"].pop("blocks") > 0


def _as_before_pr30(blob):
    assert "emb_update" not in blob["meta"]["params"]
    blob["meta"]["params"]["emb_update"] = "auto"


@pytest.mark.parametrize("older", [_as_before_block_counter, _as_before_pr30],
                         ids=["no-block-counter", "retired-emb_update"])
def test_older_snapshot_resumes(session, tmp_path, data,
                                make_killing_checkpointer, older):
    """A snapshot as an earlier program wrote it — before opt_state
    carried the block counter (no 'blocks' key), or with the retired
    ``emb_update`` parameter in its saved meta (utils/fault.RETIRED_PARAMS)
    — must still resume, to the same fit bit for bit."""
    import pickle

    from orange3_spark_tpu.utils.fault import StreamCheckpointer

    Xall, y = data
    kw = dict(optim_update="sparse_adagrad", sparse_lowering="sort",
              reg_param=1e-3, epochs=3, fused_replay=False)
    ref = _fit(session, Xall, y, **kw)
    path = str(tmp_path / "ck")
    killer = make_killing_checkpointer(path, every_steps=4, die_after=2)
    with pytest.raises(RuntimeError, match="injected fault"):
        _fit(session, Xall, y, **kw, checkpointer=killer)
    with open(path, "rb") as f:
        blob = pickle.load(f)
    older(blob)
    with open(path, "wb") as f:
        pickle.dump(blob, f)
    resumed = _fit(session, Xall, y, **kw,
                   checkpointer=StreamCheckpointer(path, every_steps=4))
    np.testing.assert_array_equal(np.asarray(resumed.theta["emb"]),
                                  np.asarray(ref.theta["emb"]))
    assert resumed.n_steps_ == ref.n_steps_
    # a parameter that still exists and differs is still refused
    killer = make_killing_checkpointer(path, every_steps=4, die_after=2)
    with pytest.raises(RuntimeError, match="injected fault"):
        _fit(session, Xall, y, **kw, checkpointer=killer)
    with pytest.raises(ValueError, match="different configuration"):
        _fit(session, Xall, y, **{**kw, "step_size": 0.06},
             checkpointer=StreamCheckpointer(path, every_steps=4))


# --------------------------------------------------- serving + sharding

def test_sparse_trained_model_serves_identically(session, data):
    from orange3_spark_tpu.serve import BucketLadder, ServingContext

    Xall, y = data
    m = _fit(session, Xall, y, optim_update="sparse_adagrad",
             reg_param=1e-3)
    raw = m.predict_proba(Xall[:777])
    with ServingContext(BucketLadder(min_bucket=64, max_bucket=1 << 11)):
        served = m.predict_proba(Xall[:777])
    np.testing.assert_array_equal(served, raw)


@pytest.mark.parametrize("lowering", ["auto", "sort"])
def test_model_sharded_table_sparse_parity(session, data, monkeypatch,
                                           lowering):
    """The sharded-table oracle: a (4 data x 2 model) mesh fit under
    sparse updates matches the replicated fit — GSPMD lowers the gathers/
    segment scatter/writeback against the P('model', None) table. The
    'sort' case takes its block loop several trips a step, the trip count
    a replicated scalar; 'auto' the one trip of the default block."""
    from jax.sharding import Mesh

    from orange3_spark_tpu.core.session import TpuSession

    Xall, y = data
    devs = np.array(jax.devices()).reshape(4, 2)
    sharded = TpuSession(Mesh(devs, ("data", "model")))
    kw = dict(optim_update="sparse_adagrad", reg_param=1e-3,
              sparse_lowering=lowering)
    if lowering == "sort":
        # a table size no other test compiles, so the patched block is
        # traced: 2049 slots a chunk in blocks of 256
        monkeypatch.setattr(sparse_mod, "SLOT_BLOCK", 256)
        kw["n_dims"] = 1 << 11
    blocks = REGISTRY.get("otpu_sparse_slot_blocks_total")
    run0 = blocks.value(which="run")
    m_sh = _fit(sharded, Xall, y, **kw)
    trips = blocks.value(which="run") - run0
    m_ref = _fit(session, Xall, y, **kw)
    assert m_sh.theta["emb"].sharding.spec[0] == "model"
    assert _emb_diff(m_sh, m_ref) < 1e-6
    if lowering == "sort":
        assert slot_blocks(1024, BASE["n_cat"], 1 << 11) == 9
        assert trips >= 2 * m_sh.n_steps_


# ------------------------------------------------------------- compiles

def test_sparse_step_compiles_once_per_bucket_and_rule(session, data,
                                                      xla_compiles):
    """Recompile-regression guard: one compile set per (chunk bucket,
    optim_update); repeats hit the jit cache, and the dense twin is a
    DIFFERENT static (new programs) that leaves the sparse programs cached
    — going back costs zero compiles."""
    Xall, y = data
    kw = dict(optim_update="sparse_adagrad", reg_param=1e-3, epochs=3)
    _fit(session, Xall, y, **kw)
    base = xla_compiles()
    # same shapes, same resolved statics: zero new programs
    _fit(session, Xall, y, **kw)
    assert xla_compiles() == base
    # a second chunk-shape bucket compiles its own step/scan set, once
    _fit(session, Xall, y, **kw, chunk_rows=512)
    per_bucket = xla_compiles() - base
    assert per_bucket > 0
    _fit(session, Xall, y, **kw, chunk_rows=512)
    assert xla_compiles() == base + per_bucket
    # the dense twin: new statics compile
    dense = {**kw, "optim_update": "dense_adagrad"}
    _fit(session, Xall, y, **dense)
    flipped = xla_compiles()
    assert flipped > base + per_bucket
    # BACK: the sparse programs are still cached — zero new compiles
    _fit(session, Xall, y, **kw)
    assert xla_compiles() == flipped
    # and the dense twin is cached too
    _fit(session, Xall, y, **dense)
    assert xla_compiles() == flipped


def test_auto_lowering_resolves_per_backend(session, monkeypatch):
    """The resolved statics do not depend on the backend: 'auto' is 'sort'
    whatever ``jax.default_backend()`` says, and nothing else in a fit's
    static key moves with it."""
    from orange3_spark_tpu.models.hashed_linear import _init_fit_state

    p = StreamingHashedLinearEstimator(
        **BASE, optim_update="sparse_adagrad", cache_dtype="auto").params
    statics = {}
    for backend in ("cpu", "tpu", "gpu"):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert resolve_sparse_lowering("auto") == "sort"
        assert resolve_sparse_lowering("sort") == "sort"
        statics[backend] = _init_fit_state(p, session)[4]
    assert statics["cpu"] == statics["tpu"] == statics["gpu"]
    assert statics["cpu"]["sparse_lowering"] == "sort"
    assert resolve_optim_update("sparse_adagrad") == "sparse_adagrad"
    with pytest.raises(ValueError, match="sparse_lowering"):
        resolve_sparse_lowering("bogus")
    with pytest.raises(ValueError, match="optim_update"):
        resolve_optim_update("sparse_adam")


def test_plan_lowering_is_refused(session, data):
    """The host-presorted lowering is gone: its name is refused, by the
    resolver and by a fit that asks for it, with a message that says so."""
    with pytest.raises(ValueError, match=r"removed in PR 30.*'sort'"):
        resolve_sparse_lowering("plan")
    Xall, y = data
    with pytest.raises(ValueError, match="removed in PR 30"):
        _fit(session, Xall, y, optim_update="sparse_adagrad",
             sparse_lowering="plan")
