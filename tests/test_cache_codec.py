"""Compressed device-resident replay cache (io/codec.py) — bit-pack
primitive roundtrips, LOSSLESS packed-replay bitwise parity vs the f32
cache, the bf16 divergence bound, the OTPU_CACHE_DTYPE kill-switch
(bitwise legacy + zero new compiles), capacity/fusion-gate economics, the
versioned spill format (old flat-f32 files stay readable), spill-file
hygiene on aborted fits, and the _DeviceCache degrade un-latch."""

import gc
import os
import warnings

import numpy as np
import pytest

import jax.numpy as jnp

from orange3_spark_tpu.io.codec import (
    BF16, bit_width, force_cache_dtype, pack_rows_np, resolve_cache_dtype,
    unpack_rows,
)
from orange3_spark_tpu.io.streaming import (
    DiskChunkCache, StreamingLinearEstimator, _DeviceCache,
    array_chunk_source,
)
from orange3_spark_tpu.models.hashed_linear import (
    StreamingHashedLinearEstimator, estimate_cached_chunk_bytes,
    resolve_chunk_codec,
)

from tests.test_hashed_linear import _criteo_shaped

BASE = dict(n_dims=1 << 12, n_dense=4, n_cat=6, epochs=4, step_size=0.05,
            reg_param=1e-3, chunk_rows=1024, optim_update="sparse_adagrad")


def _fit(session, Xall, y, cache_dtype, **kw):
    params = dict(BASE)
    params.update(kw)
    fit_kw = {k: params.pop(k) for k in
              ("cache_device_bytes", "cache_spill_dir", "stage_times",
               "holdout_chunks") if k in params}
    with force_cache_dtype(cache_dtype):
        est = StreamingHashedLinearEstimator(**params)
        return est.fit_stream(
            array_chunk_source(Xall, y, chunk_rows=1024),
            session=session, cache_device=True, **fit_kw)


def _emb(m):
    return np.asarray(m.theta["emb"])


@pytest.fixture(scope="module")
def data():
    return _criteo_shaped(4096, seed=21)


# --------------------------------------------------------- the primitives

def test_bitpack_roundtrips_all_widths():
    rng = np.random.default_rng(0)
    for bits in (1, 2, 5, 9, 12, 16, 17, 18, 22, 23, 25, 31):
        vals = rng.integers(0, 1 << bits, (37, 26),
                            dtype=np.int64).astype(np.uint32)
        out = np.asarray(unpack_rows(
            jnp.asarray(pack_rows_np(vals, bits)), bits, 26))
        np.testing.assert_array_equal(out, vals.astype(np.int32))
    assert bit_width(1) == 1 and bit_width(1 << 22) == 22


def test_resolver_gates():
    assert resolve_cache_dtype("f32") == "f32"
    with force_cache_dtype("bf16"):
        # the env kill-switch outranks the param by design
        assert resolve_cache_dtype("packed") == "bf16"
    with pytest.raises(ValueError, match="cache_dtype"):
        resolve_cache_dtype("float16")
    # vw pair chunks keep the raw layout; missing='keep' demotes packed to
    # bf16 (NaN codes must reach the in-jit hash and poison visibly)
    p = StreamingHashedLinearEstimator(
        **{**BASE, "cache_dtype": "packed"}).params
    assert resolve_chunk_codec(p).mode == "packed"
    import dataclasses

    assert resolve_chunk_codec(
        dataclasses.replace(p, value_weighted=True, n_dense=0)) is None
    assert resolve_chunk_codec(
        dataclasses.replace(p, missing="keep")).mode == "bf16"
    # label store: u8 only while every class id fits a byte — a 300-class
    # logistic fit keeps f32 labels instead of refusing the codec
    assert resolve_chunk_codec(
        dataclasses.replace(p, label_in_chunk=True)).label_u8
    assert not resolve_chunk_codec(dataclasses.replace(
        p, label_in_chunk=True, n_classes=300)).label_u8
    assert not resolve_chunk_codec(dataclasses.replace(
        p, label_in_chunk=True, loss="squared")).label_u8


# ------------------------------------------------- parity vs the f32 cache

def test_lossless_pack_replay_bitwise_identical(session):
    """The acceptance claim: with no dense block every cached quantity is
    losslessly packed (u8 label via y, pre-hashed bit-packed indices), so
    the packed-cache fit must equal the f32-cache fit BITWISE — under the
    legacy adam rule and under a sparse rule."""
    rng = np.random.default_rng(5)
    cats = rng.integers(0, 50_000, (4096, 8)).astype(np.float32)
    y = (cats[:, 0] % 3 == 0).astype(np.float32)
    # adam = the dense-autodiff path, sparse_adagrad = the touched-row
    # path; between them every decode consumer is covered
    for optim in ("adam", "sparse_adagrad"):
        kw = dict(n_dense=0, n_cat=8, optim_update=optim, epochs=5)
        m32 = _fit(session, cats, y, "f32", **kw)
        mpk = _fit(session, cats, y, "packed", **kw)
        np.testing.assert_array_equal(_emb(mpk), _emb(m32)), optim
        assert mpk.n_steps_ == m32.n_steps_


def test_bf16_divergence_bound_100_epochs(session):
    """bf16 dense-feature storage is lossy but BOUNDED: RTNE at 8 mantissa
    bits (rel. err <= 2^-8 per feature read). Over 100 seeded epochs of
    sparse-adagrad the accumulated theta divergence vs the f32 cache
    measured ~4e-4; pinned at 5e-3 (an order of magnitude of headroom —
    a codec regression would blow through it, normal float drift not)."""
    Xall, y = _criteo_shaped(2048, seed=31)
    kw = dict(n_dims=1 << 10, epochs=100, reg_param=1e-4)
    m32 = _fit(session, Xall, y, "f32", **kw)
    mpk = _fit(session, Xall, y, "packed", **kw)
    d = np.abs(_emb(mpk) - _emb(m32)).max()
    assert 0.0 < d < 5e-3, d
    # and the packed arm is exactly the bf16 arm plus LOSSLESS packing
    mbf = _fit(session, Xall, y, "bf16", **kw)
    np.testing.assert_array_equal(_emb(mpk), _emb(mbf))


def test_compressed_replay_paths_agree(session, tmp_path, data):
    """fused('all') vs epoch-granular vs disk-spill replay under the
    packed codec: the encoded chunks ride the HBM stack AND the
    typed spill records — same numbers everywhere."""
    Xall, y = data
    fused = _fit(session, Xall, y, "packed")
    st_ep: dict = {}
    epoch = _fit(session, Xall, y, "packed", replay_granularity="epoch",
                 epochs_per_dispatch=2, stage_times=st_ep)
    st_sp: dict = {}
    spill = _fit(session, Xall, y, "packed", fused_replay=False,
                 cache_device_bytes=1, cache_spill_dir=str(tmp_path),
                 stage_times=st_sp)
    assert st_ep["replay_source"] == "fused_epoch"
    assert st_sp["replay_source"] == "disk"
    np.testing.assert_array_equal(_emb(epoch), _emb(fused))
    assert np.abs(_emb(spill) - _emb(fused)).max() < 5e-9


def test_kill_switch_restores_legacy_zero_compiles(session, data,
                                                   xla_compiles,
                                                   monkeypatch):
    """OTPU_CACHE_DTYPE=f32 resolves ANY cache_dtype to the legacy layout:
    bitwise-identical results through the very same compiled programs —
    zero new compiles after a legacy fit has run (the resolution is a
    static at fit entry, never a cache-key pollutant)."""
    Xall, y = data
    m_legacy = _fit(session, Xall, y, "f32")
    base = xla_compiles()
    assert np.array_equal(_emb(_fit(session, Xall, y, "f32")),
                          _emb(m_legacy))
    assert xla_compiles() == base       # legacy programs cached
    monkeypatch.setenv("OTPU_CACHE_DTYPE", "f32")
    est = StreamingHashedLinearEstimator(**BASE, cache_dtype="packed")
    m_killed = est.fit_stream(
        array_chunk_source(Xall, y, chunk_rows=1024),
        session=session, cache_device=True)
    assert xla_compiles() == base       # kill-switch = the legacy programs
    np.testing.assert_array_equal(_emb(m_killed), _emb(m_legacy))


# ------------------------------------------------------- cache economics

def test_capacity_compressed_cache_fuses_where_f32_degrades(session, data):
    """The codec's point: at a budget whose fusion gate (cache + its
    stack) the f32 layout fails, so that it replays chunk by chunk, the
    compressed layout still passes it and replays in one dispatch — the
    fused-replay cliff moves out by the compression ratio."""
    Xall, y = data
    p_pk = StreamingHashedLinearEstimator(
        **BASE, cache_dtype="packed").params
    with force_cache_dtype("packed"):
        pk_chunk = estimate_cached_chunk_bytes(p_pk, session)
    with force_cache_dtype("f32"):
        f32_chunk = estimate_cached_chunk_bytes(p_pk, session)
    # read (CPU, PR 30): 48 B a row (4 dense + 6 codes + y + w, f32)
    # against 28 (bf16 dense, 6 x 12 bits in 3 words, y + w f32)
    assert (f32_chunk, pk_chunk) == (49152, 28672)      # 1.71x
    budget = 2 * 4 * pk_chunk + 4096    # fusion gate: 2x the 4-chunk cache
    assert 4 * f32_chunk <= budget < 2 * 4 * f32_chunk
    st_pk: dict = {}
    mpk = _fit(session, Xall, y, "packed", cache_device_bytes=budget,
               stage_times=st_pk)
    assert st_pk["replay_source"] == "fused"
    assert st_pk["cache_overflow"] is False
    assert st_pk["cache_dtype"] == "packed"
    # the cache counts each chunk's i32 n_valid beside its blocks
    assert (st_pk["cache_raw_bytes"], st_pk["cache_bytes"]) == (
        4 * f32_chunk, 4 * (pk_chunk + 4))
    st_32: dict = {}
    m32 = _fit(session, Xall, y, "f32", cache_device_bytes=budget,
               stage_times=st_32)
    assert st_32["replay_source"] == "hbm"
    assert st_32["cache_overflow"] is False
    # same math either way (bf16 rounding only)
    assert np.abs(_emb(mpk) - _emb(m32)).max() < 1e-3


def test_compressed_holdout_evaluates_on_device(session, data):
    Xall, y = data
    st: dict = {}
    m = _fit(session, Xall, y, "packed", holdout_chunks=1, stage_times=st)
    assert m.cache_codec_ is not None
    ev = m.evaluate_device(m.holdout_chunks_)
    assert 0.0 < ev["logloss"] < 2.0
    ev32 = _fit(session, Xall, y, "f32", holdout_chunks=1)
    ev32 = ev32.evaluate_device(ev32.holdout_chunks_)
    assert abs(ev["logloss"] - ev32["logloss"]) < 1e-3


def test_label_u8_rejects_inexact_labels(session):
    """Soft labels cannot ride the u8 label store — the encode must fail
    loudly (pointing at the kill-switch), never round silently."""
    rng = np.random.default_rng(6)
    raw = np.concatenate([
        rng.uniform(0.2, 0.8, (1024, 1)).astype(np.float32),   # soft labels
        rng.integers(0, 100, (1024, 10)).astype(np.float32),
    ], axis=1)
    with force_cache_dtype("packed"):
        est = StreamingHashedLinearEstimator(
            n_dims=1 << 10, n_dense=4, n_cat=6, epochs=2, chunk_rows=1024,
            label_in_chunk=True)
        with pytest.raises(ValueError, match="u8"):
            est.fit_stream(lambda: iter([raw]), session=session,
                           cache_device=True)


# ------------------------------------------------ dense streaming (bf16)

def test_dense_streaming_bf16_cache(session, tmp_path):
    rng = np.random.default_rng(1)
    X = rng.standard_normal((4096, 8)).astype(np.float32)
    y = (X[:, 0] - X[:, 1] > 0).astype(np.float32)
    src = array_chunk_source(X, y, chunk_rows=1024)

    def fit(cd, **kw):
        with force_cache_dtype(cd):
            return StreamingLinearEstimator(
                loss="logistic", epochs=3, step_size=0.05, chunk_rows=1024,
            ).fit_stream(src, n_features=8, session=session,
                         cache_device=True, **kw)

    m32, mbf = fit("f32"), fit("bf16")
    d = np.abs(np.asarray(mbf.coef) - np.asarray(m32.coef)).max()
    assert 0.0 < d < 5e-3              # bounded bf16 feature rounding
    # spill-backed replay stores bf16 records and matches the HBM replay
    msp = fit("bf16", cache_device_bytes=1, cache_spill_dir=str(tmp_path))
    np.testing.assert_array_equal(np.asarray(msp.coef), np.asarray(mbf.coef))
    # 'packed' has no int columns on the dense path: resolves to bf16
    np.testing.assert_array_equal(np.asarray(fit("packed").coef),
                                  np.asarray(mbf.coef))


# ------------------------------------------- spill format + hygiene

def test_spill_v1_typed_records_and_attach(tmp_path):
    rng = np.random.default_rng(7)
    cache = DiskChunkCache(str(tmp_path), ((8, 3), (8,), (5,)),
                           (BF16, np.float32, np.uint32), keep_file=True)
    recs = []
    for i in range(4):
        a = rng.standard_normal((8, 3)).astype(BF16)
        b = rng.standard_normal(8).astype(np.float32)
        c = rng.integers(0, 99, 5).astype(np.uint32)
        cache.append((a, b, c), n_valid=8 - i)
        recs.append((a, b, c))
    cache.finalize()
    for i, (a, b, c) in enumerate(recs):
        (ar, br, cr), n = cache.read(i)
        np.testing.assert_array_equal(np.asarray(ar), a)
        np.testing.assert_array_equal(np.asarray(br), b)
        np.testing.assert_array_equal(np.asarray(cr), c)
        assert n == 8 - i
    # a v1 file is self-describing: attach() needs no layout at all
    att = DiskChunkCache.attach(cache.path)
    assert att.n_records == 4 and att.n_valid == [8, 7, 6, 5]
    (ar, br, cr), _ = att.read(2)
    np.testing.assert_array_equal(np.asarray(ar), recs[2][0])
    np.testing.assert_array_equal(np.asarray(cr), recs[2][2])
    att.delete()
    cache.delete()
    assert not list(tmp_path.iterdir())


def test_spill_v0_flat_f32_stays_readable(tmp_path):
    """Format-version guarantee: the pre-header format (flat little-endian
    f32 records, no magic) reads back through attach()."""
    rng = np.random.default_rng(8)
    X = rng.standard_normal((3, 4, 2)).astype(np.float32)
    w = rng.standard_normal((3, 4)).astype(np.float32)
    path = str(tmp_path / "legacy.f32")
    with open(path, "wb") as f:
        for i in range(3):
            X[i].tofile(f)
            w[i].tofile(f)
    att = DiskChunkCache.attach(path, shapes=((4, 2), (4,)))
    assert att.n_records == 3
    for i in range(3):
        (Xr, wr), n = att.read(i)
        np.testing.assert_array_equal(np.asarray(Xr), X[i])
        np.testing.assert_array_equal(np.asarray(wr), w[i])
        assert n == 4                  # v0 stores no live-row counts
    att.delete()


def test_aborted_fit_leaks_no_spill_files(session, tmp_path):
    """Hygiene: an exception mid-epoch-1 (source dies after two chunks)
    must leave the spill dir empty — the anonymous-file idiom plus the
    registered finalizer cover both the unlinked and keep_file modes."""
    Xall, y = _criteo_shaped(4096, seed=33)

    def dying_source():
        yield Xall[:1024], y[:1024]
        yield Xall[1024:2048], y[1024:2048]
        raise RuntimeError("injected ingest fault")

    est = StreamingHashedLinearEstimator(**BASE)
    with pytest.raises(RuntimeError, match="injected ingest fault"):
        est.fit_stream(lambda: dying_source(), session=session,
                       cache_device=True, cache_device_bytes=1,
                       cache_spill_dir=str(tmp_path))
    gc.collect()                       # drop the dead fit frame's spill
    assert not list(tmp_path.iterdir())
    # keep_file mode: the finalizer removes an orphaned NAMED spill too
    c = DiskChunkCache(str(tmp_path), ((4,),), keep_file=True)
    c.append((np.zeros(4, np.float32),), 4)
    path = c.path
    assert os.path.exists(path)
    del c
    gc.collect()
    assert not os.path.exists(path)


# ------------------------------------------------- _DeviceCache un-latch

def test_device_cache_unlatches_when_misses_are_excluded():
    def batch(tag, kb):
        return (np.zeros(kb * 256, np.float32), tag)

    cache = _DeviceCache(True, 100 * 1024, may_exclude_tail=1)
    a, b, c = batch("a", 60), batch("b", 60), batch("c", 30)
    cache.offer(a)
    cache.offer(b)                     # would overflow: missed, degraded
    assert cache.degraded and cache.batches == [a]
    # the miss sits wholly inside the excluded last-1-offers tail:
    # forgiven — tracked by OFFER ORDINAL, never by the dead batch's id
    # (CPython recycles ids; an id match could bless an incomplete cache)
    cache.forgive_tail(1)
    assert not cache.degraded
    cache.offer(c)                     # fits again after the forgiveness
    cache.settle()
    assert cache.enabled and cache.batches == [a, c] and not cache.degraded
    # a REAL (non-tail) miss drops the whole cache the moment it ages
    # out of the excludable window — no budget's worth of HBM pinned
    # until settle, and a partial replay can never happen
    cache2 = _DeviceCache(True, 100 * 1024, may_exclude_tail=1)
    cache2.offer(batch("a", 60))
    cache2.offer(batch("b", 60))       # miss at ordinal 1: inside tail
    assert cache2.degraded and cache2.enabled
    cache2.offer(batch("h", 1))        # miss aged out of the 1-tail: drop
    assert cache2.degraded and not cache2.enabled and cache2.batches == []
    cache2.forgive_tail(1)             # nothing left to forgive
    cache2.settle()
    assert cache2.degraded and not cache2.enabled and cache2.batches == []
    # without an excluder a miss is final: the overflow drops the cache
    # AT THE OFFER (no budget's worth of HBM pinned until settle)
    cache3 = _DeviceCache(True, 100 * 1024)
    cache3.offer(batch("a", 60))
    cache3.offer(batch("b", 60))
    assert cache3.degraded and not cache3.enabled and cache3.batches == []


def test_holdout_tail_overflow_no_longer_degrades(session, data):
    """The fixed scenario: budget holds the TRAIN chunks but not the
    holdout tail. The tail misses the cache, holdout exclusion covers the
    miss, and the fit replays from HBM — previously one transient
    overflow latched `degraded` and dropped everything."""
    Xall, y = data                     # 4 chunks of 1024
    with force_cache_dtype("f32"):
        p = StreamingHashedLinearEstimator(**BASE).params
        chunk_bytes = estimate_cached_chunk_bytes(p, session)
    budget = 3 * chunk_bytes + 1024    # 3 train chunks yes, 4th (tail) no
    st: dict = {}
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)   # no overflow warn
        m = _fit(session, Xall, y, "f32", fused_replay=False,
                 cache_device_bytes=budget, holdout_chunks=1,
                 stage_times=st)
    assert st["cache_overflow"] is False
    assert st["replay_source"] == "hbm"
    assert m.n_steps_ == 3 * BASE["epochs"]
    ref = _fit(session, Xall, y, "f32", fused_replay=False,
               holdout_chunks=1)
    np.testing.assert_array_equal(_emb(m), _emb(ref))


# --------------------------------------------------------- tool smoke

def test_cache_ab_tool_smoke():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "cache_ab", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "cache_ab.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out = mod.run(rows=4096, dims=1 << 12, n_dense=0, epochs=2,
                  chunk_rows=2048)
    assert out["lossless_config"] and out["max_theta_diff"] == 0.0
    assert out["compression_ratio"] and out["compression_ratio"] > 1.5
    assert out["wall_s_f32"] > 0 and out["wall_s_compressed"] > 0
