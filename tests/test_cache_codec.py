"""Compressed device-resident replay cache (io/codec.py) — bit-pack
primitive roundtrips, LOSSLESS packed-replay bitwise parity vs the f32
cache, the bf16 divergence bound, the OTPU_CACHE_DTYPE kill-switch
(bitwise legacy + zero new compiles), capacity/fusion-gate economics, the
versioned spill format (old flat-f32 files stay readable), spill-file
hygiene on aborted fits, the _DeviceCache degrade un-latch, and the
encode's one native hash + bit-pack pass held bitwise to the numpy pair
(with its fallback when the library cannot be had)."""

import gc
import os
import warnings

import numpy as np
import pytest

import jax.numpy as jnp

from orange3_spark_tpu.io import native
from orange3_spark_tpu.io.codec import (
    BF16, bit_width, force_cache_dtype, pack_rows_np, resolve_cache_dtype,
    unpack_rows,
)
from orange3_spark_tpu.io.streaming import (
    DiskChunkCache, StreamingLinearEstimator, _DeviceCache,
    array_chunk_source,
)
from orange3_spark_tpu.models.hashed_linear import (
    StreamingHashedLinearEstimator, _encode_chunk_np,
    estimate_cached_chunk_bytes, resolve_chunk_codec,
)
from orange3_spark_tpu.obs.registry import REGISTRY
from orange3_spark_tpu.ops.hashing import (
    column_salts, hash_columns, hash_columns_np,
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


# ------------------------- the encode's native pass against the numpy pair

ENCODE_COUNTER = "otpu_encode_chunks_total"


@pytest.fixture(scope="module")
def fastcsv():
    try:
        return native.get_lib()
    except native.NativeUnavailable as e:
        pytest.skip(f"no native library here: {e}")


def _cat_block(n, n_cat, strided, seed):
    """[n, n_cat] f32 codes as the parser leaves them — 24-bit codes, with
    zeros, negatives and NaN cells among them — contiguous, or the
    categorical columns of a 40-wide parsed chunk (row stride 40)."""
    rng = np.random.default_rng(seed)
    cats = rng.integers(0, 1 << 24, (n, n_cat)).astype(np.float32)
    cats[rng.random(cats.shape) < 0.1] = 0.0
    cats[rng.random(cats.shape) < 0.1] *= -1.0
    cats[rng.random(cats.shape) < 0.1] = np.nan
    if not strided:
        return cats
    chunk = rng.standard_normal((n, 40)).astype(np.float32)
    chunk[:, 40 - n_cat:] = cats
    view = chunk[:, 40 - n_cat:]
    assert view.strides == (160, 4) and not (n > 1 and view.flags.c_contiguous)
    return view


def _numpy_pair(cats, salts, n_dims, bits, impute):
    if impute:
        cats = np.where(np.isnan(cats), np.float32(0.0), cats)
    with np.errstate(invalid="ignore"):     # NaN -> int32 under 'keep'
        return pack_rows_np(hash_columns_np(cats, salts, n_dims), bits)


@pytest.mark.parametrize("impute", [True, False], ids=["impute", "keep"])
@pytest.mark.parametrize("strided", [False, True],
                         ids=["contiguous", "stride40"])
@pytest.mark.parametrize("n_cat", [1, 26, 27])
@pytest.mark.parametrize("bits", [1, 7, 24, 29, 30, 31])
def test_native_hash_pack_is_the_numpy_pair_bitwise(fastcsv, bits, n_cat,
                                                    strided, impute):
    """Every row count (one row, an odd count, a rehearsal chunk): the same
    u32 words as ``pack_rows_np(hash_columns_np(...))``, word for word."""
    n_dims = 1 << bits
    salts = column_salts(n_cat, seed=bits)
    for n in (1, 1000, 32768):
        cats = _cat_block(n, n_cat, strided, seed=n + n_cat)
        want = _numpy_pair(cats, salts, n_dims, bits, impute)
        got = native.hash_pack_rows(cats, salts, n_dims, bits, impute=impute)
        assert got.dtype == np.uint32 and got.shape == want.shape
        np.testing.assert_array_equal(got, want, err_msg=f"{n=}")


@pytest.mark.parametrize("n_dims", [1 << 29, 1 << 30])
def test_native_words_unpack_to_the_device_buckets(fastcsv, n_dims):
    """What the step reads: ``unpack_rows`` of the native words is
    ``hash_columns`` of the imputed codes, the in-jit hash."""
    bits = bit_width(n_dims)
    cats = _cat_block(1000, 26, strided=True, seed=3)
    salts = column_salts(26, seed=0)
    words = native.hash_pack_rows(cats, salts, n_dims, bits, impute=True)
    assert words.shape == (1000, -(-(26 * bits) // 32))
    imputed = jnp.where(jnp.isnan(cats), 0.0, jnp.asarray(cats))
    np.testing.assert_array_equal(
        np.asarray(unpack_rows(jnp.asarray(words), bits, 26)),
        np.asarray(hash_columns(imputed, salts, n_dims)))


def test_native_hash_pack_refuses_what_numpy_refuses(fastcsv):
    cats = _cat_block(8, 3, strided=False, seed=1)
    salts = column_salts(3)
    with pytest.raises(ValueError, match="power of two"):
        native.hash_pack_rows(cats, salts, 1000, 10, impute=True)
    with pytest.raises(ValueError, match="bit width"):
        native.hash_pack_rows(cats, salts, 1 << 10, 32, impute=True)
    # a pack wider than the hash's mask, and a block the pass does not
    # read in place (f64, or columns not adjacent): the numpy pair's
    # bytes, or None for the caller to run it
    np.testing.assert_array_equal(
        native.hash_pack_rows(cats, salts, 1 << 10, 16, impute=True),
        _numpy_pair(cats, salts, 1 << 10, 16, True))
    assert native.hash_pack_rows(cats.astype(np.float64), salts, 1 << 10,
                                 10, impute=True) is None
    assert native.hash_pack_rows(cats[:, ::2], salts[::2], 1 << 10, 10,
                                 impute=True) is None


def _packed_codec(n_dims=1 << 29):
    return resolve_chunk_codec(StreamingHashedLinearEstimator(
        n_dims=n_dims, n_dense=13, n_cat=26, loss="squared_hinge",
        label_in_chunk=True, cache_dtype="packed").params)


def _parsed_chunk(n, seed):
    rng = np.random.default_rng(seed)
    chunk = np.empty((n, 40), np.float32)
    chunk[:, 0] = rng.integers(0, 2, n)
    chunk[:, 1:14] = rng.standard_normal((n, 13))
    chunk[:, 14:] = _cat_block(n, 26, strided=False, seed=seed)
    return chunk


def test_failed_build_falls_back_to_numpy_once(monkeypatch, tmp_path):
    """A host without a toolchain: the first chunk's encode tries the build,
    every chunk gets the numpy pair's bytes, the counter says ``numpy``,
    and g++ is not asked again per chunk."""
    attempts = []

    def no_toolchain():
        attempts.append(1)
        raise native.NativeUnavailable("fastcsv build failed: no g++")

    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_lib_error", None)
    monkeypatch.setattr(native, "_LIB", str(tmp_path / "_fastcsv.so"))
    monkeypatch.setattr(native, "_build", no_toolchain)
    REGISTRY.reset([ENCODE_COUNTER])
    codec, salts = _packed_codec(), column_salts(26, seed=0)
    assert codec.mode == "packed" and codec.impute
    for i in range(5):
        chunk = _parsed_chunk(512, seed=i)
        enc = _encode_chunk_np(codec, chunk, salts)
        np.testing.assert_array_equal(
            enc["cats"],
            _numpy_pair(chunk[:, 14:], salts, codec.n_dims, codec.idx_bits,
                        True))
    assert len(attempts) == 1
    with pytest.raises(native.NativeUnavailable, match="no g[+][+]"):
        native.get_lib()
    assert len(attempts) == 1
    counter = REGISTRY.get(ENCODE_COUNTER)
    assert counter.value(how="numpy") == 5 and counter.value(how="native") == 0


def test_encode_keeps_its_dict_and_counts_native(fastcsv):
    """Same keys, dtypes and shapes as before the native pass; labels and
    the dense block stay numpy; one count a chunk."""
    REGISTRY.reset([ENCODE_COUNTER])
    codec, salts = _packed_codec(1 << 30), column_salts(26, seed=0)
    chunk = _parsed_chunk(512, seed=9)
    enc = _encode_chunk_np(codec, chunk, salts)
    assert {k: (v.dtype, v.shape) for k, v in enc.items()} == {
        "y": (np.dtype(np.uint8), (512,)),
        "dense": (np.dtype(BF16), (512, 13)),
        "cats": (np.dtype(np.uint32), (512, 25))}
    np.testing.assert_array_equal(
        enc["cats"], _numpy_pair(chunk[:, 14:], salts, 1 << 30, 30, True))
    counter = REGISTRY.get(ENCODE_COUNTER)
    assert counter.value(how="native") == 1 and counter.value(how="numpy") == 0


def test_fit_is_bitwise_the_same_native_or_numpy(fastcsv, session, data,
                                                 monkeypatch):
    """The same tiny stream fitted with the native pass and with the
    library taken away: every leaf of ``theta`` equal to the bit."""
    Xall, y = data
    Xall = Xall.copy()
    Xall[::7, 5] = np.nan               # an empty categorical cell
    REGISTRY.reset([ENCODE_COUNTER])
    counter = REGISTRY.get(ENCODE_COUNTER)
    m_native = _fit(session, Xall, y, "packed")
    chunks = counter.value(how="native")
    assert chunks >= 4 and counter.value(how="numpy") == 0

    def no_library():
        raise native.NativeUnavailable("taken away")

    monkeypatch.setattr(native, "get_lib", no_library)
    m_numpy = _fit(session, Xall, y, "packed")
    assert counter.value(how="numpy") == chunks
    assert counter.value(how="native") == chunks
    assert m_native.n_steps_ == m_numpy.n_steps_
    for leaf in m_native.theta:
        np.testing.assert_array_equal(np.asarray(m_native.theta[leaf]),
                                      np.asarray(m_numpy.theta[leaf]), leaf)


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
