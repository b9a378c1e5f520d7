"""Hashed-sparse path (Criteo headline shape) — device hashing + streaming
fit + exactness of the gather-based forward vs a dense one-hot matmul."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from orange3_spark_tpu.models.hashed_linear import (
    HashedLinearParams,
    StreamingHashedLinearEstimator,
    _hashed_logits,
)
from orange3_spark_tpu.ops.hashing import column_salts, hash_columns, strings_to_u32


def _criteo_shaped(n, n_dense=4, n_cat=6, card=50, seed=0):
    """Synthetic Criteo-shaped data: labels driven by a few categorical
    levels + a dense signal, like real CTR data."""
    rng = np.random.default_rng(seed)
    dense = rng.standard_normal((n, n_dense)).astype(np.float32)
    cats = rng.integers(0, card, size=(n, n_cat)).astype(np.float32)
    # per-(column, level) latent effect
    effects = rng.normal(0, 1.2, size=(n_cat, card))
    logit = dense[:, 0] - 0.5 * dense[:, 1]
    for j in range(n_cat):
        logit = logit + effects[j, cats[:, j].astype(int)]
    y = (logit + 0.3 * rng.standard_normal(n) > 0).astype(np.float32)
    return np.concatenate([dense, cats], axis=1), y


def test_hash_columns_in_range_and_salted():
    salts = column_salts(3, seed=1)
    cats = jnp.asarray(np.random.default_rng(0).integers(0, 1000, (200, 3)))
    idx = np.asarray(hash_columns(cats, salts, 512))
    assert idx.min() >= 0 and idx.max() < 512
    # same raw code in different columns -> different buckets (salting)
    same = jnp.full((50, 3), 7)
    idx2 = np.asarray(hash_columns(same, salts, 512))
    assert len(set(idx2[0])) > 1
    # deterministic
    np.testing.assert_array_equal(idx, np.asarray(hash_columns(cats, salts, 512)))


def test_hash_columns_spread():
    """Buckets must be roughly uniform (murmur finalizer avalanche)."""
    salts = column_salts(1)
    codes = jnp.arange(8192)[:, None]
    idx = np.asarray(hash_columns(codes, salts, 256)).ravel()
    counts = np.bincount(idx, minlength=256)
    assert counts.max() < 3 * counts.mean()


def test_hash_columns_rejects_non_power_of_two():
    with pytest.raises(ValueError, match="power of two"):
        hash_columns(jnp.zeros((2, 2)), column_salts(2), 100)


def test_strings_to_u32_stable_and_distinct():
    a = strings_to_u32(np.array([["ad4f", "x"], ["ad4f", "y"]]))
    assert a.dtype == np.uint32
    assert a[0, 0] == a[1, 0]
    assert a[0, 1] != a[1, 1]
    np.testing.assert_array_equal(
        a, strings_to_u32(np.array([["ad4f", "x"], ["ad4f", "y"]]))
    )


def test_hashed_forward_equals_dense_onehot(session):
    """The gather-based forward must equal a dense one-hot matmul exactly."""
    rng = np.random.default_rng(2)
    n, n_dense, n_cat, D, k = 64, 3, 5, 256, 2
    Xall = np.concatenate(
        [rng.standard_normal((n, n_dense)).astype(np.float32),
         rng.integers(0, 40, (n, n_cat)).astype(np.float32)], axis=1
    )
    salts = column_salts(n_cat, seed=3)
    theta = {
        "emb": jnp.asarray(rng.standard_normal((D, k)), jnp.float32),
        "coef": jnp.asarray(rng.standard_normal((n_dense, k)), jnp.float32),
        "intercept": jnp.asarray(rng.standard_normal(k), jnp.float32),
    }
    idx = hash_columns(jnp.asarray(Xall[:, n_dense:]), salts, D)
    got = _hashed_logits(theta, jnp.asarray(Xall[:, :n_dense]), idx, jnp.float32)

    onehot = np.zeros((n, D), np.float32)
    for i in range(n):
        for j in range(n_cat):
            onehot[i, np.asarray(idx)[i, j]] += 1.0  # += : collisions stack
    want = (
        onehot @ np.asarray(theta["emb"])
        + Xall[:, :n_dense] @ np.asarray(theta["coef"])
        + np.asarray(theta["intercept"])
    )
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


def test_streaming_hashed_fit_learns(session):
    from orange3_spark_tpu.io.streaming import array_chunk_source

    Xall, y = _criteo_shaped(6000, seed=4)
    est = StreamingHashedLinearEstimator(
        n_dims=1 << 12, n_dense=4, n_cat=6, epochs=6, step_size=0.05,
        chunk_rows=1024,
    )
    model = est.fit_stream(
        array_chunk_source(Xall, y, chunk_rows=1000), session=session
    )
    acc = np.mean(model.predict(Xall) == y)
    assert acc > 0.85, f"hashed fit failed to learn: acc={acc}"
    metrics = model.evaluate_stream(
        lambda: iter([(Xall, y)])
    )
    assert metrics["accuracy"] == pytest.approx(acc, abs=1e-6)
    assert metrics["auc"] > 0.9
    assert metrics["logloss"] < 0.45


def test_hashed_fit_binary_auc_beats_dense_truncation(session):
    """The whole point of hashing: categorical signal a dense-numeric model
    cannot see. A dense logreg on the raw codes-as-numbers must lose."""
    from orange3_spark_tpu.core.domain import (
        ContinuousVariable, DiscreteVariable, Domain,
    )
    from orange3_spark_tpu.core.table import TpuTable
    from orange3_spark_tpu.io.streaming import array_chunk_source
    from orange3_spark_tpu.models.logistic_regression import LogisticRegression

    Xall, y = _criteo_shaped(4000, seed=5)
    hashed = StreamingHashedLinearEstimator(
        n_dims=1 << 12, n_dense=4, n_cat=6, epochs=6, step_size=0.05,
        chunk_rows=1024,
    ).fit_stream(array_chunk_source(Xall, y, chunk_rows=1024), session=session)
    acc_hashed = np.mean(hashed.predict(Xall) == y)

    dom = Domain(
        [ContinuousVariable(f"f{i}") for i in range(Xall.shape[1])],
        DiscreteVariable("y", ("0", "1")),
    )
    t = TpuTable.from_numpy(dom, Xall, y, session=session)
    dense = LogisticRegression(max_iter=200).fit(t)
    acc_dense = np.mean(dense.predict(t) == y)
    assert acc_hashed > acc_dense + 0.05


def test_hashed_checkpoint_resume_bit_identical(session, tmp_path):
    """Kill-and-resume must land on identical parameters (fault drill,
    SURVEY.md §5 failure injection)."""
    from orange3_spark_tpu.io.streaming import array_chunk_source
    from orange3_spark_tpu.utils.fault import StreamCheckpointer

    Xall, y = _criteo_shaped(3000, seed=6)
    kw = dict(
        n_dims=1 << 10, n_dense=4, n_cat=6, epochs=2, step_size=0.05,
        chunk_rows=512,
    )
    src = lambda: array_chunk_source(Xall, y, chunk_rows=512)()

    full = StreamingHashedLinearEstimator(**kw).fit_stream(src, session=session)

    class Killed(Exception):
        pass

    ck = StreamCheckpointer(str(tmp_path / "ck"), every_steps=3)
    killing = StreamCheckpointer(str(tmp_path / "ck"), every_steps=3)
    orig = killing.maybe_save
    calls = {"n": 0}

    def boom(step, state, meta=None):
        orig(step, state, meta=meta)
        calls["n"] += 1
        if calls["n"] == 2:
            raise Killed

    killing.maybe_save = boom
    with pytest.raises(Killed):
        StreamingHashedLinearEstimator(**kw).fit_stream(
            src, session=session, checkpointer=killing
        )
    resumed = StreamingHashedLinearEstimator(**kw).fit_stream(
        src, session=session, checkpointer=ck
    )
    np.testing.assert_array_equal(
        np.asarray(full.theta["emb"]), np.asarray(resumed.theta["emb"])
    )
    np.testing.assert_array_equal(
        np.asarray(full.theta["coef"]), np.asarray(resumed.theta["coef"])
    )


def test_fused_replay_matches_per_step_loop(session):
    """Epochs 2+ as one scan program (fused_replay=True + cache_device) must
    match the per-chunk dispatch loop numerically — same ops, same order,
    one dispatch instead of (epochs-1) x n_chunks."""
    from orange3_spark_tpu.io.streaming import array_chunk_source

    Xall, y = _criteo_shaped(4096, seed=7)

    def fit(fused: bool):
        est = StreamingHashedLinearEstimator(
            n_dims=1 << 12, n_dense=4, n_cat=6, epochs=4, step_size=0.05,
            chunk_rows=1024, fused_replay=fused,
        )
        return est.fit_stream(
            array_chunk_source(Xall, y, chunk_rows=1024),
            session=session, cache_device=True,
        )

    fused, looped = fit(True), fit(False)
    assert fused.n_steps_ == looped.n_steps_
    np.testing.assert_allclose(
        np.asarray(fused.theta["emb"]), np.asarray(looped.theta["emb"]),
        rtol=2e-5, atol=2e-7,
    )
    np.testing.assert_allclose(
        np.asarray(fused.theta["coef"]), np.asarray(looped.theta["coef"]),
        rtol=2e-5, atol=2e-7,
    )
    pred_f, pred_l = fused.predict(Xall), looped.predict(Xall)
    assert np.mean(pred_f == pred_l) > 0.999


def test_epoch_granularity_matches_all(session):
    """replay_granularity='epoch' (one n_epochs=1 scan dispatch per epoch)
    runs the same
    step math in the same order as the single n_epochs-1 scan, so the fits
    must agree to float tolerance and report their own replay_source."""
    from orange3_spark_tpu.io.streaming import array_chunk_source

    Xall, y = _criteo_shaped(4096, seed=11)

    def fit(gran: str):
        est = StreamingHashedLinearEstimator(
            n_dims=1 << 12, n_dense=4, n_cat=6, epochs=5, step_size=0.05,
            chunk_rows=1024, fused_replay=True, replay_granularity=gran,
        )
        st: dict = {}
        model = est.fit_stream(
            array_chunk_source(Xall, y, chunk_rows=1024),
            session=session, cache_device=True, stage_times=st,
        )
        return model, st

    all_m, all_st = fit("all")
    ep_m, ep_st = fit("epoch")
    assert all_st["replay_source"] == "fused"
    assert ep_st["replay_source"] == "fused_epoch"
    assert all_m.n_steps_ == ep_m.n_steps_
    np.testing.assert_allclose(
        np.asarray(all_m.theta["emb"]), np.asarray(ep_m.theta["emb"]),
        rtol=2e-5, atol=2e-7,
    )
    np.testing.assert_allclose(
        np.asarray(all_m.theta["coef"]), np.asarray(ep_m.theta["coef"]),
        rtol=2e-5, atol=2e-7,
    )


def test_fused_replay_respects_holdout(session):
    """Holdout chunks must stay out of the fused replay scan too."""
    from orange3_spark_tpu.io.streaming import array_chunk_source

    Xall, y = _criteo_shaped(4096, seed=8)
    est = StreamingHashedLinearEstimator(
        n_dims=1 << 12, n_dense=4, n_cat=6, epochs=3, step_size=0.05,
        chunk_rows=1024, fused_replay=True,
    )
    st: dict = {}
    model = est.fit_stream(
        array_chunk_source(Xall, y, chunk_rows=1024), session=session,
        cache_device=True, holdout_chunks=1, stage_times=st,
    )
    # 4 chunks, 1 held out -> 3 train chunks x 3 epochs
    assert model.n_steps_ == 9
    assert len(model.holdout_chunks_) == 1
    assert "replay_fused_s" in st
    ev = model.evaluate_device(model.holdout_chunks_)
    assert 0.0 < ev["logloss"] < 2.0
