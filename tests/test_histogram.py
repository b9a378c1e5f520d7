"""Pallas histogram kernel vs the segment_sum reference (SURVEY §2b trees)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from orange3_spark_tpu.ops.histogram import _hist_pallas, _hist_xla


@pytest.mark.parametrize("seed", range(8))
def test_pallas_interpret_matches_xla_randomized(seed):
    """Randomized-shape parity sweep: the fixed-shape cases below only ever
    exercised a handful of (nodes, bins, stats, rows, features) points —
    this sweep randomizes all five, including rows that are NOT a multiple
    of the kernel's 128-lane block (the padding path), odd feature counts,
    and single-node/single-stat degenerate shapes (VERDICT Weak #3)."""
    rng = np.random.default_rng(100 + seed)
    nodes = int(rng.choice([1, 2, 3, 5, 8]))
    n_bins = int(rng.choice([4, 8, 16, 32, 64]))
    s = int(rng.integers(1, 6))
    n = int(rng.integers(1, 3000))
    d = int(rng.integers(1, 9))
    B = jnp.asarray(rng.integers(0, n_bins, (n, d)), dtype=jnp.int32)
    S = jnp.asarray(rng.standard_normal((n, s)), dtype=jnp.float32)
    pos = jnp.asarray(rng.integers(0, nodes, n), dtype=jnp.int32)
    ref = _hist_xla(B, S, pos, nodes=nodes, n_bins=n_bins)
    got = _hist_pallas(B, S, pos, nodes=nodes, n_bins=n_bins, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5,
                               atol=1e-4,
                               err_msg=f"shape=({nodes},{n_bins},{s},{n},{d})")


@pytest.mark.parametrize("nodes,n_bins,s", [(1, 32, 3), (4, 16, 5), (8, 32, 2)])
def test_pallas_interpret_matches_xla(nodes, n_bins, s):
    rng = np.random.default_rng(0)
    n, d = 1000, 7
    B = jnp.asarray(rng.integers(0, n_bins, (n, d)), dtype=jnp.int32)
    S = jnp.asarray(rng.standard_normal((n, s)), dtype=jnp.float32)
    pos = jnp.asarray(rng.integers(0, nodes, n), dtype=jnp.int32)
    ref = _hist_xla(B, S, pos, nodes=nodes, n_bins=n_bins)
    got = _hist_pallas(B, S, pos, nodes=nodes, n_bins=n_bins, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5,
                               atol=1e-4)


def test_pallas_interpret_under_vmap_multiblock():
    """Forests vmap grow_tree over trees; the batched pallas_call must keep
    the per-tree accumulator init correct across MULTIPLE row blocks (the
    grid axis the init is keyed on). Verified on real TPU too (err ~1e-5)."""
    import functools

    rng = np.random.default_rng(2)
    t, n, d, s, n_bins, nodes = 3, 1200, 4, 2, 8, 2
    B = jnp.asarray(rng.integers(0, n_bins, (t, n, d)), dtype=jnp.int32)
    S = jnp.asarray(rng.standard_normal((t, n, s)), dtype=jnp.float32)
    pos = jnp.asarray(rng.integers(0, nodes, (t, n)), dtype=jnp.int32)
    import jax

    f = functools.partial(_hist_pallas, nodes=nodes, n_bins=n_bins,
                          interpret=True)
    g = functools.partial(_hist_xla, nodes=nodes, n_bins=n_bins)
    got = jax.vmap(f)(B, S, pos)
    ref = jax.vmap(g)(B, S, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5,
                               atol=1e-4)


def test_pallas_interpret_zero_weight_rows_ignored():
    rng = np.random.default_rng(1)
    n, d, s, n_bins = 512, 3, 2, 8
    B = jnp.asarray(rng.integers(0, n_bins, (n, d)), dtype=jnp.int32)
    S = jnp.asarray(rng.standard_normal((n, s)), dtype=jnp.float32)
    S = S.at[100:].set(0.0)  # dead rows carry zero stats
    pos = jnp.zeros((n,), jnp.int32)
    got = _hist_pallas(B, S, pos, nodes=1, n_bins=n_bins, interpret=True)
    ref = _hist_xla(B[:100], S[:100], pos[:100], nodes=1, n_bins=n_bins)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-5,
                               atol=1e-4)
