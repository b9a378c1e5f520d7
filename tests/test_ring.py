"""Ring / all-to-all sequence parallelism vs dense attention oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from orange3_spark_tpu.parallel.ring import (
    reference_attention,
    ring_attention,
    ulysses_attention,
)


@pytest.fixture(scope="module")
def mesh():
    return jax.make_mesh((8,), ("sp",))


def _qkv(seed=0, b=2, s=64, h=8, dh=16):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((b, s, h, dh)), jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_dense(mesh, causal):
    q, k, v = _qkv()
    sh = NamedSharding(mesh, P(None, "sp", None, None))
    qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))
    out = ring_attention(qs, ks, vs, mesh, "sp", causal=causal)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_dense(mesh, causal):
    q, k, v = _qkv(seed=1)
    sh = NamedSharding(mesh, P(None, "sp", None, None))
    qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))
    out = ulysses_attention(qs, ks, vs, mesh, "sp", causal=causal)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ring_attention_is_differentiable(mesh):
    q, k, v = _qkv(seed=2, s=32)
    sh = NamedSharding(mesh, P(None, "sp", None, None))
    qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))

    def loss(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh, "sp") ** 2)

    with jax.set_mesh(mesh):
        g = jax.jit(jax.grad(loss))(qs, ks, vs)
    assert np.isfinite(np.asarray(g).sum())

    def ref_loss(q, k, v):
        return jnp.sum(reference_attention(q, k, v) ** 2)

    g_ref = jax.grad(ref_loss)(q, k, v)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=3e-3, atol=3e-3)


def test_ulysses_rejects_indivisible_heads(mesh):
    q, k, v = _qkv(seed=3, h=6)  # 6 heads, 8-way axis
    with pytest.raises(ValueError, match="divisible"):
        ulysses_attention(q, k, v, mesh, "sp")
