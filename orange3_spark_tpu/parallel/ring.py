"""Sequence/context parallelism: ring attention and all-to-all (Ulysses)
attention over a mesh axis.

The reference stack is tabular (no transformer path — SURVEY.md §5 "absent
in the reference"), but the framework's parallel substrate must handle
long-sequence workloads at the same scale its distributed runtime targets,
so these are core ``parallel/`` primitives, not model code:

* ``ring_attention`` — sequence axis sharded over the mesh; K/V blocks
  rotate around the ring with ``jax.lax.ppermute`` (ICI neighbor hops, no
  all-gather memory spike) while each device folds one block per hop into a
  flash-style online softmax (running max / normalizer / accumulator).
  Memory per device: O(S_local·S_local) scores — never the full S×S.
  Causal masking uses global block offsets from ``jax.lax.axis_index``.
* ``ulysses_attention`` — the all-to-all alternative: reshard sequence →
  heads with one ``all_to_all``, run dense local attention over the FULL
  sequence for the local head group, reshard back. One collective pair per
  call; best when n_heads % axis_size == 0 and S×S fits per device.

Both run under ``shard_map`` over a named mesh axis and are differentiable
(pure jnp + collectives, so jax.grad traces through the ppermute ring).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def _online_block(q, k, v, m, l, o, mask):
    """Fold one K/V block into the flash accumulator (q: [B,Sq,H,Dh])."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, -jnp.inf)
    m_blk = jnp.max(s, axis=-1)                              # [B,H,Sq]
    m_new = jnp.maximum(m, m_blk)
    # guard -inf - -inf (fully masked row so far)
    alpha = jnp.exp(jnp.where(jnp.isfinite(m), m - m_new, -jnp.inf))
    p = jnp.exp(jnp.where(jnp.isfinite(s), s - m_new[..., None], -jnp.inf))
    l_new = alpha * l + jnp.sum(p, axis=-1)
    o_new = alpha[..., None] * o + jnp.einsum(
        "bhqk,bkhd->bhqd", p, v, preferred_element_type=jnp.float32
    )
    return m_new, l_new, o_new


def ring_attention(q, k, v, mesh: Mesh, axis: str = "sp", *, causal: bool = False):
    """Attention with Q/K/V sharded over ``axis`` along the sequence dim.

    q, k, v: f32[batch, seq, heads, head_dim] (seq divisible by axis size).
    Returns the attention output with the same sharding.
    """
    n = mesh.shape[axis]
    spec = P(None, axis, None, None)

    def local(qb, kb, vb):
        # qb/kb/vb: [B, S_loc, H, Dh] — this device's sequence block
        idx = jax.lax.axis_index(axis)
        b, s_loc, h, dh = qb.shape
        # mark the accumulators device-varying for the manual-axes carry check
        # (they start as replicated literals but each device's values diverge)
        def _varying(x):
            return jax.lax.pcast(x, (axis,), to="varying")

        m = _varying(jnp.full((b, h, s_loc), -jnp.inf, jnp.float32))
        l = _varying(jnp.zeros((b, h, s_loc), jnp.float32))
        o = _varying(jnp.zeros((b, h, s_loc, dh), jnp.float32))
        q_pos = idx * s_loc + jnp.arange(s_loc)              # global Q rows

        def block_mask(t):
            if not causal:
                return jnp.ones((1, 1, s_loc, s_loc), bool)
            src_idx = (idx - t) % n                          # whose block this is
            k_pos = src_idx * s_loc + jnp.arange(s_loc)
            return (q_pos[:, None] >= k_pos[None, :])[None, None, :, :]

        def fold(t, m, l, o, kb, vb):
            return _online_block(qb, kb, vb, m, l, o, block_mask(t))

        def hop(t, carry):
            m, l, o, kb, vb = carry
            m, l, o = fold(t, m, l, o, kb, vb)
            # rotate K/V one step around the ring (neighbor ICI hop)
            perm = [(i, (i + 1) % n) for i in range(n)]
            kb = jax.lax.ppermute(kb, axis, perm)
            vb = jax.lax.ppermute(vb, axis, perm)
            return m, l, o, kb, vb

        # n-1 fold+rotate hops, then fold the final block WITHOUT rotating —
        # the last ppermute's result would be discarded, but as a loop carry
        # XLA could not DCE the send/recv pair
        m, l, o, kb, vb = jax.lax.fori_loop(0, n - 1, hop, (m, l, o, kb, vb))
        m, l, o = fold(n - 1, m, l, o, kb, vb)
        out = o / jnp.maximum(l[..., None], 1e-30)           # [B,H,Sq,Dh]
        return out.transpose(0, 2, 1, 3)                     # [B,Sq,H,Dh]

    return jax.shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )(q, k, v)


def ulysses_attention(q, k, v, mesh: Mesh, axis: str = "sp", *,
                      causal: bool = False):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses pattern).

    Seq-sharded [B, S/n, H, Dh] --all_to_all--> head-sharded [B, S, H/n, Dh],
    dense local attention over the full sequence, then all_to_all back.
    Requires heads % axis_size == 0.
    """
    n = mesh.shape[axis]
    if q.shape[2] % n != 0:
        raise ValueError(f"heads={q.shape[2]} not divisible by {axis} size {n}")
    spec = P(None, axis, None, None)

    def local(qb, kb, vb):
        # [B, S_loc, H, Dh] -> [B, S, H_loc, Dh]: split heads, gather seq
        def seq_to_heads(x):
            return jax.lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                                      tiled=True)

        def heads_to_seq(x):
            return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                                      tiled=True)

        qh, kh, vh = seq_to_heads(qb), seq_to_heads(kb), seq_to_heads(vb)
        return heads_to_seq(_dense_attention(qh, kh, vh, causal=causal))

    return jax.shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )(q, k, v)


def _dense_attention(q, k, v, *, causal: bool = False):
    """Scaled dot-product attention over full [B,S,H,Dh] operands."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        S = q.shape[1]
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v,
                      preferred_element_type=jnp.float32)


def reference_attention(q, k, v, *, causal: bool = False):
    """Single-device dense attention (numerics oracle for the tests)."""
    return _dense_attention(q, k, v, causal=causal)
