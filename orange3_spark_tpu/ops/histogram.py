"""Node×bin histogram accumulation — the tree-induction hot loop, as a
Pallas TPU kernel.

MLlib's RandomForest/GBT spends its time in ``DecisionTree.findBestSplits``:
per tree level, aggregate per-(node, feature, bin) label statistics over all
rows (a treeAggregate of DTStatsAggregator arrays; SURVEY.md §2b "RandomForest
/ GBT" row budgets exactly this kernel — reconstructed, mount empty). The
XLA-only formulation is d ``segment_sum`` scatters, which lower to serialized
scatter-adds on TPU (no MXU, HBM-bound). The Pallas redesign turns the
scatter into matmuls:

    for each row block (grid step), for each feature j:
        onehot = (pos * n_bins + B[:, j]) == iota(nodes·bins)   # VPU compare
        H[j]  += onehotᵀ @ S                                    # MXU [nb,s]

* the one-hot never exists in HBM — it is built in VMEM per (block, feature)
  and immediately contracted on the MXU;
* the accumulator ``H[d, nodes·bins, s]`` lives in VMEM across all grid
  steps (same output block every step — Pallas' revisiting-accumulator
  pattern), written back to HBM once;
* rows are the grid axis, so the kernel scales linearly in N with a fixed
  VMEM footprint; row padding carries S = 0 and contributes nothing.

``node_histograms`` picks the backend: Pallas on TPU, the segment_sum
formulation elsewhere (CPU tests, fake-device meshes), same signature.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# flip to force a backend: "pallas" | "xla" | "" (auto)
_FORCE = os.environ.get("OTPU_HISTOGRAM_BACKEND", "")

_VMEM_ONEHOT_BUDGET = 4 << 20  # bytes for the [blk, nb] one-hot per step


def _hist_kernel(k_ref, st_ref, out_ref, *, d: int, nb: int):
    """k_ref: i32[d, blk] node*bins+bin keys (features on sublanes so the
    per-feature slice is a ROW — Mosaic cannot dynamically index lanes);
    st_ref: f32[s, blk] stats transposed; out_ref: f32[d, s, nb]."""

    @pl.when(pl.program_id(0) == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    St = st_ref[:]                                 # [s, blk]
    blk = St.shape[1]
    bins_iota = jax.lax.broadcasted_iota(jnp.int32, (blk, nb), 1)

    def body(j, _):
        key = k_ref[j, :]                          # [blk] lane vector
        onehot = (key[:, None] == bins_iota).astype(jnp.float32)  # [blk, nb]
        # [s, blk] @ [blk, nb] -> [s, nb] on the MXU. HIGHEST: the MXU's
        # default bf16 operand rounding loses ~3 decimal digits of the
        # stats, which the impurity-gain argmax downstream can feel
        contrib = jnp.dot(St, onehot, preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)
        out_ref[j] += contrib
        return 0

    jax.lax.fori_loop(0, d, body, 0)


@functools.partial(jax.jit, static_argnames=("nodes", "n_bins", "interpret"))
def _hist_pallas(B, S, pos, *, nodes: int, n_bins: int, interpret: bool = False):
    N, d = B.shape
    s = S.shape[1]
    nb = nodes * n_bins
    # block size: keep the [blk, nb] one-hot within the VMEM budget
    blk = max(512, min(4096, _VMEM_ONEHOT_BUDGET // (nb * 4)))
    blk = (blk // 128) * 128
    n_blocks = pl.cdiv(N, blk)
    n_pad = n_blocks * blk
    # fold node position into the key OUTSIDE the kernel (fused XLA add),
    # and transpose so rows are the lane axis of both operands
    K = (pos[:, None] * n_bins + B).astype(jnp.int32).T       # [d, N]
    St = S.T                                                  # [s, N]
    if n_pad != N:
        # padding rows: key 0 but S rows are zero => no contribution
        K = jnp.pad(K, ((0, 0), (0, n_pad - N)))
        St = jnp.pad(St, ((0, 0), (0, n_pad - N)))
    kernel = functools.partial(_hist_kernel, d=d, nb=nb)
    with jax.named_scope("hist/kernel"):
        out = pl.pallas_call(
            kernel,
            grid=(n_blocks,),
            in_specs=[
                pl.BlockSpec((d, blk), lambda i: (0, i),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((s, blk), lambda i: (0, i),
                             memory_space=pltpu.VMEM),
            ],
            # every grid step maps to the SAME output block: VMEM-resident
            # accumulator, flushed to HBM after the last step
            out_specs=pl.BlockSpec((d, s, nb), lambda i: (0, 0, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((d, s, nb), jnp.float32),
            interpret=interpret,
        )(K, St)
    return out.transpose(0, 2, 1)                  # [d, nb, s] like the XLA path


def _hist_xla(B, S, pos, *, nodes: int, n_bins: int):
    d = B.shape[1]

    def one_feature(j):
        key = pos * n_bins + B[:, j]
        return jax.ops.segment_sum(S, key, num_segments=nodes * n_bins)

    return jax.vmap(one_feature)(jnp.arange(d))


def node_histograms(B, S, pos, *, nodes: int, n_bins: int):
    """Per-(feature, node, bin) stat sums: f32[d, nodes*n_bins, s].

    B: i32[N, d] binned features; S: f32[N, s] per-row stats (zero on dead
    rows); pos: i32[N] node index of each row within the current level.
    """
    backend = _FORCE or ("pallas" if jax.default_backend() == "tpu" else "xla")
    if backend == "pallas":
        return _hist_pallas(B, S, pos, nodes=nodes, n_bins=n_bins)
    if backend == "pallas-interpret":  # CPU correctness testing of the kernel
        return _hist_pallas(B, S, pos, nodes=nodes, n_bins=n_bins, interpret=True)
    with jax.named_scope("hist/xla"):
        return _hist_xla(B, S, pos, nodes=nodes, n_bins=n_bins)
