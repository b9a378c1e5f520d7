"""Compressed device-resident chunk codec — the cache-precision subsystem.

The replay wall is HBM bandwidth (BENCH_r05: ``device_hbm_gbps_est``
dominates ``pure_step_ms``), and both the ``_DeviceCache`` fusion gate and
the disk spill priced every chunk at padded **f32** — so datasets fell off
the fused-replay cliff at half the rows they needed to. This module owns
the storage-side fix, the mixed-precision pattern standard in large-scale
training input pipelines: cache/spill/transfer chunks COMPRESSED and widen
them inside the jitted step (a cheap decode XLA fuses into the consumer),
so HBM, disk and the h2d DMA all move ~2x fewer bytes while the math stays
f32.

Three cache dtypes, resolved ONCE at fit entry (the resolution is a
static jit argument, never the env var):

* ``'f32'``    — the legacy layout, bit-for-bit. The kill-switch target.
* ``'bf16'``   — dense float features stored bfloat16 (lossy, bounded:
  round-to-nearest-even, relative error <= 2^-8); integer-carrying columns
  (labels where exact, categorical codes) stay exact.
* ``'packed'`` — bf16 floats PLUS lossless integer bit-packing: values with
  a statically known range (hashed categorical indices bounded by
  ``n_dims``) are stored at their true bit width in a u32 carrier and
  unpacked with static shifts/masks in-jit.

Layering: this module knows nothing about chunk layouts or models — it
provides the primitives (bit packing, bf16 host encode) and the policy
resolver; ``models/hashed_linear`` and ``io/streaming`` own their layouts.

Bit-packing layout (decodes with STATIC shift/mask ops — no gathers):
per-row, ``[N, C]`` values at ``b`` bits -> ``[N, ceil(C*b/32)]`` u32.
Row-aligned, so the packed array row-shards exactly like the raw one.
"""

from __future__ import annotations

import contextlib
import os

import jax.numpy as jnp
import ml_dtypes
import numpy as np

__all__ = [
    "CACHE_DTYPES", "BF16", "SpillCorruptionError", "resolve_cache_dtype",
    "force_cache_dtype", "bit_width", "pack_rows_np", "unpack_rows",
]


class SpillCorruptionError(RuntimeError):
    """A spill record failed integrity verification (CRC mismatch,
    truncated tail, or an impossible live-row count). Raised by
    ``io.streaming.DiskChunkCache`` naming the record ordinal — the
    alternative is silently decoding garbage into a 100-epoch replay.
    Version-2 spill files carry a per-record CRC32; the check is skipped
    under the ``OTPU_RESILIENCE=0`` kill-switch (legacy decode-anything
    behavior) and for pre-CRC files (versions 0/1, which stay readable)."""

CACHE_DTYPES = ("f32", "bf16", "packed")

#: the host-side bfloat16 dtype (numpy has none; jax ships ml_dtypes).
#: ``np.astype(BF16)`` rounds to nearest even — identical to the device's
#: ``astype(jnp.bfloat16)``, so host-encoded chunks decode the same bits.
BF16 = ml_dtypes.bfloat16


def resolve_cache_dtype(value: str, session=None) -> str:
    """The concrete cache dtype for this fit — THE one resolver, applied
    ONCE at fit entry so the resolved value is a static jit argument.

    ``OTPU_CACHE_DTYPE`` (the kill-switch, read per resolution) overrides
    the param when set: ``=f32`` restores the legacy cache exactly whatever
    the caller asked for; ``=bf16``/``=packed`` force a mode (the bench
    sweep's lever). ``'auto'`` resolves to the session policy knob
    ``TpuSession.default_cache_dtype`` ('packed' — full compression)."""
    env = os.environ.get("OTPU_CACHE_DTYPE", "")
    if env:
        value = env
    if value == "auto":
        if session is None:
            from orange3_spark_tpu.core.session import TpuSession

            session = TpuSession.active()
        value = session.default_cache_dtype
    if value not in CACHE_DTYPES:
        raise ValueError(
            f"cache_dtype must be one of {CACHE_DTYPES} or 'auto', "
            f"got {value!r}"
        )
    return value


@contextlib.contextmanager
def force_cache_dtype(value: str):
    """Pin the resolver for one bench arm. The env kill-switch outranks
    the param BY DESIGN (so ``OTPU_CACHE_DTYPE=f32`` restores the legacy
    cache whatever a caller hard-coded), which means A/B sweeps must pin
    arms through the same lever — this scopes it and restores the
    ambient value afterwards."""
    old = os.environ.get("OTPU_CACHE_DTYPE")
    os.environ["OTPU_CACHE_DTYPE"] = value
    try:
        yield
    finally:
        if old is None:
            os.environ.pop("OTPU_CACHE_DTYPE", None)
        else:
            os.environ["OTPU_CACHE_DTYPE"] = old


def bit_width(n_values: int) -> int:
    """Bits needed to hold values ``0 .. n_values-1`` (at least 1)."""
    return max(1, int(n_values - 1).bit_length())


def _check_bits(bits: int) -> np.uint32:
    if not 1 <= bits <= 31:
        raise ValueError(f"pack bit width must be in [1, 31], got {bits}")
    return np.uint32((1 << bits) - 1)


def pack_rows_np(vals: np.ndarray, bits: int) -> np.ndarray:
    """Host-side per-row pack: ``[N, C]`` unsigned values at ``bits`` bits
    each -> ``[N, ceil(C*bits/32)]`` u32 words. Values must already be in
    range (high bits are masked off, silently — callers pack statically
    bounded quantities)."""
    mask = _check_bits(bits)
    vals = np.asarray(vals).astype(np.uint32) & mask
    N, C = vals.shape
    W = -(-(C * bits) // 32)
    words = np.zeros((N, W), np.uint32)
    for c in range(C):
        bitpos = c * bits
        w0, off = bitpos // 32, bitpos % 32
        v = vals[:, c]
        words[:, w0] |= v << np.uint32(off)
        if off + bits > 32:
            words[:, w0 + 1] |= v >> np.uint32(32 - off)
    return words


def unpack_rows(packed, bits: int, n_cols: int):
    """In-jit inverse of ``pack_rows_np``: ``[N, W]`` u32 -> ``[N, n_cols]``
    i32. Every word index / shift / mask is STATIC, so the decode lowers to
    a handful of vectorized integer ops XLA fuses into the consumer (the
    embedding gather) — no gathers, no dynamic indexing."""
    mask = _check_bits(bits)
    cols = []
    for c in range(n_cols):
        bitpos = c * bits
        w0, off = bitpos // 32, bitpos % 32
        v = packed[:, w0] >> np.uint32(off)
        if off + bits > 32:
            v = v | (packed[:, w0 + 1] << np.uint32(32 - off))
        cols.append((v & mask).astype(jnp.int32))
    if not cols:
        return jnp.zeros((packed.shape[0], 0), jnp.int32)
    return jnp.stack(cols, axis=1)
