"""Device-side feature hashing — the Criteo-scale categorical path.

MLlib's FeatureHasher/HashingTF run MurmurHash3 per cell on JVM executors
(SURVEY.md §2b "Feature transformers"; reconstructed, mount empty). The
TPU-native redesign moves the hash INTO the jitted step: raw categorical
codes ship to the device as one [N, C] integer array (the cheapest possible
host->HBM transfer: 4 bytes/cell, no python per-cell work), and a murmur3-
finalizer mix runs as a handful of vectorized uint32 ops — microseconds on
the VPU, fused by XLA into the embedding-gather that consumes the indices.

``n_dims`` must be a power of two so the bucket map is a bit-mask, not an
integer modulo.
"""

from __future__ import annotations

import zlib

import jax.numpy as jnp
import numpy as np

__all__ = ["hash_columns", "hash_columns_np", "column_salts",
           "strings_to_u32", "STRING_CODE_MASK"]


def _fmix32(h):
    """murmur3 32-bit finalizer — full avalanche in 5 vector ops."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def column_salts(n_columns: int, seed: int = 0) -> np.ndarray:
    """Per-column uint32 salts: the same raw code in different columns must
    land in different buckets (MLlib prefixes the column name; we xor a salt)."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, 2**32, size=n_columns, dtype=np.uint32)


def hash_columns(cats, salts, n_dims: int):
    """[N, C] integer categorical codes -> [N, C] bucket indices in [0, n_dims).

    Trace-time safe; cats may be any integer dtype or float32 holding exact
    integers (fastcsv parses everything to f32 — ints < 2^24 are exact).
    """
    if n_dims & (n_dims - 1):
        raise ValueError(f"n_dims must be a power of two, got {n_dims}")
    u = cats.astype(jnp.int32).astype(jnp.uint32)  # wrap negatives to uint32
    h = _fmix32(u ^ jnp.asarray(salts, jnp.uint32)[None, :])
    return (h & jnp.uint32(n_dims - 1)).astype(jnp.int32)


def hash_columns_np(cats: np.ndarray, salts: np.ndarray,
                    n_dims: int) -> np.ndarray:
    """Host twin of ``hash_columns`` — BIT-IDENTICAL buckets. The packed
    cache codec hashes a chunk's categoricals on the prefetch thread and
    the step only unpacks them, so any drift between host and device
    buckets would silently update the wrong table rows. The encode's one
    native pass (io/native.py ``hash_pack_rows``) does this arithmetic per
    value; this function is its fallback on a host without the library and
    the oracle it is held to (tests/test_cache_codec.py), as
    tests/test_sparse_optim pins this one to ``hash_columns`` over random
    codes including negatives and the f32 carrier dtype."""
    if n_dims & (n_dims - 1):
        raise ValueError(f"n_dims must be a power of two, got {n_dims}")
    u = np.asarray(cats).astype(np.int32).astype(np.uint32)
    h = u ^ np.asarray(salts, np.uint32)[None, :]
    h ^= h >> np.uint32(16)
    h = (h * np.uint32(0x85EBCA6B)) & np.uint32(0xFFFFFFFF)
    h ^= h >> np.uint32(13)
    h = (h * np.uint32(0xC2B2AE35)) & np.uint32(0xFFFFFFFF)
    h ^= h >> np.uint32(16)
    return (h & np.uint32(n_dims - 1)).astype(np.int32)


#: String codes are masked to 24 bits so they survive a float32 round-trip
#: EXACTLY (f32 mantissa = 24 bits) — the chunk pipeline carries categoricals
#: as one f32 array (see models/hashed_linear.py) and full-range u32 codes
#: would collapse above 2^24. The native parser's categorical mode
#: (native/fastcsv.cpp fcsv_set_categorical) applies the SAME crc32 & mask so
#: models checkpoint-port between the host and native on-ramps.
STRING_CODE_MASK = 0x00FFFFFF


def strings_to_u32(arr) -> np.ndarray:
    """Host-side: stable uint32 codes for string categoricals (crc32 — python
    ``hash()`` is per-process salted and therefore useless for checkpoints).
    Real Criteo ships hex-string categories; this is their on-ramp into the
    integer pipeline. Vectorized per unique value, so cost is O(cardinality).

    Codes are ``crc32 & STRING_CODE_MASK`` (24 bits): exact in float32, so
    the f32 chunk path cannot merge distinct codes."""
    arr = np.asarray(arr)
    uniq, inv = np.unique(arr, return_inverse=True)
    codes = np.fromiter(
        (zlib.crc32(str(u).encode()) & STRING_CODE_MASK for u in uniq),
        dtype=np.uint32,
        count=len(uniq),
    )
    return codes[inv].reshape(arr.shape)
