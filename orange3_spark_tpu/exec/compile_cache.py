"""Persistent XLA compilation cache wiring.

The hot programs (the fused replay scan, the L-BFGS while_loop, the eval
fold, every serving bucket) each cost seconds-to-minutes of XLA compile per
PROCESS. jax's persistent cache keeps compiled executables keyed by
(program, backend, flags, cache path): the first run pays the compile and
writes an entry; every later process with the same shapes loads the binary.

The directory is placed from OUTSIDE: where ``JAX_COMPILATION_CACHE_DIR``
is set jax reads it itself and this module sets no directory in code;
otherwise the cache lives at one fixed path inside the checkout
(``<repo>/.jax_cache``). The path is part of the cache key, so it must
never move between runs. ``enable_compilation_cache`` zeroes the
min-compile-time and min-entry-size gates because this workload has few,
large, endlessly re-used programs. ``cache_report`` turns a pre-run
snapshot into the bench line's ``cache_hit``/``cache_entries`` fields.
"""

from __future__ import annotations

import os

import jax
from jax.experimental.compilation_cache import compilation_cache as _cc

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def default_cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_REPO_ROOT, ".jax_cache"))


def cache_entries(cache_dir: str) -> int:
    """Number of persisted executables under ``cache_dir`` (0 if absent)."""
    n = 0
    for _root, _dirs, files in os.walk(cache_dir):
        n += len(files)
    return n


def enable_compilation_cache() -> dict:
    """Turn on jax's persistent compilation cache at ``default_cache_dir()``.

    Returns ``{"enabled", "dir", "pre_entries"}`` — keep the dict and hand
    it to ``cache_report`` after the measured work to learn whether the run
    compiled anything new. An unwritable directory degrades to
    ``enabled: False``: the cache is an accelerator, never a correctness
    dependency.
    """
    from_env = bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))
    d = default_cache_dir()
    try:
        os.makedirs(d, mode=0o700, exist_ok=True)
    except OSError as e:
        return {"enabled": False, "dir": None, "pre_entries": 0,
                "reason": f"{type(e).__name__}: {e}"}
    pre = cache_entries(d)
    if not from_env:
        jax.config.update("jax_compilation_cache_dir", d)
    # few, large, endlessly re-used programs: cache everything
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # the cache module LATCHES its initialized/disabled state at the
    # process's first compile — if anything compiled before this call the
    # configured dir would silently never be used; reset so the next
    # compile re-initializes against it
    _cc.reset_cache()
    return {"enabled": True, "dir": d, "pre_entries": pre}


def cache_report(info: dict) -> dict:
    """``{"cache_hit", "cache_entries"}`` for the bench JSON line.

    ``cache_hit`` is True when the run found a warm cache AND wrote no new
    entries (every program it compiled was served from disk); False when it
    had to compile something (first run, or changed shapes/flags); None
    when the cache is disabled/unavailable.
    """
    if not info.get("enabled"):
        return {"cache_hit": None, "cache_entries": None}
    post = cache_entries(info["dir"])
    pre = info.get("pre_entries", 0)
    return {"cache_hit": bool(pre > 0 and post <= pre),
            "cache_entries": post}
