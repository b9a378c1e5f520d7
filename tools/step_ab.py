"""A/B the hashed-step embedding-update formulations on real hardware.

The Criteo step is scatter-OP-bound (BASELINE.md roofline). Three
numerically-identical lowerings exist behind ``HashedLinearParams.emb_update``
('fused' | 'per_column' | 'sorted'); this tool times each on the current
backend and prints one JSON line so the winner can be promoted to the bench
default. Run on the TPU host:

    python tools/step_ab.py [--rows 262144] [--dims 4194304] [--steps 20]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(
        globals().get("__file__", "tools/step_ab.py"))))
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1 << 18)
    ap.add_argument("--dims", type=int, default=1 << 22)
    ap.add_argument("--steps", type=int, default=20)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from orange3_spark_tpu.models.hashed_linear import (
        _ADAM_UNIT,
        _hashed_step,
    )
    from orange3_spark_tpu.ops.hashing import column_salts

    n_dense, n_cat = 13, 26
    rng = np.random.default_rng(0)
    Xall = np.concatenate(
        [rng.integers(0, 2, (args.rows, 1)).astype(np.float32),
         rng.lognormal(0, 1, (args.rows, n_dense)).astype(np.float32),
         rng.integers(0, 200_000, (args.rows, n_cat)).astype(np.float32)],
        axis=1,
    )
    Xd = jax.device_put(Xall)
    salts = jnp.asarray(column_salts(n_cat, 0))
    zero = jnp.zeros((1,), jnp.float32)
    out = {"metric": "hashed_step_ms_by_emb_update", "unit": "ms/step",
           "rows": args.rows, "dims": args.dims,
           "backend": jax.default_backend()}
    variants = [(v, "float32") for v in ("fused", "per_column", "sorted")]
    # dtype axis: bfloat16 halves the gather/matmul bytes of the two
    # leading formulations — the next hardware window should decide
    # whether the table can live in bf16 (adam state stays f32 via optax)
    variants += [("fused", "bfloat16"), ("sorted", "bfloat16")]
    for variant, dt in variants:
        key = variant if dt == "float32" else f"{variant}_{dt}"
        theta = {"emb": jnp.zeros((args.dims, 1), jnp.float32),
                 "coef": jnp.zeros((n_dense, 1), jnp.float32),
                 "intercept": jnp.zeros((1,), jnp.float32)}
        opt = _ADAM_UNIT.init(theta)
        kw = dict(loss_kind="binary_logistic", n_dims=args.dims,
                  n_dense=n_dense, label_in_chunk=True, emb_update=variant,
                  compute_dtype=jnp.dtype(dt))
        theta, opt, loss = _hashed_step(
            theta, opt, Xd, jnp.int32(args.rows), zero, zero, salts,
            jnp.float32(0.0), jnp.float32(0.04), **kw)
        jax.block_until_ready(loss)     # compile
        t0 = time.perf_counter()
        for _ in range(args.steps):
            theta, opt, loss = _hashed_step(
                theta, opt, Xd, jnp.int32(args.rows), zero, zero, salts,
                jnp.float32(0.0), jnp.float32(0.04), **kw)
        jax.block_until_ready(loss)
        ms = (time.perf_counter() - t0) / args.steps * 1e3
        out[key] = round(ms, 2)
        out[f"{key}_rows_per_sec"] = round(args.rows / ms * 1e3, 1)
    best = min(("fused", "per_column", "sorted"), key=lambda v: out[v])
    out["best"] = best
    # the winning variant's step time is the headline value
    out["value"] = out[best]
    # print + flush the A/B line BEFORE the scan cell below: that cell
    # dispatches a multi-chunk multi-epoch scan, the one program shape
    # with a known device-fault history — it must not be able to cost
    # the five measurements already in hand
    print(json.dumps(out), flush=True)

    # in-scan step time: the same step executed INSIDE the replay scan
    # program (_hashed_replay_epochs), one dispatch for stack_chunks x
    # scan_epochs steps. The 2026-07-31 window measured ~0.5 s/step
    # in-scan on a 1-chunk stack vs 0.27 ms standalone at 02:04 — this
    # cell decides whether that 2000x gap is the scan lowering (would
    # reproduce here) or window-to-window device variance (would not).
    # Emitted as its OWN JSON line, in a fault guard, for the same reason.
    try:
        from orange3_spark_tpu.models.hashed_linear import (
            _hashed_replay_epochs,
        )

        stack_chunks, scan_epochs = 4, 5
        theta = {"emb": jnp.zeros((args.dims, 1), jnp.float32),
                 "coef": jnp.zeros((n_dense, 1), jnp.float32),
                 "intercept": jnp.zeros((1,), jnp.float32)}
        opt = _ADAM_UNIT.init(theta)
        kw = dict(loss_kind="binary_logistic", n_dims=args.dims,
                  n_dense=n_dense, label_in_chunk=True, emb_update="fused",
                  compute_dtype=jnp.dtype("float32"))
        stacks = (jnp.stack([Xd] * stack_chunks),
                  jnp.full((stack_chunks,), args.rows, jnp.int32),
                  jnp.zeros((stack_chunks, 1), jnp.float32),
                  jnp.zeros((stack_chunks, 1), jnp.float32))
        theta, opt, losses = _hashed_replay_epochs(
            theta, opt, stacks, salts, jnp.float32(0.0), jnp.float32(0.04),
            n_epochs=scan_epochs, **kw)
        jax.block_until_ready(losses)       # compile + first run
        t0 = time.perf_counter()            # stacks are not donated; reuse
        theta, opt, losses = _hashed_replay_epochs(
            theta, opt, stacks, salts, jnp.float32(0.0), jnp.float32(0.04),
            n_epochs=scan_epochs, **kw)
        jax.block_until_ready(losses)
        n_in_scan = stack_chunks * scan_epochs
        ms = (time.perf_counter() - t0) / n_in_scan * 1e3
        print(json.dumps({
            "metric": "hashed_step_in_scan_ms", "value": round(ms, 2),
            "unit": "ms/step", "rows": args.rows, "dims": args.dims,
            "backend": jax.default_backend(),
            "steps_per_dispatch": n_in_scan,
            "standalone_fused_ms": out["fused"],
        }), flush=True)
    except Exception as e:  # noqa: BLE001 — the A/B line is already out
        print(f"in-scan cell died (A/B line unaffected): "
              f"{type(e).__name__}: {e}"[:300], file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
