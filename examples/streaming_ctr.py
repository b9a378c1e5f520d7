"""Criteo-scale streaming CTR fit on one chip — the BASELINE config-2
pipeline at example scale: CSV on disk → native C++ parse → device DMA →
hashed-sparse minibatch steps → HBM-cached fused replay → on-device eval.

Run:  PYTHONPATH=.:$PYTHONPATH python examples/streaming_ctr.py
"""

import os
import tempfile

import numpy as np

import orange3_spark_tpu as otpu
from orange3_spark_tpu.io.streaming import csv_raw_chunk_source
from orange3_spark_tpu.models.hashed_linear import StreamingHashedLinearEstimator

N_ROWS, N_DENSE, N_CAT = 200_000, 5, 8


def write_csv(path: str) -> None:
    rng = np.random.default_rng(0)
    eff = rng.normal(0, 0.8, (N_CAT, 64)).astype(np.float32)
    dense = rng.lognormal(0, 1, (N_ROWS, N_DENSE)).astype(np.float32)
    cats = rng.integers(0, 5000, (N_ROWS, N_CAT))
    logit = 0.1 * dense.sum(1) + eff[np.arange(N_CAT), cats % 64].sum(1) - 2.0
    y = (rng.random(N_ROWS) < 1 / (1 + np.exp(-logit))).astype(np.int32)
    cols = [y] + [dense[:, j] for j in range(N_DENSE)] \
        + [cats[:, j] for j in range(N_CAT)]
    header = ",".join(["label"] + [f"i{j}" for j in range(N_DENSE)]
                      + [f"c{j}" for j in range(N_CAT)])
    np.savetxt(path, np.column_stack(cols), delimiter=",", header=header,
               comments="", fmt="%.6g")


def main() -> None:
    otpu.TpuSession.builder_get_or_create()
    # regenerate every run, atomically (a killed prior run must not leave
    # a truncated file that poisons later runs)
    path = os.path.join(tempfile.gettempdir(), "example_ctr.csv")
    tmp = path + f".tmp{os.getpid()}"
    write_csv(tmp)
    os.replace(tmp, path)

    est = StreamingHashedLinearEstimator(
        n_dims=1 << 18, n_dense=N_DENSE, n_cat=N_CAT, epochs=8,
        chunk_rows=1 << 15, label_in_chunk=True, step_size=0.05,
        # defer_epoch1: the streaming pass is pure ingest and ALL epochs
        # train inside the fused replay program — bit-identical to the
        # interleaved schedule, but zero per-chunk step dispatches.
        # replay_granularity='epoch'
        # (one dispatch per epoch) additionally composes with a
        # StreamCheckpointer for kill-and-resume at epoch boundaries.
        defer_epoch1=True,
    )
    model = est.fit_stream(
        csv_raw_chunk_source(path, chunk_rows=1 << 15),
        cache_device=True,      # Spark's persist(): epochs 2+ replay HBM
        holdout_chunks=1,
    )
    ev = model.evaluate_device(model.holdout_chunks_)
    print(f"steps={model.n_steps_}  holdout: logloss={ev['logloss']:.3f} "
          f"acc={ev['accuracy']:.3f} auc={ev['auc']:.3f}")
    assert ev["auc"] > 0.65


if __name__ == "__main__":
    main()
