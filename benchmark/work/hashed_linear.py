"""Work functions of the hashed linear fit: the bytes and operations the
ALGORITHM needs, from shapes — a lower bound that no implementation of the
same fit can go under, each byte counted once and nothing recomputed.

One training step on one cached chunk of R rows, C categorical and D count
columns, a table of 2^b rows, U distinct table rows touched:
- read the chunk as it is cached, once: R * (1 label byte + 2*D bytes of
  bfloat16 counts + ceil(C*b/32)*4 bytes of packed buckets);
- per distinct touched row, read and write back its weight, its Adagrad
  accumulator and its last-seen step: 12 B in, 12 B out.
Operations: R * (C adds + 2*D for the count term + ~10 for the loss) in the
forward, the same again for the gradient, 6 per touched row for the rule —
some 10^7 against 10^8 bytes, so every step is bound by bytes on any chip
whose FLOP/s : B/s ratio is over 1 (v5e: 240).
An evaluation step reads the chunk and 4 B per distinct row, writes nothing.
"""

from __future__ import annotations

from benchmark.work import least_seconds

STATE_BYTES_PER_ROW = 12          # weight f32 + accumulator f32 + step i32


def chunk_bytes(rows: int, n_dense: int, n_cat: int, n_dims: int) -> int:
    idx_bits = max(1, (n_dims - 1).bit_length())
    words = -(-(n_cat * idx_bits) // 32)
    return rows * (1 + 2 * n_dense + 4 * words)


def step_bytes(rows: int, n_dense: int, n_cat: int, n_dims: int,
               distinct_rows: int) -> int:
    return (chunk_bytes(rows, n_dense, n_cat, n_dims)
            + 2 * STATE_BYTES_PER_ROW * distinct_rows)


def step_ops(rows: int, n_dense: int, n_cat: int, distinct_rows: int) -> int:
    return 2 * rows * (n_cat + 2 * n_dense + 10) + 6 * distinct_rows


def eval_bytes(rows: int, n_dense: int, n_cat: int, n_dims: int,
               distinct_rows: int) -> int:
    return chunk_bytes(rows, n_dense, n_cat, n_dims) + 4 * distinct_rows


def job_work(*, chunk_rows: int, n_dense: int, n_cat: int, n_dims: int,
             distinct_rows: list, epochs: int, holdout_chunks: int,
             peaks: dict) -> dict:
    """Sums over one job: every training step of every epoch, then the
    holdout's evaluation. -> bytes, ops, least seconds, the bound."""
    n_train = len(distinct_rows) - holdout_chunks
    b = o = 0
    for u in distinct_rows[:n_train]:
        b += epochs * step_bytes(chunk_rows, n_dense, n_cat, n_dims, u)
        o += epochs * step_ops(chunk_rows, n_dense, n_cat, u)
    steps_s, bound = least_seconds(b, o, peaks)
    eb = sum(eval_bytes(chunk_rows, n_dense, n_cat, n_dims, u)
             for u in distinct_rows[n_train:])
    eo = holdout_chunks * chunk_rows * (n_cat + 2 * n_dense + 10)
    eval_s, _ = least_seconds(eb, eo, peaks)
    return {"step_bytes": b, "step_ops": o, "steps": epochs * n_train,
            "steps_least_s": steps_s, "bound": bound,
            "job_least_s": steps_s + eval_s}
