"""NYC-taxi-shaped trips from a seed: ``bench.gen_taxi``'s draws copied
(lognormal distance, duration and fare correlated with it, uniform pick-up
position, small-integer hour, day of week and passengers), so that the
yardstick does not move when the program's copy does. Drawn in blocks, each
from a generator of its own keyed by (seed, block): threads fill the table
in parallel (numpy releases the GIL in these draws) and the same seed gives
the same rows whatever the thread count."""

from __future__ import annotations

import glob
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

COLUMNS = ("dist", "dur", "fare", "lon", "lat", "hour", "dow", "pax")
BLOCK_ROWS = 1 << 22
GEN_THREADS = 8


def fill_block(out: np.ndarray, seed: int, b: int) -> None:
    """Rows of block ``b`` into ``out`` (f32[n, 8], a slice of the table)."""
    n = out.shape[0]
    rng = np.random.default_rng([int(seed), b])
    dist = rng.lognormal(0.5, 1.0, n).astype(np.float32)
    dur = (dist * 3.2 + rng.lognormal(0, 0.4, n)).astype(np.float32)
    out[:, 0] = dist
    out[:, 1] = dur
    out[:, 2] = 2.5 + 1.8 * dist + 0.4 * dur + rng.standard_normal(n)
    out[:, 3] = rng.uniform(-74.05, -73.75, n)
    out[:, 4] = rng.uniform(40.6, 40.9, n)
    out[:, 5] = rng.integers(0, 24, n)
    out[:, 6] = rng.integers(0, 7, n)
    out[:, 7] = rng.integers(1, 7, n)


def rows(n_rows: int, seed: int) -> np.ndarray:
    """-> f32[n_rows, 8]."""
    out = np.empty((n_rows, len(COLUMNS)), np.float32)
    starts = range(0, n_rows, BLOCK_ROWS)
    with ThreadPoolExecutor(max_workers=GEN_THREADS) as pool:
        list(pool.map(
            lambda a: fill_block(out[a[1]:a[1] + BLOCK_ROWS], seed, a[0]),
            enumerate(starts)))
    return out


def table_path(name: str, n_rows: int, seed: int, data_dir: str) -> str:
    return os.path.join(data_dir, f"{name}_r{n_rows}_s{seed}.npy")


def ensure_table(name: str, n_rows: int, seed: int,
                 data_dir: str) -> tuple[np.ndarray, bool]:
    """-> (the table, generated now). Written once as ``.npy`` and read
    back by later runs of the same seed; a table of another seed or size
    under the same name is removed first (4.3 GB each at the cell's size:
    one is kept, not one a seed). Written to a temporary name and renamed,
    so a killed run leaves no half file under the final name."""
    os.makedirs(data_dir, exist_ok=True)
    path = table_path(name, n_rows, seed, data_dir)
    if os.path.exists(path):
        return np.load(path), False
    for stale in glob.glob(os.path.join(data_dir, f"{name}_r*_s*.npy*")):
        os.remove(stale)
    table = rows(n_rows, seed)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        np.save(f, table)
    os.replace(tmp, path)
    return table, True
