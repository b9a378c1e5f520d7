"""Criteo-Terabyte-shaped click logs from a seed: arrays first, the TSV
record format second.

Record format (Criteo Terabyte click logs): label, 13 integer counts, 26
categorical values as 8-hex-digit strings, tab-separated, no header, empty
cells for missing values.

Everything is drawn block by block (``block_rows`` rows) from
``default_rng([seed, block])``, so the writer and the plain reference make
the same rows without sharing anything but this file and the seed. The
label model is bench.gen_criteo_csv's (latent effects on ``code % 1024``
plus a dense term), with three changes the configuration file lists under
``assumed``: the record format above, per-column cardinalities of the
Criteo-1TB tables, and Zipf(1) popularity inside a column
(rank = floor(card ** u), u uniform; rank -> code by a fixed affine
permutation of [0, card)).
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

N_DENSE, N_CAT = 13, 26
MAX_DIGITS = 5                      # counts are clipped to 99,999
GEN_THREADS = 4
_HEX = np.frombuffer(b"0123456789abcdef", np.uint8)
_ROW_WIDTH = 2 + N_DENSE * (MAX_DIGITS + 1) + N_CAT * 9


class Model:
    """Seed-wide constants: label effects and the per-column permutations."""

    def __init__(self, data: dict, seed: int):
        rng = np.random.default_rng([int(seed), 0x5EED])
        self.cards = np.asarray(data["cardinalities"], np.int64)
        assert self.cards.shape == (N_CAT,)
        self.effects = rng.normal(
            0.0, 0.9, size=(N_CAT, data["effect_card"])).astype(np.float32)
        self.w_dense = rng.normal(0.0, 0.4, size=N_DENSE).astype(np.float32)
        # rank -> code: (a * rank + b) mod card, a coprime to card
        a = rng.integers(1, 1 << 31, size=N_CAT, dtype=np.int64) | 1
        for j in range(N_CAT):
            while np.gcd(a[j], self.cards[j]) != 1:
                a[j] += 2
        self.perm_a = a % np.maximum(self.cards, 2)
        self.perm_a[self.perm_a == 0] = 1
        self.perm_b = rng.integers(0, 1 << 31, size=N_CAT,
                                   dtype=np.int64) % self.cards
        # code -> the 32-bit value printed in hex: an odd multiplier and a
        # per-column xor are both bijections of 32-bit words
        self.hex_mul = np.uint32(0x9E3779B1)
        self.hex_xor = rng.integers(0, 1 << 32, size=N_CAT, dtype=np.uint32)
        self.count_scale = float(data["count_scale"])
        self.missing_int = float(data["missing_share_int"])
        self.missing_cat = float(data["missing_share_cat"])
        self.effect_card = int(data["effect_card"])
        self.label_noise = float(data["label_noise"])


def block(model: Model, seed: int, index: int, n: int) -> dict:
    """Rows of block ``index``: ``label`` u8 [n], ``counts`` i32 [n, 13]
    (-1 = empty cell), ``hex32`` u32 [n, 26] with ``cat_missing`` bool."""
    rng = np.random.default_rng([int(seed), 1 + int(index)])
    raw = np.exp(rng.standard_normal(size=(n, N_DENSE), dtype=np.float32))
    counts = np.minimum(raw * np.float32(model.count_scale),
                        10 ** MAX_DIGITS - 1).astype(np.int32)
    u = rng.random(size=(n, N_CAT), dtype=np.float32)
    log_card = np.log(model.cards).astype(np.float32)
    rank = np.clip(np.exp(u * log_card[None, :]).astype(np.int64) - 1,
                   0, model.cards[None, :] - 1)
    code = (model.perm_a[None, :] * rank + model.perm_b[None, :]) \
        % model.cards[None, :]
    int_missing = rng.random(size=(n, N_DENSE),
                             dtype=np.float32) < model.missing_int
    cat_missing = rng.random(size=(n, N_CAT),
                             dtype=np.float32) < model.missing_cat
    logit = np.where(int_missing, 0.0,
                     np.log1p(counts) - 1.4).astype(np.float32) @ model.w_dense
    logit -= 0.5
    for j in range(N_CAT):
        eff = model.effects[j, code[:, j] % model.effect_card]
        logit += np.where(cat_missing[:, j], 0.0, eff)
    noise = rng.standard_normal(n, dtype=np.float32)
    label = (logit + model.label_noise * noise > 0).astype(np.uint8)
    hex32 = (code.astype(np.uint32) * model.hex_mul) ^ model.hex_xor[None, :]
    counts = np.where(int_missing, -1, counts).astype(np.int32)
    return {"label": label, "counts": counts, "hex32": hex32,
            "cat_missing": cat_missing}


def tsv_bytes(rows: dict) -> bytes:
    """The rows in the record format, vectorised: a fixed-width byte matrix
    with 0 in unused places, compacted once."""
    n = rows["label"].shape[0]
    buf = np.zeros((n, _ROW_WIDTH), np.uint8)
    buf[:, 0] = rows["label"] + ord("0")
    buf[:, 1] = ord("\t")
    counts = rows["counts"]
    present = counts >= 0
    v = np.where(present, counts, 0)
    cell = buf[:, 2:2 + N_DENSE * (MAX_DIGITS + 1)].reshape(
        n, N_DENSE, MAX_DIGITS + 1)
    for k in range(MAX_DIGITS):           # k-th digit from the right
        p = 10 ** k
        show = present & ((v >= p) | (k == 0))
        cell[:, :, MAX_DIGITS - 1 - k] = np.where(
            show, (v // p) % 10 + ord("0"), 0)
    cell[:, :, MAX_DIGITS] = ord("\t")
    off = 2 + N_DENSE * (MAX_DIGITS + 1)
    hexc = buf[:, off:].reshape(n, N_CAT, 9)
    h = rows["hex32"]
    keep = ~rows["cat_missing"]
    for k in range(8):
        nib = (h >> np.uint32(28 - 4 * k)) & np.uint32(0xF)
        hexc[:, :, k] = np.where(keep, _HEX[nib], 0)
    hexc[:, :, 8] = ord("\t")
    hexc[:, N_CAT - 1, 8] = ord("\n")
    return buf[buf != 0].tobytes()


def data_path(data_dir: str, name: str, rows: int, seed: int) -> str:
    return os.path.join(data_dir, f"{name}_{rows}r_s{seed}.tsv")


def ensure_tsv(data: dict, name: str, rows: int, seed: int,
               data_dir: str) -> tuple[str, bool]:
    """-> (path, generated now). Written to a temporary name and renamed,
    so a killed run leaves no half file under the final name."""
    os.makedirs(data_dir, exist_ok=True)
    path = data_path(data_dir, name, rows, seed)
    if os.path.exists(path):
        return path, False
    model = Model(data, seed)
    tmp = f"{path}.tmp.{os.getpid()}"
    block_rows = int(data["block_rows"])
    sizes = [min(block_rows, rows - start)
             for start in range(0, rows, block_rows)]
    # numpy releases the GIL in these passes: a few blocks at a time
    with ThreadPoolExecutor(max_workers=GEN_THREADS) as pool, \
            open(tmp, "wb") as f:
        for chunk in pool.map(
                lambda a: tsv_bytes(block(model, seed, *a)),
                enumerate(sizes)):
            f.write(chunk)
    os.replace(tmp, path)
    return path, True
