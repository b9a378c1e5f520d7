"""Plain reference of the hashed linear fit (BASELINE config 2): feature
hashing of Criteo records into one table, logistic loss, Adagrad with
decoupled weight decay, multi-epoch over the same chunks, holdout logloss
and AUC. Straightforward jax.numpy in float32 at 'highest' matmul
precision; imports nothing of the program and takes nothing it has made.

What the configuration states, restated here independently:
- a categorical cell is the crc32 of its 8-hex-digit text, masked to 24
  bits; an empty cell is the reserved code 0; an empty count is 0;
- bucket = murmur3 finaliser(code xor column salt) & (n_dims - 1), salts =
  ``default_rng(hash_seed).integers(0, 2**32, 26, dtype=uint32)``;
- counts are stored in bfloat16 (the 'packed' cache), everything else f32;
- logit = sum of 26 table rows + counts . coef + intercept; loss = mean
  over the chunk's rows of softplus(z) - z*y ('logistic') or of
  max(0, 1 - (2y-1) z)^2 ('squared_hinge', MLlib's LinearSVC role);
- per step, table and coef: p <- p * (1 - lr*reg), then Adagrad
  (acc += g*g; p -= lr * g / sqrt(acc + 1e-10)); the intercept has no decay.
  The program applies the decay lazily to the rows it touches and settles
  the rest at the end; the dense schedule here is the same product;
- epochs replay the training chunks in order; the last ``holdout_chunks``
  chunks are never trained on and give logloss and AUC (exact, by rank).

``precision='bfloat16'`` is the control: table, accumulators, inputs and
arithmetic in bfloat16 — the step below float32 that halving the table's
bytes would tempt a later PR to take.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

ADAGRAD_EPS = 1e-10
CODE_MASK = 0x00FFFFFF


def _crc_table() -> np.ndarray:
    t = np.arange(256, dtype=np.uint32)
    for _ in range(8):
        t = np.where(t & 1, (t >> 1) ^ np.uint32(0xEDB88320), t >> 1)
    return t.astype(np.uint32)


_CRC_T = _crc_table()
_HEX = np.frombuffer(b"0123456789abcdef", np.uint8)


def crc32_hex8(h32: np.ndarray) -> np.ndarray:
    """crc32 of the 8 lowercase hex digits of each 32-bit word."""
    crc = np.full(h32.shape, 0xFFFFFFFF, np.uint32)
    for k in range(8):
        byte = _HEX[(h32 >> np.uint32(28 - 4 * k)) & np.uint32(0xF)]
        crc = _CRC_T[(crc ^ byte) & np.uint32(0xFF)] ^ (crc >> np.uint32(8))
    return crc ^ np.uint32(0xFFFFFFFF)


def fmix32(h: np.ndarray) -> np.ndarray:
    h = h.astype(np.uint32)
    h ^= h >> np.uint32(16)
    h *= np.uint32(0x85EBCA6B)
    h ^= h >> np.uint32(13)
    h *= np.uint32(0xC2B2AE35)
    h ^= h >> np.uint32(16)
    return h


def features(rows: dict, n_dims: int, hash_seed: int):
    """Generator rows -> (y f32 [n], counts f32 [n, 13] rounded through
    bfloat16, bucket i32 [n, 26])."""
    salts = np.random.default_rng(hash_seed).integers(
        0, 2 ** 32, size=rows["hex32"].shape[1], dtype=np.uint32)
    code = np.where(rows["cat_missing"], np.uint32(0),
                    crc32_hex8(rows["hex32"]) & np.uint32(CODE_MASK))
    bucket = (fmix32(code ^ salts[None, :])
              & np.uint32(n_dims - 1)).astype(np.int32)
    counts = np.maximum(rows["counts"], 0).astype(np.float32)
    counts = np.asarray(jnp.asarray(counts).astype(jnp.bfloat16)
                        .astype(jnp.float32))
    return rows["label"].astype(np.float32), counts, bucket


def _logits(emb, coef, b, counts, bucket, dt):
    z = jnp.sum(emb[bucket].astype(dt), axis=1, dtype=dt)
    z = z + jnp.dot(counts.astype(dt), coef.astype(dt),
                    precision="highest", preferred_element_type=dt)
    return z + b.astype(dt)


def row_loss(z, y, loss: str):
    """Per-row loss, labels in {0, 1}."""
    if loss == "logistic":
        return jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
    if loss == "squared_hinge":
        return jnp.maximum(0, 1 - (2 * y - 1) * z) ** 2
    raise ValueError(loss)


def row_loss_grad(z, y, loss: str):
    """Its derivative in z, written out (not autodiff: the logistic loss is
    smooth and its derivative at z = 0 is sigmoid(0) - y = 0.5 - y, which
    autodiff of the stable formula above does not give)."""
    if loss == "logistic":
        return jax.nn.sigmoid(z) - y
    sign = 2 * y - 1
    return -2 * sign * jnp.maximum(0, 1 - sign * z)


@functools.partial(jax.jit, static_argnames=("dt", "loss"),
                   donate_argnums=(0,))
def _step(state, y, counts, bucket, lr, reg, *, dt, loss):
    emb, acc, coef, cacc, b, bacc = state
    y = y.astype(dt)
    z = _logits(emb, coef, b, counts, bucket, dt)
    mean_loss = jnp.mean(row_loss(z, y, loss).astype(jnp.float32))
    dl = (row_loss_grad(z, y, loss) / y.shape[0]).astype(dt)
    g = jnp.zeros(emb.shape, dt).at[bucket.reshape(-1)].add(
        jnp.repeat(dl, bucket.shape[1]))
    g_coef = jnp.dot(counts.astype(dt).T, dl, precision="highest",
                     preferred_element_type=dt)
    g_b = jnp.sum(dl)
    decay = (1.0 - lr * reg).astype(dt)
    lr = lr.astype(dt)

    def rule(p, a, grad, decayed):
        if decayed:
            p = p * decay
        a = a + grad * grad
        return p - lr * grad * jax.lax.rsqrt(a + jnp.asarray(ADAGRAD_EPS, dt)), a

    emb, acc = rule(emb, acc, g, True)
    coef, cacc = rule(coef, cacc, g_coef, True)
    b, bacc = rule(b, bacc, g_b, False)
    return (emb, acc, coef, cacc, b, bacc), mean_loss


@functools.partial(jax.jit, static_argnames=("dt",))
def _scores(state, counts, bucket, *, dt):
    emb, _, coef, _, b, _ = state
    return _logits(emb, coef, b, counts, bucket, dt).astype(jnp.float32)


def exact_auc(score: np.ndarray, y: np.ndarray) -> float:
    """Rank AUC with ties averaged."""
    order = np.argsort(score, kind="stable")
    s = score[order]
    ranks = np.empty(len(s), np.float64)
    first = np.flatnonzero(np.r_[True, s[1:] != s[:-1]])
    last = np.r_[first[1:], len(s)]
    ranks[order] = np.repeat((first + last + 1) / 2.0, last - first)
    npos = float(y.sum())
    nneg = len(y) - npos
    return float((ranks[y > 0.5].sum() - npos * (npos + 1) / 2)
                 / (npos * nneg))


def fit(chunks, *, n_dims: int, n_dense: int, epochs: int,
        holdout_chunks: int, step_size: float, reg_param: float,
        loss: str = "logistic", precision: str = "float32",
        fault: str | None = None) -> dict:
    """``chunks``: a callable giving (y, counts, bucket) of chunk i, and
    their number. -> theta, the last step's loss and the holdout's mean
    loss, accuracy and (logistic only) AUC — None without a holdout.

    ``fault`` plants one of the faults a fit can have, for the readings the
    limits are set from (``control.py``), never in a benchmark run:
    ``'skip_step'`` — the first epoch's second step returns its state
    unchanged; ``'half_batch'`` — the second half of every chunk's rows is
    left out and the mean taken over the rest."""
    get, n_chunks = chunks
    if fault not in (None, "skip_step", "half_batch"):
        raise ValueError(fault)
    dt = jnp.dtype(precision)
    n_train = n_chunks - holdout_chunks
    state = (jnp.zeros((n_dims,), dt), jnp.zeros((n_dims,), dt),
             jnp.zeros((n_dense,), dt), jnp.zeros((n_dense,), dt),
             jnp.zeros((), dt), jnp.zeros((), dt))
    lr, reg = jnp.float32(step_size), jnp.float32(reg_param)
    dev = [tuple(jnp.asarray(a) for a in get(i)) for i in range(n_train)]
    if fault == "half_batch":
        dev = [tuple(a[:a.shape[0] // 2] for a in c) for c in dev]
    last = None
    for epoch in range(epochs):
        for k, (y, counts, bucket) in enumerate(dev):
            if fault == "skip_step" and epoch == 0 and k == 1:
                continue
            state, last = _step(state, y, counts, bucket, lr, reg, dt=dt,
                                loss=loss)
    out = {"final_loss": float(last), "holdout_loss": None,
           "holdout_accuracy": None, "holdout_auc": None}
    del dev
    if holdout_chunks:
        zs, ys = [], []
        for i in range(n_train, n_chunks):
            y, counts, bucket = get(i)
            zs.append(np.asarray(_scores(state, jnp.asarray(counts),
                                         jnp.asarray(bucket), dt=dt)))
            ys.append(y)
        z, y = np.concatenate(zs).astype(np.float64), np.concatenate(ys)
        out["holdout_loss"] = float(np.mean(np.asarray(
            row_loss(jnp.asarray(z, jnp.float32), jnp.asarray(y), loss),
            np.float64)))
        out["holdout_accuracy"] = float(np.mean((z > 0) == (y > 0.5)))
        if loss == "logistic":
            out["holdout_auc"] = exact_auc(z, y)
    out["emb"] = state[0]            # stays on the device: 2 GB at 2^29
    out["coef"] = np.asarray(state[2].astype(jnp.float32))
    out["intercept"] = np.asarray(state[4].astype(jnp.float32)).reshape(1)
    return out
