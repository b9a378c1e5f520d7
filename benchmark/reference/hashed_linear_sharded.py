"""Plain reference of the hashed linear fit for a table one device cannot
hold: ``reference/hashed_linear.py``'s fit — the same features, row loss,
dense Adagrad with decoupled weight decay, epochs over the same chunks,
holdout loss — with its ``[n_dims]`` weight, accumulator and dense
gradient placed over the cell's devices by a plain ``NamedSharding``
(rows over one mesh axis: at 2^30 rows and four chips 1.07 GB a chip per
table, 3.2 GB for the three together; the one-device reference would need
12.9 GB on one). Straightforward jax.numpy in float32 at 'highest' matmul
precision; imports nothing of the program and takes nothing it has made.

Departures from the one-device reference, each because of the placement
and none in the arithmetic:
- the three table-sized arrays carry ``NamedSharding(mesh, P('rows'))``
  over a one-axis mesh of the devices given; chunks, the dense leaf and
  every scalar are replicated. The program's own layout (rows of the
  chunk over ``data``, table over ``model``) is NOT copied: each device
  here sees every row of a chunk and owns a quarter of the table;
- the step pins the dense gradient and the updated tables to that
  sharding (``with_sharding_constraint``), so the partitioner scatters
  each device's own range of rows and never gathers a table;
- holdout scores gather ``emb[bucket]`` from the sharded table (a masked
  lookup per device and a sum over devices: one non-zero term a row).
Per table row the additions happen in the same order as on one device
(occurrences of a row are added in chunk order on the device that owns
it), so at the rehearsal size the fit equals ``hashed_linear.fit`` to the
bit on XLA:CPU (``tests/test_hashed_sharded_reference.py``).

``precision`` and ``fault`` are ``hashed_linear.fit``'s.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmark.reference.hashed_linear import (  # noqa: F401 - features
    ADAGRAD_EPS, exact_auc, features, row_loss, row_loss_grad,  # re-exported
)


def _logits(emb, coef, b, counts, bucket, dt):
    z = jnp.sum(emb[bucket].astype(dt), axis=1, dtype=dt)
    z = z + jnp.dot(counts.astype(dt), coef.astype(dt),
                    precision="highest", preferred_element_type=dt)
    return z + b.astype(dt)


@functools.partial(jax.jit, static_argnames=("dt", "loss", "table"),
                   donate_argnums=(0,))
def _step(state, y, counts, bucket, lr, reg, *, dt, loss, table):
    emb, acc, coef, cacc, b, bacc = state
    y = y.astype(dt)
    z = _logits(emb, coef, b, counts, bucket, dt)
    mean_loss = jnp.mean(row_loss(z, y, loss).astype(jnp.float32))
    dl = (row_loss_grad(z, y, loss) / y.shape[0]).astype(dt)
    g = jnp.zeros(emb.shape, dt).at[bucket.reshape(-1)].add(
        jnp.repeat(dl, bucket.shape[1]))
    g = jax.lax.with_sharding_constraint(g, table)
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
    emb = jax.lax.with_sharding_constraint(emb, table)
    acc = jax.lax.with_sharding_constraint(acc, table)
    coef, cacc = rule(coef, cacc, g_coef, True)
    b, bacc = rule(b, bacc, g_b, False)
    return (emb, acc, coef, cacc, b, bacc), mean_loss


@functools.partial(jax.jit, static_argnames=("dt",))
def _scores(state, counts, bucket, *, dt):
    emb, _, coef, _, b, _ = state
    return _logits(emb, coef, b, counts, bucket, dt).astype(jnp.float32)


def fit(chunks, *, devices, n_dims: int, n_dense: int, epochs: int,
        holdout_chunks: int, step_size: float, reg_param: float,
        loss: str = "logistic", precision: str = "float32",
        fault: str | None = None) -> dict:
    """``hashed_linear.fit`` with the tables over ``devices`` (their number
    divides ``n_dims``). -> the same dict; ``emb`` stays on the devices,
    sharded."""
    get, n_chunks = chunks
    if fault not in (None, "skip_step", "half_batch"):
        raise ValueError(fault)
    mesh = Mesh(np.asarray(list(devices)), ("rows",))
    table = NamedSharding(mesh, P("rows"))
    everywhere = NamedSharding(mesh, P())
    dt = jnp.dtype(precision)
    n_train = n_chunks - holdout_chunks

    def small(shape):
        return jnp.zeros(shape, dt, device=everywhere)

    state = (jnp.zeros((n_dims,), dt, device=table),
             jnp.zeros((n_dims,), dt, device=table),
             small((n_dense,)), small((n_dense,)), small(()), small(()))
    lr, reg = jnp.float32(step_size), jnp.float32(reg_param)

    def put(arrays):
        return tuple(jax.device_put(a, everywhere) for a in arrays)

    dev = [put(get(i)) for i in range(n_train)]
    if fault == "half_batch":
        dev = [tuple(a[:a.shape[0] // 2] for a in c) for c in dev]
    last = None
    for epoch in range(epochs):
        for k, (y, counts, bucket) in enumerate(dev):
            if fault == "skip_step" and epoch == 0 and k == 1:
                continue
            state, last = _step(state, y, counts, bucket, lr, reg, dt=dt,
                                loss=loss, table=table)
    out = {"final_loss": float(last), "holdout_loss": None,
           "holdout_accuracy": None, "holdout_auc": None}
    del dev
    if holdout_chunks:
        zs, ys = [], []
        for i in range(n_train, n_chunks):
            y, counts, bucket = get(i)
            zs.append(np.asarray(_scores(state, *put((counts, bucket)),
                                         dt=dt)))
            ys.append(y)
        z, y = np.concatenate(zs).astype(np.float64), np.concatenate(ys)
        out["holdout_loss"] = float(np.mean(np.asarray(
            row_loss(jnp.asarray(z, jnp.float32), jnp.asarray(y), loss),
            np.float64)))
        out["holdout_accuracy"] = float(np.mean((z > 0) == (y > 0.5)))
        if loss == "logistic":
            out["holdout_auc"] = exact_auc(z, y)
    out["emb"] = state[0]         # stays on the devices: 1.07 GB a chip
    out["coef"] = np.asarray(state[2].astype(jnp.float32))
    out["intercept"] = np.asarray(state[4].astype(jnp.float32)).reshape(1)
    return out
