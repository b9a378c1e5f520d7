"""Shared sharded L-BFGS trainer for linear models (LogReg, LinearSVC).

MLlib fits its linear classifiers with L-BFGS/OWLQN where each iteration's
loss+gradient is one ``treeAggregate`` over the cluster (SURVEY.md §3 step 3;
reconstructed, mount empty). TPU-native redesign: the ENTIRE optimization loop
— L-BFGS direction, zoom linesearch, convergence test — is a single jitted
``lax.while_loop``. The per-iteration all-reduce falls out of GSPMD: X is
sharded P('data', None), the loss contracts over the row axis, XLA inserts the
ICI all-reduce exactly where Spark would shuffle partial gradients to the
driver. No host round-trip per iteration (Spark pays driver↔executor latency
every step; we pay zero).

The matmuls  X @ coef  ([N,d] @ [d,k]) are the FLOP carriers and map straight
onto the MXU; optionally computed in bfloat16 with f32 accumulation.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import optax
import optax.tree_utils as otu

from orange3_spark_tpu.exec.donate import donating_jit, donation_enabled


class LinearFitResult(NamedTuple):
    coef: jax.Array       # [d, k]
    intercept: jax.Array  # [k]
    n_iter: jax.Array     # []
    final_loss: jax.Array # []


def lbfgs_minimize(value_fn, theta0, tol, max_iter, *, memory_size: int = 10):
    """Shared fused L-BFGS driver: minimize value_fn over the theta0 pytree
    inside one ``lax.while_loop`` (optax.lbfgs + zoom linesearch). Returns
    (theta, n_iter, final_value). Trace-time only — call from inside jit.

    This is the one implementation of the optimizer loop; fit_linear, AFT,
    and the MLP trainer all route through it.
    """
    opt = optax.lbfgs(memory_size=memory_size)
    value_and_grad = optax.value_and_grad_from_state(value_fn)

    def step(carry):
        theta, state = carry
        value, grad = value_and_grad(theta, state=state)
        updates, state = opt.update(
            grad, state, theta, value=value, grad=grad, value_fn=value_fn
        )
        theta = optax.apply_updates(theta, updates)
        return theta, state

    def keep_going(carry):
        _, state = carry
        count = otu.tree_get(state, "count")
        grad = otu.tree_get(state, "grad")
        gnorm = otu.tree_norm(grad)
        # first iteration always runs (grad in fresh state is zero), but
        # max_iter=0 must return the zero init, matching MLlib maxIter=0
        return (max_iter > 0) & ((count == 0) | ((count < max_iter) & (gnorm > tol)))

    theta, state = jax.lax.while_loop(keep_going, step, (theta0, opt.init(theta0)))
    n_iter = otu.tree_get(state, "count")
    # converged loss is already in the linesearch state; only the max_iter=0
    # path (state still holds optax's inf sentinel) pays a fresh evaluation
    final_value = jax.lax.cond(
        n_iter == 0, lambda: value_fn(theta), lambda: otu.tree_get(state, "value")
    )
    return theta, n_iter, final_value


def owlqn_minimize(
    smooth_fn,
    x0,
    l1_weight,
    tol,
    max_iter,
    *,
    memory_size: int = 10,
    max_backtracks: int = 25,
):
    """Orthant-Wise Limited-memory Quasi-Newton (Andrew & Gao 2007), fused
    into ONE ``lax.while_loop``: minimizes  smooth_fn(x) + Σ l1_weight·|x|.

    MLlib fits elasticNetParam>0 linear models with Breeze's OWLQN, one
    treeAggregate per iteration (SURVEY.md §2b row "LogisticRegression /
    LinearSVC"; reconstructed, mount empty). Here the whole solver — pseudo-
    gradient, two-loop recursion over fixed-size (m, n) memory buffers,
    orthant-projected backtracking linesearch — is a single XLA program; the
    gradient all-reduce falls out of GSPMD like the L2 path's.

    Args:
      smooth_fn: x[n] -> scalar, the differentiable part of the objective.
      l1_weight: f32[n] per-coordinate L1 penalty (0 on unpenalized coords,
        e.g. the intercept).
    Returns (x, n_iter, final_full_value). Trace-time only — call under jit.
    """
    m = memory_size
    c1 = 1e-4
    grad_fn = jax.value_and_grad(smooth_fn)

    def full_value(x):
        return smooth_fn(x) + jnp.sum(l1_weight * jnp.abs(x))

    def pseudo_grad(x, g):
        # subgradient of minimum norm: steepest-descent direction of F
        right = g + l1_weight
        left = g - l1_weight
        return jnp.where(
            x > 0, right,
            jnp.where(
                x < 0, left,
                jnp.where(right < 0, right, jnp.where(left > 0, left, 0.0)),
            ),
        )

    def two_loop(gp, S, Y, rho, n_mem):
        # newest pair at slot m-1; the last n_mem slots are valid
        valid = jnp.arange(m) >= (m - n_mem)

        def bwd(j, carry):
            q, alpha = carry
            i = m - 1 - j
            a_i = jnp.where(valid[i], rho[i] * jnp.dot(S[i], q), 0.0)
            return q - a_i * Y[i], alpha.at[i].set(a_i)

        q, alpha = jax.lax.fori_loop(0, m, bwd, (gp, jnp.zeros((m,), gp.dtype)))
        sy = jnp.dot(S[m - 1], Y[m - 1])
        yy = jnp.dot(Y[m - 1], Y[m - 1])
        gamma = jnp.where(n_mem > 0, sy / jnp.maximum(yy, 1e-30), 1.0)

        def fwd(i, r):
            b_i = jnp.where(valid[i], rho[i] * jnp.dot(Y[i], r), 0.0)
            return r + S[i] * (alpha[i] - b_i)

        return jax.lax.fori_loop(0, m, fwd, gamma * q)

    def linesearch(x, F, gp, d, n_mem):
        # orthant of the current point (sign forced by -gp on zero coords);
        # every trial point is projected back into it
        xi = jnp.where(x != 0, jnp.sign(x), jnp.sign(-gp))
        t0 = jnp.where(
            n_mem > 0, 1.0, 1.0 / jnp.maximum(jnp.linalg.norm(d), 1e-12)
        )

        def body(carry):
            t, k, _, _, _ = carry
            x_t = jnp.where((x + t * d) * xi > 0, x + t * d, 0.0)
            F_t = full_value(x_t)
            ok = F_t <= F + c1 * jnp.dot(gp, x_t - x)
            return t * 0.5, k + 1, x_t, F_t, ok

        def cond(carry):
            _, k, _, _, ok = carry
            return (~ok) & (k < max_backtracks)

        _, _, x_t, F_t, ok = jax.lax.while_loop(
            cond, body, (t0, 0, x, F, False)
        )
        # an exhausted linesearch must NOT adopt its rejected trial point —
        # keep the last accepted iterate and let the stalled flag end the loop
        x_t = jnp.where(ok, x_t, x)
        F_t = jnp.where(ok, F_t, F)
        return x_t, F_t, ok

    def step(carry):
        x, F, g, _, S, Y, rho, n_mem, it, _ = carry
        gp = pseudo_grad(x, g)
        d = -two_loop(gp, S, Y, rho, n_mem)
        d = jnp.where(d * gp < 0, d, 0.0)  # keep only descent-aligned coords
        # a fully-zeroed direction would make the linesearch accept x_t == x
        # (Armijo holds trivially at step 0) and spin to max_iter — treat it
        # as converged/stalled instead
        d_zero = ~jnp.any(d != 0.0)
        x_new, F_new, ok = linesearch(x, F, gp, d, n_mem)
        _, g_new = grad_fn(x_new)
        s, yv = x_new - x, g_new - g
        sy = jnp.dot(s, yv)
        keep = sy > 1e-10  # curvature condition: only well-posed pairs enter
        S = jnp.where(keep, jnp.roll(S, -1, axis=0).at[m - 1].set(s), S)
        Y = jnp.where(keep, jnp.roll(Y, -1, axis=0).at[m - 1].set(yv), Y)
        rho = jnp.where(
            keep, jnp.roll(rho, -1).at[m - 1].set(1.0 / sy), rho
        )
        n_mem = jnp.where(keep, jnp.minimum(n_mem + 1, m), n_mem)
        gpnorm = jnp.linalg.norm(pseudo_grad(x_new, g_new))
        return x_new, F_new, g_new, gpnorm, S, Y, rho, n_mem, it + 1, ~ok | d_zero

    def keep_going(carry):
        _, _, _, gpnorm, *_, it, stalled = carry
        return (it < max_iter) & (gpnorm > tol) & (~stalled)

    n = x0.shape[0]
    f0, g0 = grad_fn(x0)
    F0 = f0 + jnp.sum(l1_weight * jnp.abs(x0))
    init = (
        x0, F0, g0, jnp.linalg.norm(pseudo_grad(x0, g0)),
        jnp.zeros((m, n), x0.dtype), jnp.zeros((m, n), x0.dtype),
        jnp.zeros((m,), x0.dtype), jnp.int32(0), jnp.int32(0), False,
    )
    x, F, _, _, _, _, _, _, n_iter, _ = jax.lax.while_loop(
        keep_going, step, init
    )
    return x, n_iter, F


def _make_objective(loss_kind: str, fit_intercept: bool, compute_dtype):
    """Builds loss(theta, X, y, w, reg_l2, sum_w) -> scalar.

    Losses (all per-row, weighted, normalized by total weight — MLlib's
    objective convention: (1/Σw) Σ wᵢ·lossᵢ + regParam·R(coef), intercept
    unregularized):
      * 'logistic'      — softmax cross-entropy over k classes
      * 'hinge'         — binary SVM hinge on the first logit (LinearSVC)
      * 'squared_hinge' — smooth hinge variant (plays nicer with L-BFGS)
      * 'squared'       — least squares (LinearRegression)
    """

    def objective(theta, X, y, w, reg_l2, sum_w, col_scale):
        coef = theta["coef"]
        intercept = theta["intercept"]
        # THE in-scan decode point for compressed caches (io/codec.py):
        # a bf16-cached X widens here — one fused convert-on-load, so the
        # streaming replay scan reads half the HBM/spill bytes while the
        # matmul accumulates in f32 exactly as before (f32 input: no-op).
        # tests/test_cache_codec.py pins the bf16-vs-f32 fit divergence.
        Xc = X.astype(compute_dtype)
        # fold per-column standardization into the coefficient side: X@(s*B)
        # keeps the [N,d] operand untouched (no scaled copy of the data ever
        # materializes — XLA fuses the [d,k] scale into the matmul epilogue)
        logits = jnp.dot(Xc, (coef * col_scale[:, None]).astype(compute_dtype),
                         preferred_element_type=jnp.float32)
        if fit_intercept:
            logits = logits + intercept
        row_loss = per_row_loss(loss_kind, logits, y)
        data_loss = jnp.sum(row_loss * w) / sum_w
        return data_loss + 0.5 * reg_l2 * jnp.sum(coef * coef)

    return objective


def per_row_loss(loss_kind: str, logits, y):
    """Per-row loss from precomputed logits — the ONE implementation shared
    by the dense objective, the streaming step, and the hashed-sparse path
    (whose logits come from an embedding gather, not a matmul)."""
    if loss_kind == "logistic":
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(
            logp, y.astype(jnp.int32)[:, None], axis=1
        )[:, 0]
    if loss_kind == "binary_logistic":
        # single-logit sigmoid form (k=1): numerically stable softplus(z)-z*y.
        # Identical optimum to 2-column softmax but HALF the embedding-table
        # gather/scatter traffic — the hashed Criteo path's hot bytes.
        z = logits[:, 0]
        return jnp.maximum(z, 0.0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
    if loss_kind in ("hinge", "squared_hinge"):
        sign = 2.0 * y - 1.0
        margin = jnp.maximum(0.0, 1.0 - sign * logits[:, 0])
        return margin if loss_kind == "hinge" else margin**2
    if loss_kind == "squared":
        return 0.5 * (logits[:, 0] - y) ** 2
    raise ValueError(loss_kind)  # pragma: no cover


@donating_jit(
    static_argnames=("loss_kind", "k", "fit_intercept", "memory_size",
                     "compute_dtype"),
    donate_argnums=(0, 1, 2),
)
def _fit_linear_jit(
    X, y, w, reg_l2, tol, max_iter, col_scale, reg_l1,
    *,
    loss_kind: str,
    k: int,
    fit_intercept: bool = True,
    memory_size: int = 10,
    compute_dtype=jnp.float32,
):
    d = X.shape[1]
    if col_scale is None:
        col_scale = jnp.ones((d,), jnp.float32)
    theta0 = {
        "coef": jnp.zeros((d, k), jnp.float32),
        "intercept": jnp.zeros((k,), jnp.float32),
    }
    sum_w = jnp.maximum(jnp.sum(w), EPS_TOTAL_WEIGHT)
    objective = _make_objective(loss_kind, fit_intercept, compute_dtype)

    def value_fn(theta):
        return objective(theta, X, y, w, reg_l2, sum_w, col_scale)

    if reg_l1 is not None:
        from jax.flatten_util import ravel_pytree

        x0, unravel = ravel_pytree(theta0)
        # L1 hits the coefficients only — never the intercept (MLlib)
        l1_mask, _ = ravel_pytree(
            {"coef": jnp.ones((d, k), jnp.float32),
             "intercept": jnp.zeros((k,), jnp.float32)}
        )
        x, n_iter, final_loss = owlqn_minimize(
            lambda x: value_fn(unravel(x)),
            x0, reg_l1 * l1_mask, tol, max_iter, memory_size=memory_size,
        )
        theta = unravel(x)
    else:
        theta, n_iter, final_loss = lbfgs_minimize(
            value_fn, theta0, tol, max_iter, memory_size=memory_size
        )
    return LinearFitResult(
        coef=theta["coef"],
        intercept=theta["intercept"] if fit_intercept else jnp.zeros((k,)),
        n_iter=n_iter,
        final_loss=final_loss,
    )


def fit_linear(
    X,             # f32[N_pad, d]  sharded P('data', None)
    y,             # f32[N_pad]     labels (class index, ±target, or regression y)
    w,             # f32[N_pad]     weights; 0 on padding
    reg_l2,        # f32[] L2 regParam
    tol,           # f32[] gradient-norm tolerance
    max_iter,      # i32[]
    col_scale=None,  # f32[d] standardization scale folded into the matmul
    reg_l1=None,     # f32[] L1 strength (elasticNet); None -> pure-L2 L-BFGS
    *,
    loss_kind: str,
    k: int,
    fit_intercept: bool = True,
    memory_size: int = 10,
    compute_dtype=jnp.float32,
    donate_data: bool = False,
):
    """One fused XLA program: full L-BFGS (or OWLQN when reg_l1 is given)
    fit of a linear model.

    MLlib's regParam/elasticNetParam split maps to
    ``reg_l2 = regParam*(1-alpha), reg_l1 = regParam*alpha``; with
    standardization the L1 applies in the SCALED space, matching MLlib.

    Note: with ``col_scale`` the optimization runs in the scaled space; the
    returned coef is the SCALED-space coefficient — callers multiply by the
    scale to return to original feature space (MLlib does the same rescale).

    ``donate_data=True`` donates the (X, y, w) buffers to the fit (the
    exec/donate.py sweep): the estimator entry points pass table-BORROWED
    arrays that must survive for transform/evaluate, so donation is opt-in
    for callers feeding one-shot transient batches (tuning folds, staged
    refit loops) — it frees the batch's HBM the moment the fit consumes
    it. Bit-identical either way (donation is pure buffer aliasing).
    """
    jitted = (_fit_linear_jit.donated
              if donate_data and donation_enabled()
              else _fit_linear_jit.plain)
    return jitted(
        X, y, w, reg_l2, tol, max_iter, col_scale, reg_l1,
        loss_kind=loss_kind, k=k, fit_intercept=fit_intercept,
        memory_size=memory_size, compute_dtype=compute_dtype,
    )


# MLlib-style scale-only standardization factor; shared stats kernels.
from orange3_spark_tpu.ops.stats import (  # noqa: E402
    EPS_TOTAL_WEIGHT,
    inv_std_scale as column_inv_std,
)
