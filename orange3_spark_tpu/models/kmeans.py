"""KMeans — parity with ``pyspark.ml.clustering.KMeans``.

MLlib runs Lloyd's algorithm with k-means|| initialization, one
treeAggregate per iteration to sum per-cluster centroids (SURVEY.md §2b row
"KMeans"; reconstructed, mount empty). TPU-native redesign:

* assignment = argmin of pairwise squared distances computed with the matmul
  identity  |x-c|² = |x|² - 2x·c + |c|²  — the 2x·c term is an [N,d]@[d,k]
  MXU matmul, not a broadcast subtract (HBM-bandwidth friendly);
* center update = one-hot(assign)ᵀ @ X — another MXU matmul whose row-axis
  contraction GSPMD all-reduces over ICI (the treeAggregate moment);
* the whole Lloyd loop is a single jitted ``lax.while_loop`` with the MLlib
  convergence test (all center moves < tol).

Init: 'random' samples k distinct live rows; 'k-means||' is served by
kmeans++ on a host-side sample (≤ init_sample_size rows) — same quality goal
(spread seeds) without a multi-round distributed sampling pass.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from orange3_spark_tpu.core.domain import DiscreteVariable, Domain
from orange3_spark_tpu.core.table import TpuTable
from orange3_spark_tpu.exec.donate import donating_jit
from orange3_spark_tpu.models.base import concrete_or_none, Estimator, Model, Params
from orange3_spark_tpu.obs.registry import REGISTRY
from orange3_spark_tpu.ops.stats import rows_dot

_M_ITERATIONS = REGISTRY.counter(
    "otpu_kmeans_iterations_total",
    "Lloyd iterations run by finished KMeans fits, by fit: eager (counted "
    "at the end of the fit) | staged (a staged refit, workflow/staging.py: "
    "counted when its states come back)")


@dataclasses.dataclass(frozen=True)
class KMeansParams(Params):
    k: int = 2                    # MLlib k
    max_iter: int = 20            # MLlib maxIter
    tol: float = 1e-4             # MLlib tol (center movement)
    init_mode: str = "k-means||"  # MLlib initMode: 'random' | 'k-means||'
    seed: int = 0                 # MLlib seed
    n_init: int = 1               # restarts, best-cost wins (vmapped — beyond
                                  # MLlib, which is single-init; ~free on TPU)
    init_sample_size: int = 8192  # host sample for the ++-style init
    compute_dtype: str = "float32"


def live_cluster_sizes(W, assign, num_segments: int):
    """MLlib ``summary.clusterSizes``: live ROW counts per cluster (Spark
    counts rows, not weights — W only gates padding/filtered membership).
    THE one implementation, shared by KMeans / BisectingKMeans / GMM."""
    # a compare-and-sum over [N, k], fused into one reduction: a
    # segment_sum here is a scatter-add of N values, 1.2 s at 2^27 rows on
    # a v5e where this pass reads its two inputs once (PERF.md, PR 35)
    hit = (assign[:, None] == jnp.arange(num_segments)) & (W > 0)[:, None]
    return jnp.sum(hit, axis=0, dtype=jnp.int32).astype(jnp.float32)


@partial(jax.jit, static_argnames=("compute_dtype",))
def _assign(X, centers, w, compute_dtype=jnp.float32):
    """Nearest-center ids + weighted cost. Distances via the matmul identity."""
    Xc = X.astype(compute_dtype)
    Cc = centers.astype(compute_dtype)
    # [N,k] on the MXU. HIGHEST: the TPU's default rounds both operands of
    # a float32 matmul to bfloat16, which is compute_dtype='bfloat16' under
    # another name (bfloat16 operands pass through unchanged)
    cross = jnp.dot(Xc, Cc.T, preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST)
    x2 = jnp.sum(X * X, axis=1, keepdims=True)
    c2 = jnp.sum(centers * centers, axis=1)
    d2 = x2 - 2.0 * cross + c2
    assign = jnp.argmin(d2, axis=1)
    cost = jnp.sum(jnp.min(d2, axis=1) * w)
    return assign, cost


@donating_jit(static_argnames=("k", "max_iter", "compute_dtype"),
              donate_argnums=(2,))
def _lloyd(X, w, centers0, tol, *, k: int, max_iter: int, compute_dtype=jnp.float32):
    """Fused Lloyd loop. ``centers0`` is DONATED — every caller builds the
    seed centers fresh (host kmeans++ / device D² sampling), and the loop
    round-trips a same-shaped centers array, so XLA reuses the buffer. The
    vmapped restart path calls ``_lloyd.plain`` (donation under vmap
    tracing is a no-op)."""
    def body(carry):
        centers, _, it, _ = carry
        assign, cost = _assign(X, centers, w, compute_dtype)
        onehot = jax.nn.one_hot(assign, k, dtype=jnp.float32) * w[:, None]  # [N,k]
        # [k,d] MXU matmuls over row blocks, all-reduced by GSPMD
        sums = rows_dot(onehot, X)
        counts = jnp.sum(onehot, axis=0)
        new_centers = jnp.where(
            counts[:, None] > 0, sums / jnp.maximum(counts, 1e-12)[:, None], centers
        )
        move = jnp.sqrt(jnp.sum((new_centers - centers) ** 2, axis=1))
        converged = jnp.all(move < tol)
        return new_centers, cost, it + 1, converged

    def keep_going(carry):
        _, _, it, converged = carry
        return (it < max_iter) & ~converged

    centers, cost, n_iter, _ = jax.lax.while_loop(
        keep_going, body, (centers0, jnp.float32(jnp.inf), 0, False)
    )
    # final stats at the converged centers
    assign, cost = _assign(X, centers, w, compute_dtype)
    return centers, assign, cost, n_iter


def kmeanspp_seed(sample: np.ndarray, k: int, rng) -> np.ndarray:
    """kmeans++ seeding on a host-side sample -> f32[k, d] centers.

    Distances/probabilities run in float64 (float32 D² vectors can fail
    numpy's choice() sum-to-1 tolerance on large samples) and the result is
    jitter-padded when the sample has fewer than k distinct points (exact
    duplicate centers would never win an argmin tie and stay empty forever).
    Shared by KMeans._init_centers and io.streaming.StreamingKMeans.
    """
    sample = np.asarray(sample, dtype=np.float64)
    m = len(sample)
    centers = [sample[rng.integers(m)]]
    d2 = np.sum((sample - centers[0]) ** 2, axis=1)
    for _ in range(1, min(k, m)):
        s = d2.sum()
        if s > 0:
            p = d2 / s
            p = p / p.sum()  # exact renormalization for choice()
            centers.append(sample[rng.choice(m, p=p)])
        else:  # all remaining points identical to a seed: pick uniformly
            centers.append(sample[rng.integers(m)])
        d2 = np.minimum(d2, np.sum((sample - centers[-1]) ** 2, axis=1))
    out = np.stack(centers)
    if out.shape[0] < k:  # fewer rows than k: pad with PER-ROW random jitter
        # (a shared constant offset would make the pads exact duplicates of
        # each other — precisely the dead-center failure this guards against)
        extra = out[rng.integers(out.shape[0], size=k - out.shape[0])]
        # jitter scaled to the value's magnitude: an absolute 1e-3 rounds
        # away in float32 when |center| ~ 1e5+ and the pads collapse back
        # into exact duplicates
        jitter = rng.normal(size=extra.shape) * 1e-3 * (1.0 + np.abs(extra))
        out = np.concatenate([out, extra + jitter], axis=0)
    return out.astype(np.float32)


class KMeansModel(Model):
    def __init__(self, params, centers):
        self.params = params
        self.centers = centers  # f32[k, d]
        self.n_iter_: int | None = None
        self.training_cost_: float | None = None  # MLlib summary.trainingCost

    @property
    def state_pytree(self):
        return {"centers": self.centers}

    def load_fit_summary(self, summary, fit: str = "eager") -> None:
        """``cost``, ``n_iter``, ``cluster_sizes``, ``init_centers`` as
        the fit left them on the device; the host mirrors (and the
        iteration counter) follow once they are concrete."""
        self.fit_summary = summary
        self.cluster_sizes_ = summary["cluster_sizes"]
        self.n_iter_ = concrete_or_none(summary["n_iter"], int)
        self.training_cost_ = concrete_or_none(summary["cost"])
        if self.n_iter_ is not None:
            _M_ITERATIONS.inc(self.n_iter_, fit=fit)

    @property
    def cluster_centers_(self) -> np.ndarray:
        return np.asarray(self.centers)

    def predict(self, table: TpuTable) -> np.ndarray:
        assign, _ = _assign(table.X, self.centers, table.W)
        return np.asarray(assign)[: table.n_rows]

    def _device_predict(self, table: TpuTable):
        """Serving hook (serve/context.py): per-row cluster ids, device-pure
        — assignment is row-wise (argmin over centers), so bucket padding
        cannot perturb live rows."""
        assign, _ = _assign(table.X, self.centers, table.W)
        return assign

    def compute_cost(self, table: TpuTable) -> float:
        _, cost = _assign(table.X, self.centers, table.W)
        return float(cost)

    def transform(self, table: TpuTable) -> TpuTable:
        """Append the 'cluster' prediction column (Spark's predictionCol)."""
        assign, _ = _assign(table.X, self.centers, table.W)
        k = self.centers.shape[0]
        new_attrs = list(table.domain.attributes) + [
            DiscreteVariable("cluster", tuple(str(i) for i in range(k)))
        ]
        new_domain = Domain(new_attrs, table.domain.class_vars, table.domain.metas)
        X = jnp.concatenate([table.X, assign[:, None].astype(jnp.float32)], axis=1)
        return table.with_X(X, new_domain)


def device_sample_live(X, W, cap: int, key):
    """Tracer-safe uniform subsample of up to ``cap`` LIVE rows (gumbel-max
    top-k over the live mask): the device twin of the eager inits'
    host-side 8192-row sampling. Seeding on the sample instead of the full
    data turns the D² init's k distance passes from k x N rows into
    k x cap rows — at 10M rows that was the dominant cost of a staged
    REFIT (round-4 measurement: the fused fit program spent more time
    seeding than Lloyd's took to converge). Returns (Xs [cap, d],
    Ws [cap]) where dead/past-live picks carry Ws=0."""
    N = X.shape[0]
    live = W > 0
    g = jnp.where(live, jax.random.gumbel(key, (N,)), -jnp.inf)
    # approx_max_k: the winners of ~cap buckets of rows, each uniform over
    # its bucket's live rows — a uniform sample all the same, where an
    # exact top_k sorts all N (0.44 s at 2^27 rows on a v5e against 2 ms,
    # and 34 s of compile; PERF.md, PR 35). Exact on the CPU
    gv, idx = jax.lax.approx_max_k(g, min(cap, N))
    return X[idx], jnp.isfinite(gv).astype(jnp.float32)


def device_d2_seed(X, W, k: int, k0, k1) -> jnp.ndarray:
    """Device-pure categorical D²-sampling (kmeans++) seeding — tracer-safe,
    shared by KMeans (k-means|| init) and GaussianMixture (means init)
    under staged refit, where the host-sample init cannot run."""
    N, d = X.shape
    live = W > 0
    # first center: uniform over live rows via gumbel-max
    g = jax.random.gumbel(k0, (N,))
    i0 = jnp.argmax(jnp.where(live, g, -jnp.inf))
    centers = jnp.zeros((k, d), X.dtype).at[0].set(X[i0])
    d2 = jnp.where(live, jnp.sum((X - X[i0]) ** 2, axis=1), 0.0)

    def body(c, carry):
        centers, d2, key = carry
        key, kc, ku = jax.random.split(key, 3)
        mask = live & (d2 > 0)
        logits = jnp.where(mask, jnp.log(jnp.maximum(d2, 1e-30)), -jnp.inf)
        cat = jax.random.categorical(kc, logits)
        # all remaining live points coincide with a seed: uniform pick
        gu = jax.random.gumbel(ku, (N,))
        uni = jnp.argmax(jnp.where(live, gu, -jnp.inf))
        idx = jnp.where(jnp.any(mask), cat, uni)
        # duplicate centers get per-coordinate jitter scaled to
        # magnitude (same dead-center guard as kmeanspp_seed)
        newc = X[idx] + jnp.where(
            jnp.any(mask), 0.0,
            1e-3 * (1.0 + jnp.abs(X[idx]))
            * jax.random.normal(ku, (d,), X.dtype),
        )
        centers = centers.at[c].set(newc)
        d2 = jnp.minimum(d2, jnp.sum((X - newc) ** 2, axis=1))
        d2 = jnp.where(live, d2, 0.0)
        return centers, d2, key

    centers, _, _ = jax.lax.fori_loop(1, k, body, (centers, d2, k1))
    return centers


class KMeans(Estimator):
    ParamsCls = KMeansParams
    params: KMeansParams

    def _device_init_centers(self, X, W) -> jnp.ndarray:
        """Device-pure center init — used when the fit itself is being
        TRACED (staged refit, workflow/staging.py): the host-sample init
        below cannot run on tracers (and it ships no sample device→host,
        which the eager init does). Honors ``init_mode``: 'random' is
        a gumbel-max uniform draw of k live rows; 'k-means||' is
        categorical D²-sampling (kmeans++) in a fori_loop. Seeded and
        deterministic, but a different random stream than the host init
        (documented)."""
        p = self.params
        key = jax.random.PRNGKey(p.seed)
        k0, k1 = jax.random.split(key)
        if p.init_mode == "random":
            # k distinct uniform live rows (device_sample_live's gumbel-max
            # top-k). Picks past the live count would land on DEAD rows —
            # the exact stranded-center failure the eager path guards
            # against — so they are replaced by jittered duplicates of the
            # first (live) pick, mirroring the eager live-center padding.
            centers, ws = device_sample_live(X, W, p.k, k0)
            dead = ws == 0
            base = centers[0]                     # live whenever any row is
            jit_ = (1e-3 * (1.0 + jnp.abs(base))
                    * jax.random.normal(k1, centers.shape, X.dtype))
            return jnp.where(dead[:, None], base[None, :] + jit_, centers)
        if p.init_mode != "k-means||":
            raise ValueError(f"unknown init_mode {p.init_mode!r}")
        # seed on a uniform live subsample (the eager path's
        # init_sample_size-row sampling, on device): D² passes then cost
        # k x sample rows, not k x N — the difference between a staged
        # refit that beats the eager walk and one that loses to it at
        # 10M rows
        ks, k0b = jax.random.split(k0)
        Xs, Ws = device_sample_live(X, W, p.init_sample_size, ks)
        return device_d2_seed(Xs, Ws, p.k, k0b, k1)

    def _init_centers(self, table: TpuTable) -> jnp.ndarray:
        p = self.params
        if isinstance(table.X, jax.core.Tracer):
            return self._device_init_centers(table.X, table.W)
        rng = np.random.default_rng(p.seed)
        # sample only live rows — filtered (w=0) rows must not seed centers,
        # or a center stranded on a dead outlier never receives points and
        # Lloyd's keeps it forever
        live = np.flatnonzero(np.asarray(jax.device_get(table.W)) > 0)
        n = len(live)
        if n == 0:
            raise ValueError("cannot fit KMeans: table has no live rows")
        if p.init_mode == "random":
            idx = live[rng.choice(n, size=min(p.k, n), replace=False)]
            centers = np.asarray(jax.device_get(table.X[np.sort(idx)]))
        elif p.init_mode == "k-means||":
            # kmeans++ on a host sample: same seed-spreading intent as
            # MLlib's distributed k-means|| oversampling rounds.
            m = min(n, p.init_sample_size)
            idx = live[rng.choice(n, size=m, replace=False)] if m < n else live
            # gather the sample ON DEVICE, then pull only those m rows host-ward
            # (never device_get the full [N,d] table)
            sample = np.asarray(jax.device_get(table.X[np.sort(idx)]))
            centers = kmeanspp_seed(sample, p.k, rng)
        else:
            raise ValueError(f"unknown init_mode {p.init_mode!r}")
        if centers.shape[0] < p.k:  # fewer rows than k: pad with jitter
            extra = centers[rng.integers(centers.shape[0], size=p.k - centers.shape[0])]
            centers = np.concatenate([centers, extra + 1e-3], axis=0)
        return jax.device_put(centers.astype(np.float32), table.session.replicated)

    def _fit(self, table: TpuTable) -> KMeansModel:
        p = self.params
        lloyd_kw = dict(k=p.k, max_iter=p.max_iter,
                        compute_dtype=jnp.dtype(p.compute_dtype))
        tol = jnp.float32(p.tol)
        if p.n_init <= 1:
            with jax.named_scope("kmeans/init"):
                init = self._init_centers(table)
            with jax.named_scope("kmeans/lloyd"):
                # _lloyd consumes its seed centres: the summary keeps a copy
                centers, assign, cost, n_iter = _lloyd(
                    table.X, table.W, jnp.copy(init), tol, **lloyd_kw)
        else:
            # all restarts advance in lockstep inside one vmapped while_loop —
            # n_init independent Lloyd runs for roughly the cost of one.
            # Donation under a vmap trace is a silent no-op, so call the
            # undonated twin rather than compile a donating executable
            # whose aliasing can never engage.
            with jax.named_scope("kmeans/init"):
                inits = jnp.stack([
                    self.replace_seed(s)._init_centers(table)
                    for s in range(p.seed, p.seed + p.n_init)
                ])
            with jax.named_scope("kmeans/lloyd"):
                centers_v, assign_v, cost_v, iter_v = jax.vmap(
                    lambda c0: _lloyd.plain(table.X, table.W, c0, tol,
                                            **lloyd_kw)
                )(inits)
            best = jnp.argmin(cost_v)
            centers, cost, n_iter = centers_v[best], cost_v[best], iter_v[best]
            assign, init = assign_v[best], inits[best]
        model = KMeansModel(p, centers)
        model.load_fit_summary({
            "cost": cost, "n_iter": n_iter, "init_centers": init,
            # reuses the converged Lloyd assignment — no extra distance pass
            "cluster_sizes": live_cluster_sizes(table.W, assign, p.k),
        })
        return model

    def replace_seed(self, seed: int) -> "KMeans":
        return KMeans(self.params.replace(seed=seed))
