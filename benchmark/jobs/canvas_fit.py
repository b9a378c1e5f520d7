"""Job kind ``canvas_fit``: an analyst's canvas OWTable -> OWStandardScaler ->
OWPCA -> OWKMeans re-fitted on a trip table that is already on the device,
through the program's own entry points (``WorkflowGraph``,
``workflow.staging.stage_graph(refit=True)``, ``StagedGraph.run``). Set-up
makes or loads the table, puts it on the device, builds the canvas on the
table's first ``template_rows`` rows (staging runs the graph eagerly once,
and an eager run keeps every widget's output table: four of them do not
fit beside a 4.3 GB input) and stages it; a job is ONE staged refit with
the resident table as the source's replacement — from the call to the
fitted states and the table ready on the device — plus the job's own small
read-back: the states and ``sample_rows`` seeded rows of the table. The
answer of a job is that read-back.
"""

from __future__ import annotations

import importlib
import time

import numpy as np

from benchmark import harness
from benchmark.datagen import taxi

MODES = ("program", "control_program", "control_reference",
         "fault_skip_step", "fault_half_batch", "fault_altered_center")


def _same(a: dict, b: dict) -> bool:
    return a.keys() == b.keys() and all(np.array_equal(a[k], b[k])
                                        for k in a)


class Job:
    modes = MODES

    def __init__(self, config: dict, traffic: dict, seed: int,
                 data_dir: str):
        self.cfg, self.traffic, self.seed = config, traffic, int(seed)
        self.data_dir = data_dir
        self.rows = int(config["rows"])
        self.pca_k = int(config["pca"]["k"])
        self.km = dict(config["kmeans"])
        self.reference = importlib.import_module(
            f"benchmark.reference.{config['reference']}")
        self.work_fn = importlib.import_module(
            f"benchmark.work.{config['work']}")
        self.table = self.staged = None
        self.last_iterations = None

    # ------------------------------------------------------------ set-up
    def prepare(self) -> dict:
        import jax

        from orange3_spark_tpu.core.session import TpuSession
        from orange3_spark_tpu.workflow.staging import StagedGraph

        if not hasattr(StagedGraph, "run"):
            # a program from before PR 35: fail at once, before the data
            raise SystemExit(
                "this program's staged refit hands back no fitted states "
                "(no StagedGraph.run): the cell cannot be judged on it")
        t0 = time.perf_counter()
        where = (self.cfg["name"], self.rows, self.seed, self.data_dir)
        X, generated = taxi.ensure_table(*where)
        self.path = taxi.table_path(*where)
        t1 = time.perf_counter()
        self.session = TpuSession.builder_get_or_create()
        self.table = self._put(X)
        jax.block_until_ready((self.table.X, self.table.W))
        # the rows the canvas is built on; the host's copy of the rest goes
        self.head = np.array(X[:int(self.cfg["template_rows"])])
        del X
        t2 = time.perf_counter()
        rng = np.random.default_rng([self.seed, 11])
        self.sample_idx = np.sort(rng.choice(
            self.rows, size=min(int(self.cfg["sample_rows"]), self.rows),
            replace=False))
        self._idx_dev = jax.device_put(self.sample_idx.astype(np.int32))
        self._take = jax.jit(lambda X, idx: X[idx])
        self.staged, self.src, self.nodes = self._stage(
            self.cfg["precision"])
        return {"data_s": t1 - t0, "put_s": t2 - t1,
                "stage_s": time.perf_counter() - t2,
                "data_generated": generated}

    def _put(self, X):
        from orange3_spark_tpu.core.domain import ContinuousVariable, Domain
        from orange3_spark_tpu.core.table import TpuTable

        domain = Domain([ContinuousVariable(c) for c in taxi.COLUMNS])
        return TpuTable.from_numpy(domain, X, session=self.session)

    def _stage(self, compute_dtype: str):
        """The canvas on the table's first rows, run eagerly once by
        ``stage_graph`` and fused. -> (staged, source node, widget nodes)."""
        from orange3_spark_tpu.widgets.catalog import WIDGET_REGISTRY, OWTable
        from orange3_spark_tpu.workflow.graph import WorkflowGraph
        from orange3_spark_tpu.workflow.staging import stage_graph

        km = self.km
        g = WorkflowGraph()
        src = g.add(OWTable(self._put(self.head)))
        sc = g.add(WIDGET_REGISTRY["OWStandardScaler"](**self.cfg["scaler"]))
        pca = g.add(WIDGET_REGISTRY["OWPCA"](k=self.pca_k))
        kmn = g.add(WIDGET_REGISTRY["OWKMeans"](
            k=km["k"], max_iter=km["max_iter"], tol=km["tol"],
            init_mode=km["init_mode"], seed=int(km["seed"]),
            compute_dtype=compute_dtype))
        g.connect(src, "data", sc, "data")
        g.connect(sc, "data", pca, "data")
        g.connect(pca, "data", kmn, "data")
        staged = stage_graph(g, kmn, refit=True)
        if staged.refit_fallbacks:
            raise RuntimeError(
                "the staged canvas kept eager state where it must re-fit: "
                f"{staged.refit_fallbacks}")
        return staged, src, {"scaler": sc, "pca": pca, "kmeans": kmn}

    # --------------------------------------------------------------- job
    def run(self, staged=None) -> dict:
        import jax

        staged = staged or self.staged
        t0 = time.perf_counter()
        with harness.span("refit"):
            # waits for the table and the states (StagedGraph.run)
            table, states = staged.run(replacements={self.src: self.table})
        t1 = time.perf_counter()
        with harness.span("digest"):
            states, sample = jax.device_get(
                (states, self._take(table.X, self._idx_dev)))
            sc, pca, km = (states[self.nodes[n]]
                           for n in ("scaler", "pca", "kmeans"))
            answer = {
                "scaler_mean": np.asarray(sc["shift"], np.float64),
                "scaler_scale": np.asarray(sc["scale"], np.float64),
                "pca_components": np.asarray(pca["components"], np.float64),
                "pca_variance": np.asarray(pca["explained_variance"],
                                           np.float64),
                "centers": np.asarray(km["centers"], np.float64),
                "init_centers": np.asarray(km["init_centers"], np.float64),
                "cost": float(km["cost"]),
                "n_iter": int(km["n_iter"]),
                "sizes": np.asarray(km["cluster_sizes"], np.float64),
                "sample": np.asarray(sample, np.float64),
            }
        del table
        t2 = time.perf_counter()
        self.last_iterations = answer["n_iter"]
        return {"rows": self.rows, "seconds": t2 - t0,
                "spans": {"refit_s": t1 - t0, "digest_s": t2 - t1,
                          "iterations": answer["n_iter"]},
                "resolved": {"fallbacks": len(staged.refit_fallbacks)},
                "answer": answer}

    # ------------------------------------------------------- after window
    def take_last(self) -> None:
        """Free the device: the reference reads the table's file."""
        self.table = self.staged = self._idx_dev = None

    def _rows(self, precision: str = "float64", fault: str | None = None):
        """The reference's passes over the table: its file mapped (the
        reference splits a mapped table of the cell's size over workers)."""
        return self.reference.Rows(np.load(self.path, mmap_mode="r"),
                                   precision=precision, fault=fault)

    def reference_for_check(self) -> dict:
        """What ``compare`` needs that no answer changes: the scaler's and
        the PCA's own fit, and the open passes over the table."""
        rows = self._rows()
        return {"rows": rows, "st": rows.fit_scaler_pca(self.pca_k)}

    def _judge(self, a: dict, ref: dict) -> dict:
        """One answer against the reference (module docstring of the
        reference: what is proved of the answer before it is followed)."""
        st, rows, k = ref["st"], ref["rows"], self.pca_k
        out = {
            # in units of the column's deviation: 1e-4 moves a trip by a
            # ten-thousandth of the spread of its column
            "scaler_mean": np.abs(a["scaler_mean"] - st["mean"]) / st["std"],
            "scaler_scale": np.abs(a["scaler_scale"] / st["scale"] - 1.0),
            "pca_subspace": self.reference.subspace_gap(
                st, a["pca_components"]),
            "pca_variance": np.abs(a["pca_variance"] / st["eigenvalues"][:k]
                                   - 1.0),
        }
        rows.project(st, a["pca_components"])
        out["init_centers"] = rows.init_gap(a["init_centers"])
        if np.isfinite(out["init_centers"]):
            fit = rows.lloyd(a["init_centers"], max_iter=self.km["max_iter"],
                             tol=self.km["tol"])
            out["centers"] = (np.linalg.norm(a["centers"] - fit["centers"])
                              / np.linalg.norm(fit["centers"]))
            out["cost"] = abs(a["cost"] - fit["cost"]) / fit["cost"]
            out["cluster_sizes"] = (np.abs(a["sizes"] - fit["sizes"]).sum()
                                    / (2.0 * fit["sizes"].sum()))
            out["iterations"] = abs(a["n_iter"] - fit["n_iter"])
        else:
            out.update({n: float("inf") for n in
                        ("centers", "cost", "cluster_sizes", "iterations")})
        scores = rows.rows_at(self.sample_idx)[0]
        out["table_scores"] = (np.linalg.norm(a["sample"][:, :k] - scores)
                               / np.linalg.norm(scores))
        # the cluster column is the argmin under the RETURNED centres
        c = a["centers"]
        d2 = ((scores[:, None, :] - c[None, :, :]) ** 2).sum(-1)
        out["table_cluster"] = np.mean(a["sample"][:, k] != d2.argmin(1))
        return {n: (float(np.max(v)) if np.all(np.isfinite(v))
                    else float("inf")) for n, v in out.items()}

    def compare(self, answers: list, ref: dict) -> dict:
        """Every job's answer against the reference, the worst kept; an
        answer equal to one already judged shares its verdict (the jobs of
        a window refit one table from one seed)."""
        out: dict = {}
        judged: list = []
        for a in answers:
            if any(_same(a, b) for b in judged):
                continue
            judged.append(a)
            for n, v in self._judge(a, ref).items():
                out[n] = max(out.get(n, 0.0), v)
        return out

    def check(self, answers: list) -> dict:
        ref = self.reference_for_check()
        try:
            numbers = self.compare(answers, ref)
        finally:
            ref["rows"].close()
        st = ref["st"]
        self.ref_summary = {"eigenvalues": st["eigenvalues"].tolist(),
                            "iterations": self.last_iterations}
        return numbers

    def reference_answer(self, precision: str = "float64",
                         fault: str | None = None) -> dict:
        """The reference's own fit put in the program's place, in the
        shape of a job's answer: in ``precision``, or with ``fault``
        planted; its initial centres are a seeded draw of its own rows."""
        km, k = self.km, self.pca_k
        with self._rows(precision, "half_batch" if fault == "half_batch"
                        else None) as rows:
            st = rows.fit_scaler_pca(k)
            rows.project(st, st["components"])
            init = rows.draw_init(km["k"], self.seed)
            fit = rows.lloyd(init, max_iter=km["max_iter"], tol=km["tol"],
                             skip_step=fault == "skip_step")
            scores, assign, _ = rows.rows_at(self.sample_idx)
        centers = fit["centers"].copy()
        if fault == "altered_center":
            centers[0] *= 1.05
        return {"scaler_mean": st["mean"], "scaler_scale": st["scale"],
                "pca_components": st["components"],
                "pca_variance": st["eigenvalues"][:k],
                "centers": centers, "init_centers": init,
                "cost": fit["cost"], "n_iter": fit["n_iter"],
                "sizes": fit["sizes"],
                "sample": np.concatenate(
                    [scores, assign[:, None].astype(np.float64)], axis=1)}

    def reading(self, mode: str, ref: dict) -> dict:
        """One reading for the limits (``control.py``): the program, the
        program with KMeans' own lower-precision path on, or the reference
        put in the program's place in the control precision or with a
        fault planted — each through the comparison a run makes."""
        if mode == "program":
            answer = self.run()["answer"]
        elif mode == "control_program":
            staged, _, _ = self._stage(self.cfg["control_precision"])
            answer = self.run(staged)["answer"]
        elif mode == "control_reference":
            answer = self.reference_answer(self.cfg["control_precision"])
        elif mode.startswith("fault_"):
            answer = self.reference_answer(fault=mode[len("fault_"):])
        else:
            raise ValueError(f"{mode!r}: this job kind has {MODES}")
        self.logged = {"n_iter": answer["n_iter"], "cost": answer["cost"]}
        return self.compare([answer], ref)

    # ------------------------------------------------------------- work
    def work(self, peaks: dict) -> dict:
        return self.work_fn.job_work(
            rows=self.rows, d=len(taxi.COLUMNS), pca_k=self.pca_k,
            k=int(self.km["k"]), iterations=int(self.last_iterations or 0),
            peaks=peaks)
