"""Job kind ``tree_fit``: one analyst's GBT fit and forest fit on one
device-resident table, each followed by ``predict_proba`` on a holdout,
through the program's own entry points (``TpuTable.from_numpy`` in set-up,
``GBTClassifier.fit`` / ``RandomForestClassifier.fit`` /
``predict_proba`` in the job). The configuration file says what is fitted;
the answer of a job is the holdout's class-1 probabilities of both models.
"""

from __future__ import annotations

import importlib
import time

import numpy as np

from benchmark import harness
from benchmark.datagen import higgs

MODES = ("program", "control_reference", "fault_skip_step",
         "fault_half_batch")


def _same(a, b) -> bool:
    """Two answers equal to the last bit (nested dicts of arrays)."""
    if isinstance(a, dict):
        return a.keys() == b.keys() and all(_same(a[k], b[k]) for k in a)
    return np.array_equal(a, b)


def _logloss(p: np.ndarray, y: np.ndarray) -> float:
    p = np.clip(p.astype(np.float64), 1e-7, 1 - 1e-7)
    return float(-np.mean(y * np.log(p) + (1 - y) * np.log(1 - p)))


class Job:
    modes = MODES

    def __init__(self, config: dict, traffic: dict, seed: int,
                 data_dir: str):
        self.cfg, self.traffic, self.seed = config, traffic, int(seed)
        self.rows = int(config["rows"])
        self.holdout_rows = int(config["holdout_rows"])
        self.n_feat = int(config["features"])
        self.gbt_kw = dict(config["gbt"])
        self.rf_kw = dict(config["forest"])
        self.reference = importlib.import_module(
            f"benchmark.reference.{config['reference']}")
        self.work_fn = importlib.import_module(
            f"benchmark.work.{config['work']}")
        self.table = self.holdout = None
        self._binned = None        # the reference's (train, holdout) bins

    # ------------------------------------------------------------ set-up
    def prepare(self) -> dict:
        import jax

        from orange3_spark_tpu.core.domain import (
            ContinuousVariable, DiscreteVariable, Domain,
        )
        from orange3_spark_tpu.core.session import TpuSession
        from orange3_spark_tpu.core.table import TpuTable

        t0 = time.perf_counter()
        self.X, self.y = higgs.rows(self.rows + self.holdout_rows,
                                    self.n_feat, self.seed)
        t1 = time.perf_counter()
        session = TpuSession.builder_get_or_create()
        domain = Domain([ContinuousVariable(f"f{i}")
                         for i in range(self.n_feat)],
                        DiscreteVariable("signal", ("0", "1")))
        n = self.rows
        self.table = TpuTable.from_numpy(domain, self.X[:n], self.y[:n],
                                         session=session)
        self.holdout = TpuTable.from_numpy(domain, self.X[n:], self.y[n:],
                                           session=session)
        jax.block_until_ready((self.table.X, self.holdout.X))
        return {"data_s": t1 - t0, "put_s": time.perf_counter() - t1,
                "data_generated": True}

    # --------------------------------------------------------------- job
    def run(self) -> dict:
        import jax

        from orange3_spark_tpu.models.gbt import GBTClassifier
        from orange3_spark_tpu.models.random_forest import (
            RandomForestClassifier,
        )

        spans, answer = {}, {}
        t0 = time.perf_counter()
        for name, est in (("gbt", GBTClassifier(**self.gbt_kw)),
                          ("rf", RandomForestClassifier(**self.rf_kw))):
            t_a = time.perf_counter()
            with harness.span(name + "_fit"):
                model = est.fit(self.table)
                jax.block_until_ready(model.state_pytree)
            t_b = time.perf_counter()
            with harness.span(name + "_predict"):
                proba = model.predict_proba(self.holdout)
            spans[name + "_fit_s"] = t_b - t_a
            spans[name + "_predict_s"] = time.perf_counter() - t_b
            answer[name + "_proba"] = np.asarray(proba[:, 1], np.float32)
            if name == "gbt":     # read back for the look (``compare``)
                with harness.span("digest"):
                    answer["gbt_trees"] = {
                        "f0": float(model.f0),
                        "feature": np.asarray(model.forest.feature),
                        "split_bin": np.asarray(model.forest.split_bin),
                        "leaf": np.asarray(model.forest.leaf_value)[..., 0]}
            del model
        return {"rows": 2 * self.rows, "seconds": time.perf_counter() - t0,
                "spans": spans, "resolved": {}, "answer": answer}

    # ------------------------------------------------------- after window
    def take_last(self) -> None:
        """Free the device: the reference bins the host's copy of the rows."""
        self.table = self.holdout = None

    def _bins(self) -> tuple:
        if self._binned is None:
            n, ref = self.rows, self.reference
            edges = ref.bin_edges(self.X[:n], int(self.gbt_kw["max_bins"]))
            self._binned = (ref.bin_rows(self.X[:n], edges),
                            ref.bin_rows(self.X[n:], edges))
        return self._binned

    def reference_answer(self, precision: str = "float32",
                         fault: str | None = None,
                         models=("gbt", "rf")) -> dict:
        """The reference's own fits, in the shape of a job's answer."""
        B, B_eval = self._bins()
        y, g, f = self.y[:self.rows], self.gbt_kw, self.rf_kw
        out = {}
        if "gbt" in models:
            out["gbt_proba"], out["gbt_trees"] = self.reference.fit_gbt(
                B, y, B_eval, rounds=g["max_iter"], depth=g["max_depth"],
                n_bins=g["max_bins"], precision=precision, fault=fault)
        if "rf" in models:
            out["rf_proba"] = self.reference.fit_forest(
                B, y, B_eval, trees=f["num_trees"], depth=f["max_depth"],
                n_bins=f["max_bins"], seed=int(f.get("seed", 0)),
                precision=precision, fault=fault)
        return out

    def compare(self, answers: list, ref: dict) -> dict:
        """Every job's answer against the reference's own fits from the
        same seed, the worst kept: the mean gap of the holdout's
        probabilities, boosted trees and forest. An answer equal to one
        already judged shares its verdict. The first answer's boosted trees
        are also followed node by node (``check_gbt``) and logged: a
        witness where ``gbt_proba`` reads high, never a comparison, since
        it judges the program by trees the program made."""
        out: dict = {}
        judged: list = []
        for a in answers:
            if any(_same(a, b) for b in judged):
                continue
            judged.append(a)
            for k in ("gbt_proba", "rf_proba"):
                p, r = a[k], ref[k]
                gap = (float(np.mean(np.abs(p.astype(np.float64)
                                            - r.astype(np.float64))))
                       if p.shape == r.shape else float("inf"))
                gap = gap if gap == gap else float("inf")   # nan: not right
                out[k] = max(out.get(k, 0.0), min(gap, 1e30))
        self.logged = {}
        if judged and "gbt_trees" in judged[0]:
            B, B_eval = self._bins()
            g = self.gbt_kw
            self.logged = {"gbt_follow": self.reference.check_gbt(
                B, self.y[:self.rows], B_eval, judged[0]["gbt_proba"],
                judged[0]["gbt_trees"], depth=g["max_depth"],
                n_bins=g["max_bins"])}
        return out

    def reference_for_check(self) -> dict:
        """What ``compare`` needs of the reference's own fits."""
        return self.reference_answer()

    def check(self, answers: list) -> dict:
        ref = self.reference_for_check()
        y = self.y[self.rows:].astype(np.float64)
        numbers = self.compare(answers, ref)
        self.ref_summary = {"gbt_logloss": _logloss(ref["gbt_proba"], y),
                            "rf_logloss": _logloss(ref["rf_proba"], y),
                            **self.logged}
        return numbers

    def reading(self, mode: str, ref: dict) -> dict:
        """One reading for the limits (``control.py``)."""
        if mode == "program":
            answer = self.run()["answer"]
        elif mode == "control_reference":
            answer = self.reference_answer(self.cfg["control_precision"])
        elif mode.startswith("fault_"):
            answer = self.reference_answer(fault=mode[len("fault_"):])
        else:
            raise ValueError(f"{mode!r}: this job kind has {MODES}")
        return self.compare([answer], ref)

    # ------------------------------------------------------------- work
    def work(self, peaks: dict) -> dict:
        g, f = self.gbt_kw, self.rf_kw
        return self.work_fn.job_work(
            rows=self.rows, holdout_rows=self.holdout_rows, d=self.n_feat,
            bins=int(g["max_bins"]),
            fits=[(int(g["max_iter"]), 3, int(g["max_depth"])),
                  (int(f["num_trees"]), 2, int(f["max_depth"]))],
            peaks=peaks)
