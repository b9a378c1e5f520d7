"""Job kind ``fit_stream``: one analyst's fit of a streaming hashed linear
model over a Criteo-format TSV, through the program's own entry points
(``csv_raw_chunk_source`` -> ``StreamingHashedLinearEstimator.fit_stream``
-> ``evaluate_device``). The traffic file says how the fit is asked for
(epochs, device cache, holdout); the configuration file says what is
fitted. Nothing here times anything but whole calls ending in
``block_until_ready``.
"""

from __future__ import annotations

import importlib
import time

import numpy as np

from benchmark import harness
from benchmark.datagen import criteo_tsv

#: every EMB_STRIDE-th table row is kept of each job's model, so that every
#: job of the window is compared and not the last alone
EMB_STRIDE = 4096
MODES = ("program", "control_program", "control_reference",
         "fault_skip_step", "fault_half_batch")


class Job:
    modes = MODES

    def __init__(self, config: dict, traffic: dict, seed: int,
                 data_dir: str):
        self.cfg, self.traffic, self.seed = config, traffic, int(seed)
        self.data_dir = data_dir
        self.est_kw = dict(config["estimator"])
        self.rows = int(config["rows"])
        self.epochs = int(traffic.get("epochs", config["epochs"]))
        self.cache_device = bool(traffic["cache_device"])
        self.holdout_chunks = int(traffic["holdout_chunks"])
        self.chunk_rows = int(self.est_kw["chunk_rows"])
        self.n_chunks = -(-self.rows // self.chunk_rows)
        self.n_dims = int(self.est_kw["n_dims"])
        self.reference = importlib.import_module(
            f"benchmark.reference.{config['reference']}")
        self.work_fn = importlib.import_module(
            f"benchmark.work.{config['work']}")
        self.model = None          # the last job's fitted model
        self.last_emb = None       # its table on the host (take_last)
        self.path = None
        self._gen = None           # the generator's seed-wide constants
        self._chunks: dict = {}

    # ------------------------------------------------------------ set-up
    def prepare(self) -> dict:
        from orange3_spark_tpu.io import native

        t0 = time.perf_counter()
        native.get_lib()                      # g++ build on first use
        t1 = time.perf_counter()
        self.path, generated = criteo_tsv.ensure_tsv(
            self.cfg["data"], self.cfg["name"], self.rows, self.seed,
            self.data_dir)
        return {"native_build_s": t1 - t0,
                "data_s": time.perf_counter() - t1,
                "data_generated": generated}

    def _estimator(self):
        from orange3_spark_tpu.models.hashed_linear import (
            StreamingHashedLinearEstimator,
        )

        return StreamingHashedLinearEstimator(
            epochs=self.epochs, **self.est_kw)

    def _source(self):
        from orange3_spark_tpu.io.streaming import csv_raw_chunk_source

        n_dense, n_cat = self.est_kw["n_dense"], self.est_kw["n_cat"]
        inner = csv_raw_chunk_source(
            self.path, chunk_rows=self.chunk_rows, delimiter="\t",
            header=False,
            categorical_cols=tuple(range(1 + n_dense, 1 + n_dense + n_cat)))

        def source():
            it = iter(inner())
            while True:
                with harness.span("parse"):
                    chunk = next(it, None)
                if chunk is None:
                    return
                yield chunk

        return source

    # --------------------------------------------------------------- job
    def run(self) -> dict:
        """One whole job. -> rows, seconds, the program's stage seconds and
        what is compared of the result."""
        import jax

        self.model = None                     # the previous job's table
        stages: dict = {}
        t0 = time.perf_counter()
        with harness.span("fit_stream"):
            model = self._estimator().fit_stream(
                self._source(), cache_device=self.cache_device,
                holdout_chunks=self.holdout_chunks, stage_times=stages)
            jax.block_until_ready(model.theta)
        t_fit = time.perf_counter()
        ev = None
        if self.holdout_chunks and model.holdout_chunks_:
            with harness.span("evaluate"):
                ev = model.evaluate_device(model.holdout_chunks_)
        t1 = time.perf_counter()
        with harness.span("digest"):
            emb = model.theta["emb"]
            answer = {
                "final_loss": model.final_loss_,
                "n_steps": model.n_steps_,
                "coef": np.asarray(model.theta["coef"])[:, 0],
                "intercept": np.asarray(model.theta["intercept"]),
                "emb_slice": np.asarray(emb[::EMB_STRIDE, 0]),
                "holdout_loss": ev["logloss"] if ev else None,
                "holdout_accuracy": ev["accuracy"] if ev else None,
                "holdout_auc": ev.get("auc") if ev else None,
            }
        # the device cache and the holdout die with the job, the table stays
        # until the next job starts (or the window's close reads it)
        model.device_chunks_ = model.holdout_chunks_ = None
        self.model = model
        ep = stages.get("epoch_s") or [None]
        return {
            "rows": self.rows, "seconds": t1 - t0,
            "spans": {
                "fit_s": t_fit - t0, "evaluate_s": t1 - t_fit,
                "ingest_s": sum(stages.get(k, 0.0) for k in
                                ("parse_s", "encode_s", "h2d_s")),
                "parse_s": stages.get("parse_s"),
                "encode_s": stages.get("encode_s"),
                "h2d_s": stages.get("h2d_s"),
                "epoch1_s": ep[0],
                "replay_s": stages.get("replay_fused_s"),
                "prefetch_wait_s": stages.get("prefetch_wait_s"),
                "overlap_pct": stages.get("overlap_pct"),
            },
            "resolved": {k: stages.get(k) for k in
                         ("optim_update", "sparse_lowering", "cache_dtype",
                          "replay_source", "cache_overflow", "cache_bytes")},
            "answer": answer,
        }

    # ------------------------------------------------------- after window
    def take_last(self) -> None:
        """Bring the last job's table to the host and free the device."""
        if self.model is not None:
            self.last_emb = np.asarray(self.model.theta["emb"])[:, 0]
        self.model = None

    def _chunk(self, i: int):
        """(y, counts, bucket) of chunk i, regenerated from the seed."""
        if i not in self._chunks:
            data = self.cfg["data"]
            if self._gen is None:
                self._gen = criteo_tsv.Model(data, self.seed)
            br = int(data["block_rows"])
            lo, hi = i * self.chunk_rows, min((i + 1) * self.chunk_rows,
                                              self.rows)
            parts = []
            for b in range(lo // br, (hi - 1) // br + 1):
                blk = criteo_tsv.block(self._gen, self.seed, b,
                                       min(br, self.rows - b * br))
                s, e = max(lo - b * br, 0), min(hi - b * br, br)
                parts.append({k: v[s:e] for k, v in blk.items()})
            rows = {k: np.concatenate([p[k] for p in parts])
                    for k in parts[0]}
            self._chunks[i] = self.reference.features(
                rows, self.n_dims, int(self.est_kw.get("seed", 0)))
        return self._chunks[i]

    def reference_answer(self, precision: str = "float32",
                      fault: str | None = None) -> dict:
        return self.reference.fit(
            (self._chunk, self.n_chunks), n_dims=self.n_dims,
            n_dense=self.est_kw["n_dense"], epochs=self.epochs,
            holdout_chunks=self.holdout_chunks,
            step_size=self.est_kw["step_size"],
            reg_param=self.est_kw["reg_param"], loss=self.est_kw["loss"],
            precision=precision, fault=fault)

    def reference_for_check(self) -> dict:
        """What ``compare`` needs of the reference's own fit: all of it."""
        return self.reference_answer()

    def compare(self, answers: list, ref: dict, last_emb=None) -> dict:
        """The numbers compared: every job's answer against the reference,
        the worst job kept; the whole table of the last job."""
        import jax.numpy as jnp

        def rel(a, b):
            a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
            return float(np.linalg.norm(a - b)
                         / max(np.linalg.norm(b), 1e-30))

        ref_emb = ref["emb"].astype(jnp.float32)
        ref_slice = np.asarray(ref_emb[::EMB_STRIDE])
        ref_dense = np.concatenate([ref["coef"], ref["intercept"]])
        out = {"final_loss": 0.0, "dense_leaf": 0.0, "emb_slice": 0.0}
        # the holdout's accuracy and AUC are read back and logged, not
        # compared: a count of rows whose score changes sign reads 0 to 2
        # rows in sound runs and 6 in the control, under three times apart
        # (PERF.md section 6, PR 25); the holdout's loss separates them
        held = [k for k in ("holdout_loss",) if ref[k] is not None]
        out.update({k: 0.0 for k in held})
        for a in answers:
            worst = {
                "final_loss": abs(a["final_loss"] - ref["final_loss"])
                / abs(ref["final_loss"]),
                "dense_leaf": rel(np.concatenate([a["coef"],
                                                  a["intercept"]]),
                                  ref_dense),
                "emb_slice": rel(a["emb_slice"], ref_slice),
            }
            for k in held:
                worst[k] = abs(a[k] - ref[k]) / ref[k]
            out = {k: max(v, worst[k]) for k, v in out.items()}
        if last_emb is not None:
            # in blocks: the program's table comes back from the host
            num = den = 0.0
            blk = 1 << 26
            for s in range(0, last_emb.shape[0], blk):
                d = jnp.asarray(last_emb[s:s + blk]) - ref_emb[s:s + blk]
                num += float(jnp.sum(d * d))
                den += float(jnp.sum(ref_emb[s:s + blk] ** 2))
            out["emb_table"] = float(np.sqrt(num / max(den, 1e-30)))
        return out

    def check(self, answers: list) -> dict:
        ref = self.reference_for_check()
        numbers = self.compare(answers, ref, self.last_emb)
        self.ref_summary = {k: ref[k] for k in
                            ("final_loss", "holdout_loss",
                             "holdout_accuracy", "holdout_auc")}
        return numbers

    def reading(self, mode: str, ref: dict) -> dict:
        """One reading for the limits (``control.py``): the program, the
        program with its own lower-precision path on, or the reference put
        in the program's place in the control precision or with a fault
        planted — each through the comparison a run makes."""
        import jax.numpy as jnp

        if mode in ("program", "control_program"):
            stated = self.est_kw["compute_dtype"]
            if mode == "control_program":
                self.est_kw["compute_dtype"] = self.cfg["control_precision"]
            try:
                answer = self.run()["answer"]
            finally:
                self.est_kw["compute_dtype"] = stated
            self.take_last()
            return self.compare([answer], ref, self.last_emb)
        if mode == "control_reference":
            fit = self.reference_answer(self.cfg["control_precision"])
        elif mode.startswith("fault_"):
            fit = self.reference_answer(fault=mode[len("fault_"):])
        else:
            raise ValueError(f"{mode!r}: this job kind has {MODES}")
        emb = np.asarray(fit["emb"].astype(jnp.float32))
        answer = {k: fit[k] for k in ("final_loss", "coef", "intercept",
                                      "holdout_loss", "holdout_accuracy",
                                      "holdout_auc")}
        answer["emb_slice"] = emb[::EMB_STRIDE]
        return self.compare([answer], ref, emb)

    # ------------------------------------------------------------- work
    def work(self, peaks: dict) -> dict:
        """Least chip seconds of one job, and of its step programs, from
        the work function and the chunks' own distinct-row counts."""
        distinct = [int(np.unique(self._chunk(i)[2]).size)
                    for i in range(self.n_chunks)]
        return self.work_fn.job_work(
            chunk_rows=self.chunk_rows, n_dense=self.est_kw["n_dense"],
            n_cat=self.est_kw["n_cat"], n_dims=self.n_dims,
            distinct_rows=distinct, epochs=self.epochs,
            holdout_chunks=self.holdout_chunks, peaks=peaks)
