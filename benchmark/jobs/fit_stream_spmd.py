"""Job kind ``fit_stream_spmd``: ``fit_stream``'s job on a partitioner's
session — the same entry points (``csv_raw_chunk_source`` ->
``StreamingHashedLinearEstimator.fit_stream`` -> ``evaluate_device``), with
the mesh the configuration's ``layout`` block states: ``SPMDPartitioner(
devices, model_parallel=...)`` on the cell's first four devices, every
job run inside its session. Nothing else differs: the traffic file says
how the fit is asked for, the parent class what is compared and how.

The reference is the sharded one (``reference/hashed_linear_sharded.py``:
its dense tables over the same four devices) and the work function the
aggregate of the chips the cell names (``work/hashed_linear_spmd.py``).

The harness initialises jax before it makes the job, so a CPU rehearsal
gets its four devices from the caller (the configuration's
``rehearsal_command``)."""

from __future__ import annotations

from benchmark.jobs import fit_stream


class Job(fit_stream.Job):
    def __init__(self, config: dict, traffic: dict, seed: int,
                 data_dir: str):
        super().__init__(config, traffic, seed, data_dir)
        self.layout = config["layout"]
        self.chips = int(self.layout["chips"])
        self.part = None

    def prepare(self) -> dict:
        import jax

        from orange3_spark_tpu.parallel import partitioner

        devices = jax.devices()
        if len(devices) < self.chips:
            raise SystemExit(
                f"job kind fit_stream_spmd needs {self.chips} devices for "
                f"the {self.layout['mesh']} mesh; jax reports "
                f"{len(devices)}. A CPU rehearsal is: "
                f"{self.cfg['rehearsal_command']}")
        kind = getattr(partitioner, self.layout["partitioner"])
        self.part = kind(devices[:self.chips],
                         model_parallel=int(self.layout["model_parallel"]))
        if dict(self.part.mesh.shape) != self.layout["mesh"]:
            raise SystemExit(
                f"the partitioner's mesh is {dict(self.part.mesh.shape)}, "
                f"the configuration states {self.layout['mesh']} "
                f"(OTPU_MULTIHOST=0 makes every partitioner a facade)")
        return {**super().prepare(), "mesh": dict(self.part.mesh.shape)}

    def run(self) -> dict:
        with self.part.session.use():
            return super().run()

    def reference_answer(self, precision: str = "float32",
                         fault: str | None = None) -> dict:
        return self.reference.fit(
            (self._chunk, self.n_chunks), devices=self.part.mesh.devices.flat,
            n_dims=self.n_dims, n_dense=self.est_kw["n_dense"],
            epochs=self.epochs, holdout_chunks=self.holdout_chunks,
            step_size=self.est_kw["step_size"],
            reg_param=self.est_kw["reg_param"], loss=self.est_kw["loss"],
            precision=precision, fault=fault)

    def work(self, peaks: dict) -> dict:
        """The parent's sums, against the aggregate peaks of the chips the
        cell names."""
        return super().work(self.work_fn.aggregate(peaks, self.chips))
