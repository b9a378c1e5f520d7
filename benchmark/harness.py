"""The harness: one cell, one run. Driven by data — a cell is an entry of
``BENCHMARK.json`` naming a configuration (``configs/<config>.json``) and a
traffic mix (``traffic/<traffic>.json``); its limits are in
``cells/<workload>.json``; a job kind is ``jobs/<kind>.py``; a per-layer
metric is ``metrics/<name>.py``; a plain reference is
``reference/<name>.py`` and a work function ``work/<name>.py``, both named
by the configuration. Adding any of them adds files and entries only.

A run: set-up (compile cache on, data made or reused, ONE whole warm job
of the cell's own shapes) -> the measured window (jobs back to back until
``--seconds`` have passed, the job in progress finished) -> memory read ->
the program's state freed -> the plain reference and the comparison ->
one JSON line.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import importlib
import json
import os
import shutil
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: generated data and traces; inside the checkout, listed in .gitignore
DATA_DIR = os.path.join(ROOT, ".bench_data")


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def span(name: str):
    """A host span in the profiler's own trace; next to free when no trace
    is being taken. The trace reduction names idle gaps by these."""
    import jax

    return jax.profiler.TraceAnnotation("bench:" + name)


def read_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_pair(config_name: str, traffic_name: str, rehearse: bool) -> tuple:
    """The configuration and the traffic mix as they are run; a rehearsal
    takes each file's own ``rehearsal`` block over it."""
    config = read_json(HERE, "configs", config_name + ".json")
    traffic = read_json(HERE, "traffic", traffic_name + ".json")
    if rehearse:
        for target in (config, traffic):
            over = target.get("rehearsal", {})
            for k, v in over.items():
                if isinstance(v, dict) and isinstance(target.get(k), dict):
                    target[k].update(v)
                else:
                    target[k] = v
    return config, traffic


def load_bench() -> dict:
    """``BENCHMARK.json`` and, beside its entries, those of the cells that
    are kept out of it: a cell file's own ``entries`` (the ``configs``,
    ``workloads`` and ``per_layer`` entries that would add the cell; PERF.md
    section 7 says why each is out). Such a cell still runs by name, here
    and under ``benchmark/tests``; the driver never asks for it."""
    bench = read_json(ROOT, "BENCHMARK.json")
    bench["put_off"] = []
    for name in sorted(os.listdir(os.path.join(HERE, "cells"))):
        entries = read_json(HERE, "cells", name).get("entries")
        if entries:
            bench["put_off"] += [w["name"] for w in entries["workloads"]]
            for key, more in entries.items():
                bench[key] = bench[key] + more
    return bench


def load_cell(workload: str, rehearse: bool) -> dict:
    bench = load_bench()
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(has: {sorted(cells)})")
    cell = cells[workload]
    config, traffic = load_pair(cell["config"], cell["traffic"], rehearse)
    limits = read_json(HERE, "cells", workload + ".json")["limits"]

    def reports(m: dict) -> bool:
        return "workloads" not in m or workload in m["workloads"]

    return {"cell": cell, "config": config, "traffic": traffic,
            "limits": limits,
            "end_to_end": [m for m in bench["end_to_end"] if reports(m)],
            "per_layer": [m for m in bench["per_layer"] if reports(m)]}


def devices_or_exit(chips: int, rehearse: bool):
    import jax

    devs = jax.devices()
    if rehearse:
        return devs[:chips]
    if devs[0].platform != "tpu" or len(devs) < chips:
        log(f"need {chips} TPU chip(s); jax reports {len(devs)} x "
            f"{devs[0].platform}: no result")
        raise SystemExit(3)
    return devs[:chips]


def memory_peak(devices) -> dict:
    """Peak bytes of the fullest device. On the v5e runtime
    ``peak_bytes_in_use`` is the high-water mark of live buffers and a
    program's temp comes from a reserved region whose own high-water mark
    is ``peak_bytes_reserved`` (PERF.md, PR 22): what a run needed is the
    sum."""
    best = {"memory_peak_bytes": 0}
    for d in devices:
        st = d.memory_stats() or {}
        total = (st.get("peak_bytes_in_use", 0)
                 + st.get("peak_bytes_reserved", 0))
        if total >= best["memory_peak_bytes"]:
            best = {"memory_peak_bytes": int(total),
                    "peak_bytes_in_use": st.get("peak_bytes_in_use"),
                    "peak_bytes_reserved": st.get("peak_bytes_reserved"),
                    "bytes_limit": st.get("bytes_limit")}
    return best


class CompileCounter:
    """Backend compiles (a persistent-cache retrieval is one too: either is
    a program the warm job did not leave in this process)."""

    count = 0

    @classmethod
    def install(cls) -> None:
        import jax

        def on_duration(key: str, _dur: float, **_kw) -> None:
            if key == "/jax/core/compile/backend_compile_duration":
                cls.count += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)


def peaks_for(device_kind: str) -> dict:
    table = read_json(HERE, "peaks.json")
    if device_kind not in table:
        raise SystemExit(f"device kind {device_kind!r} is not in "
                         f"benchmark/peaks.json: add it with its source")
    return table[device_kind]


@contextlib.contextmanager
def tracing(trace_dir: str):
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with span("traced_window"):
            yield
    finally:
        jax.profiler.stop_trace()


def run_window(job, seconds: float, trace_dir: str | None,
               trace_jobs: int) -> dict:
    """Jobs back to back until ``seconds`` have passed; the job in progress
    is finished and counted. With a trace directory, the first
    ``trace_jobs`` jobs run inside the profiler."""
    records, failed, attempted = [], 0, 0
    stack = contextlib.ExitStack()
    t0 = time.perf_counter()
    t_end = t0
    with stack:
        if trace_dir:
            stack.enter_context(tracing(trace_dir))
        while time.perf_counter() - t0 < seconds:
            if trace_dir and attempted == trace_jobs:
                stack.close()
            attempted += 1
            try:
                with span("job"):
                    records.append(job.run())
                t_end = time.perf_counter()
            except Exception:            # a job that fails is counted
                failed += 1
                log(traceback.format_exc())
                if failed >= 2:
                    break
    return {"records": records, "attempted": attempted, "failed": failed,
            "t0": t0, "window_s": t_end - t0,
            "traced_jobs": min(trace_jobs, attempted) if trace_dir else 0}


def judge(numbers: dict, limits: dict) -> tuple:
    """Each number compared beside its limit; a number without a limit or a
    limit without a number is not correct."""
    compared, ok = {}, True
    for name in sorted(set(numbers) | set(limits)):
        value, limit = numbers.get(name), limits.get(name)
        good = (value is not None and limit is not None
                and value == value and value <= limit)
        ok = ok and good
        compared[name] = {"value": value, "limit": limit}
    return compared, ok


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the files' own rehearsal sizes: "
                         "runs every step, prints no device metric")
    args = ap.parse_args(argv)
    spec = load_cell(args.workload, args.rehearse)
    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    seconds = args.seconds if args.seconds is not None else \
        read_json(ROOT, "BENCHMARK.json")["run_seconds"]

    if args.rehearse:       # the program's kernels in interpret mode
        os.environ.setdefault("OTPU_HISTOGRAM_BACKEND", "pallas-interpret")
    import jax

    devices = devices_or_exit(cell["chips"], args.rehearse)
    kind = devices[0].device_kind
    peaks = None if args.rehearse else peaks_for(kind)
    from orange3_spark_tpu.core.session import TpuSession

    cache = TpuSession.enable_compilation_cache()   # before the first jit
    CompileCounter.install()
    job_kind = importlib.import_module(f"benchmark.jobs.{traffic['job']}")
    job = job_kind.Job(config, traffic, args.seed, DATA_DIR)
    prep = job.prepare()
    t_warm = time.perf_counter()
    job.run()                                        # one whole warm job
    setup_s = time.perf_counter() - t_start
    log(json.dumps({"setup": {**prep, "warm_job_s": time.perf_counter()
                              - t_warm, "compile_cache": cache.get("dir"),
                              "compiles": CompileCounter.count}}))

    trace_dir = (os.path.join(DATA_DIR, "trace",
                              f"{args.workload}_s{args.seed}")
                 if args.trace else None)
    c0 = CompileCounter.count
    win = run_window(job, seconds, trace_dir,
                     int(traffic.get("trace_jobs", 1)))
    compiles = CompileCounter.count - c0
    records = win["records"]
    for r in records:
        log(json.dumps({"job": {k: r[k] for k in
                                ("rows", "seconds", "spans", "resolved")}}))
    mem = memory_peak(devices)
    job.take_last()
    gc.collect()

    t_ref = time.perf_counter()
    numbers = job.check([r["answer"] for r in records]) if records else {}
    compared, ok = judge(numbers, spec["limits"])
    ref_s = time.perf_counter() - t_ref
    correct = bool(ok and records and win["failed"] == 0)
    log(json.dumps({"reference_s": ref_s,
                    "reference": getattr(job, "ref_summary", None)}))

    result = {"correct": correct, "attempted": win["attempted"],
              "failed": win["failed"]}
    if args.rehearse:
        result["rehearsal"] = True      # no device metric from a CPU run
    else:
        done_rows = sum(r["rows"] for r in records)
        values = {"setup_s": setup_s}
        if records:
            values["fit_rows_per_s"] = done_rows / win["window_s"]
            values["fit_s_max"] = max(r["seconds"] for r in records)
        device = {"platform": devices[0].platform, "kind": kind,
                  "count": len(devices),
                  "memory_peak_bytes": mem["memory_peak_bytes"]}
        if not args.trace:
            metrics = {m["name"]: {"value": values[m["name"]],
                                   "unit": m["unit"]}
                       for m in spec["end_to_end"] if m["name"] in values}
        else:
            from benchmark import xplane

            t_tr = time.perf_counter()
            reduced = xplane.reduce(
                xplane.load(xplane.find_trace(trace_dir)),
                window="traced_window")
            shutil.rmtree(trace_dir, ignore_errors=True)
            run = {"jobs": records, "trace": reduced,
                   "traced_jobs": win["traced_jobs"],
                   "work": job.work(peaks), "peaks": peaks, "memory": mem,
                   "compiles_in_window": compiles, "config": config,
                   "window_s": win["window_s"]}
            metrics = {}
            for m in spec["per_layer"]:
                reader = importlib.import_module(
                    f"benchmark.metrics.{m['name']}")
                value = reader.read(run)
                if value is not None:        # nothing to read: left out
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            result["breakdown"] = {"device_ops": reduced["device_ops"],
                                   "idle_gaps": reduced["idle_gaps"]}
            log(json.dumps({"trace_reduce_s": time.perf_counter() - t_tr,
                            "programs": reduced["programs"],
                            "lines": reduced["lines"],
                            "work": run["work"]}))
        result.update(metrics=metrics, device=device)
        log(json.dumps({"values": values, "memory": mem,
                        "compiles_in_window": compiles}))
    result["compared"] = compared
    for name, c in compared.items():
        log(f"compared {name} value {c['value']} limit {c['limit']}")
    print(json.dumps(result), flush=True)
    return 0
