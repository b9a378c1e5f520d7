"""One-shot goodput & device-memory viewer — "where did the time and the
HBM go", rendered.

Renders the obs/prof.py attribution surfaces as a readable report: the
five-way wall decomposition as an ASCII bar per stage, the per-epoch
bottleneck classification, and the device-memory ledger table (per-owner
bytes + the largest named entries + the high-water interval and census).

Three input shapes, sniffed automatically:

* a ``RunReport`` JSON (``model.run_report_.to_json(path)``) — renders
  its ``goodput`` + ``device_memory`` sections;
* a deep-capture ``snapshot.json`` (or the capture DIRECTORY holding
  one — ``prof.capture()`` / ``POST /debug/profile`` artifacts);
* no argument: **demo mode** — fit a tiny hashed CTR model in-process
  and render its report (the zero-setup smoke, and the tier-1 test).

Importable: ``run_view(path=None, ...) -> dict`` (the summary the CLI
prints as its one JSON line).

Usage:
    python tools/goodput_view.py [REPORT.json | CAPTURE_DIR]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

_BAR_W = 36


def _bar(frac: float) -> str:
    n = int(round(max(0.0, min(frac, 1.0)) * _BAR_W))
    return "#" * n + "." * (_BAR_W - n)


def ledger_lines(device_memory: dict, *, max_entries: int = 10) -> list:
    """The ONE device-memory-ledger table rendering (shared with
    tools/flight_view.py — a ledger-schema change edits one place):
    per-owner totals, the largest named entries, and the account against
    the allocator: the interval that set each peak and the last census of
    the live arrays (absent from a report written before the ledger took
    marks: rendered without)."""
    dm = device_memory
    lines = [f"device-memory ledger "
             f"(live {dm.get('total_bytes', 0)/1e6:.2f} MB, "
             f"peak {dm.get('peak_bytes', 0)/1e6:.2f} MB)"]
    for owner, nbytes in sorted((dm.get("owners") or {}).items()):
        lines.append(f"  {owner:<20} {nbytes/1e6:10.3f} MB")
    for e in (dm.get("entries") or [])[:max_entries]:
        lines.append(f"    {e['owner']}/{e['name']:<26} "
                     f"{e['bytes']/1e6:10.3f} MB")
    hw = dm.get("high_water") or {}
    if hw:
        lines.append(
            f"  live peak {hw['peak_bytes_in_use']/1e6:.2f} MB set in "
            f"'{hw['span']}' (fit {hw['fit']}): named "
            f"{hw['named_bytes']/1e6:.2f} + unnamed "
            f"{hw['unnamed_bytes']/1e6:.2f} + transient "
            f"{hw['transient_bytes']/1e6:.2f} MB")
    hwt = dm.get("high_water_temp") or {}
    if hwt:
        lines.append(
            f"  temp peak {hwt['peak_bytes_reserved']/1e6:.2f} MB set in "
            f"'{hwt['span']}' (fit {hwt['fit']})")
    census = dm.get("census") or {}
    if census:
        lines.append(
            f"  census at '{census['span']}' (fit {census['fit']}, "
            f"{census['arrays']} arrays): live "
            f"{census['live_bytes']/1e6:.2f} MB, runtime-held "
            f"{census['runtime_held_bytes']/1e6:.2f} MB")
        for g in (census.get("groups") or [])[:max_entries]:
            shape = "x".join(map(str, g["shape"])) or "scalar"
            lines.append(f"    {g['owner']:<18} {g['count']:>4} x "
                         f"{g['dtype']}[{shape}]"
                         f" {g['bytes']/1e6:10.3f} MB")
    return lines


def render(goodput: dict | None, device_memory: dict | None,
           out=sys.stderr) -> None:
    """Print the human-readable report (stderr — stdout carries the one
    summary JSON line, the tools convention)."""
    if goodput:
        print(f"[goodput] wall {goodput.get('wall_s', 0):.3f}s  "
              f"bottleneck: {goodput.get('bottleneck')}", file=out)
        for stage, frac in (goodput.get("fractions") or {}).items():
            secs = (goodput.get("seconds") or {}).get(stage, 0.0)
            print(f"[goodput]   {stage:<15} {_bar(frac)} "
                  f"{100 * frac:5.1f}%  {secs:.3f}s", file=out)
        epochs = goodput.get("epochs") or []
        if epochs:
            print("[goodput] per-epoch bottleneck: "
                  + " ".join(f"e{e['epoch']}={e['bottleneck']}"
                             for e in epochs), file=out)
    else:
        print("[goodput] no goodput section (OTPU_PROF=0 run, or a "
              "pre-prof report)", file=out)
    if device_memory:
        for line in ledger_lines(device_memory):
            print(f"[ledger] {line}", file=out)


def _load(path: str) -> tuple[dict | None, dict | None, str]:
    """(goodput, device_memory, source kind) from any of the three input
    shapes."""
    if os.path.isdir(path):
        snap_path = os.path.join(path, "snapshot.json")
        if not os.path.exists(snap_path):
            raise FileNotFoundError(
                f"{path} is a directory without a snapshot.json — not a "
                f"deep-capture artifact (prof.capture / /debug/profile)")
        path = snap_path
    with open(path) as f:
        d = json.load(f)
    if "prof_schema" in d and "ledger" in d:      # capture snapshot.json
        return d.get("goodput"), d.get("ledger"), "capture"
    # RunReport dict: goodput/device_memory sections (absent under
    # OTPU_PROF=0 — rendered as such, never a crash)
    return d.get("goodput"), d.get("device_memory"), "report"


def _demo_report(session=None, rows: int = 4096) -> dict:
    """Demo mode: a tiny hashed CTR fit, cache-device on, report back."""
    import numpy as np

    from orange3_spark_tpu.core.session import TpuSession
    from orange3_spark_tpu.io.streaming import array_chunk_source
    from orange3_spark_tpu.models.hashed_linear import (
        StreamingHashedLinearEstimator,
    )

    session = session or TpuSession.builder_get_or_create()
    rng = np.random.default_rng(11)
    X = np.concatenate([
        rng.standard_normal((rows, 4)).astype(np.float32),
        rng.integers(0, 500, (rows, 4)).astype(np.float32),
    ], axis=1)
    y = (rng.random(rows) < 0.3).astype(np.float32)
    model = StreamingHashedLinearEstimator(
        n_dims=1 << 12, n_dense=4, n_cat=4, epochs=3, step_size=0.05,
        chunk_rows=512,
    ).fit_stream(array_chunk_source(X, y, chunk_rows=512),
                 session=session, cache_device=True)
    rep = getattr(model, "run_report_", None)
    return rep.to_dict() if rep is not None else {}


def run_view(path: str | None = None, session=None,
             rows: int = 4096) -> dict:
    """Render one goodput/ledger view; returns the summary dict."""
    if path is not None:
        goodput, device_memory, source = _load(path)
    else:
        d = _demo_report(session, rows)
        goodput, device_memory, source = (
            d.get("goodput"), d.get("device_memory"), "demo")
    render(goodput, device_memory)
    fracs = (goodput or {}).get("fractions") or {}
    return {
        "metric": "goodput_view",
        "source": source,
        "bottleneck": (goodput or {}).get("bottleneck"),
        "fractions": fracs,
        "fractions_sum": round(sum(fracs.values()), 4) if fracs else None,
        "ledger_owners": (device_memory or {}).get("owners"),
        "ledger_total_bytes": (device_memory or {}).get("total_bytes"),
        "ledger_peak_bytes": (device_memory or {}).get("peak_bytes"),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("path", nargs="?", default=None,
                    help="RunReport JSON or deep-capture dir/snapshot "
                         "(default: demo fit)")
    ap.add_argument("--rows", type=int, default=4096)
    args = ap.parse_args()
    out = run_view(args.path, rows=args.rows)
    print(json.dumps(out, default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())
