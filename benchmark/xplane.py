"""The smallest reduction of a profiler trace (``.xplane.pb``) that the
per-layer metrics need: planes -> device lines -> busy union, time by
operation and by program name, and the idle gaps named by what the host
was doing.

Read with ``jax.profiler.ProfileData`` and nothing else. A device plane is
``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per executed HLO
operation (a ``while`` spans its body's operations, so durations nest) and
its ``XLA Modules`` line one event per executed program, named
``jit_<function>(<fingerprint>)``. Host spans are the benchmark's own
``TraceAnnotation``s, whose names start with ``bench:``, on any thread of
``/host:CPU``. Checked against ``xplane_fixture.textproto`` by
``tests/test_xplane.py``.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench:"
TOP = 10
NAME_CHARS = 160


def find_trace(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _events(line) -> list:
    return sorted(((e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for e in line.events), key=lambda t: (t[0], -t[1]))


def union(intervals: list) -> list:
    """Sorted (start, end) pairs -> their union as disjoint pairs."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def self_times(events: list) -> dict:
    """Seconds by name, each event less the events nested inside it (the
    events of one line nest and never cross)."""
    total: dict = {}
    stack: list = []          # [end, name, self_ns]

    def close():
        end, name, self_ns = stack.pop()
        total[name] = total.get(name, 0.0) + self_ns * 1e-9

    for s, e, name in events:
        while stack and s >= stack[-1][0]:
            close()
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    while stack:
        close()
    return total


def _strip(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def reduce(profile, window: tuple | None = None) -> dict:
    """-> {"devices": n, "window_s", "busy_s" (mean over devices),
    "busy_s_per_device", "ops": {name: self seconds, summed over devices},
    "programs": {name: seconds}, "gaps": {host span name: idle seconds},
    "spans": {bench span name: [seconds of each]}, "device_ops": [[name, s]...], "idle_gaps": [[name, s]...]}.
    ``window``: (start_ns, end_ns) on the trace's clock, or the name of a
    bench span whose first event is the window; None takes the first start
    to the last end of the device operations."""
    dev_planes = [p for p in profile.planes if DEVICE_PLANE.match(p.name)]
    if not dev_planes:
        raise ValueError("the trace holds no /device:TPU:<n> plane, only "
                         f"{[p.name for p in profile.planes]}")
    per_dev = []
    for plane in dev_planes:
        lines = {l.name: l for l in plane.lines}
        ops = _events(lines[OPS_LINE]) if OPS_LINE in lines else []
        mods = _events(lines[MODULES_LINE]) if MODULES_LINE in lines else []
        per_dev.append((ops, mods))
    spans = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name[len(SPAN_PREFIX):]))
    if isinstance(window, str):
        named = sorted(sp for sp in spans if sp[2] == window)
        if not named:
            raise ValueError(f"the trace holds no bench span {window!r}")
        window = named[0][:2]
    if window is None:
        starts = [ev[0][0] for ops, mods in per_dev for ev in (ops, mods)
                  if ev]
        ends = [max(e for _, e, _ in ev) for ops, mods in per_dev
                for ev in (ops, mods) if ev]
        if not starts:
            raise ValueError("no operation ran on the device in the trace")
        window = (min(starts), max(ends))
    w0, w1 = window
    span_s: dict = {}
    for s0, s1, name in spans:
        span_s.setdefault(name, []).append((s1 - s0) * 1e-9)
    busy, ops_s, prog_s, gaps_s = [], {}, {}, {}
    for ops, mods in per_dev:
        base = ops if ops else mods
        merged = union([(max(s, w0), min(e, w1)) for s, e, _ in base
                        if e > w0 and s < w1])
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        for name, sec in self_times(ops).items():
            ops_s[name] = ops_s.get(name, 0.0) + sec
        for s, e, name in mods:
            name = _strip(name)
            prog_s[name] = prog_s.get(name, 0.0) + (e - s) * 1e-9
        edges = [w0] + [t for pair in merged for t in pair] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            mid = (g0 + g1) / 2
            cover = [sp for sp in spans if sp[0] <= mid < sp[1]]
            name = (min(cover, key=lambda sp: sp[1] - sp[0])[2]
                    if cover else "no_bench_span")
            gaps_s[name] = gaps_s.get(name, 0.0) + (g1 - g0) * 1e-9

    def top(d: dict) -> list:
        # a device operation's name is its whole HLO line: the head says
        # what it is (result shape, kind, first operands)
        return [[k[:NAME_CHARS], v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {"lines": {p.name: [l.name for l in p.lines] for p in dev_planes},
            "devices": len(per_dev), "window_s": (w1 - w0) * 1e-9,
            "busy_s": sum(busy) / len(busy), "busy_s_per_device": busy,
            "ops": ops_s, "programs": prog_s, "gaps": gaps_s,
            "spans": span_s,
            "device_ops": top(ops_s), "idle_gaps": top(gaps_s)}
