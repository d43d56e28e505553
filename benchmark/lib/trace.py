"""Reduction of a profiler trace to the numbers the per-layer metrics read.

Two steps, so that the arithmetic can be checked on a small recorded trace
without a profiler: `load_xplane` turns an `.xplane.pb` into plain lists
(needs jax's ProfileData, so it runs in a process of its own, never in the
harness), and `reduce_trace` is pure Python on those lists.

A trace here is {"planes": [{"name": str, "lines": [{"name": str,
"events": [[name, start_ns, duration_ns], ...]}]}]}.

On a TPU the device planes are named `/device:TPU:<n>`; their line
`XLA Ops` holds one event per operation that ran, `XLA Modules` one per
executed program (`jit_<function>(<hash>)`).
"""

from __future__ import annotations

import json
import re
import sys
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
# operations that only contain others: their time is their children's
CONTAINERS = ("while", "conditional", "call")
TOP_N = 10


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            if DEVICE_PLANE.match(plane.name) and line.name not in (
                    OPS_LINE, MODULES_LINE):
                continue
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def short_op(name: str) -> str:
    """'%fusion.12 = bf16[...] fusion(...)' -> 'fusion.12'."""
    return name.split(" = ", 1)[0].lstrip("%")


def short_module(name: str) -> str:
    """'jit_train_step(978618...)' -> 'jit_train_step'."""
    return name.split("(", 1)[0]


def _is_container(name: str) -> bool:
    base = short_op(name).split(".", 1)[0]
    return base in CONTAINERS


def union_length(intervals) -> tuple:
    """(covered length, merged intervals) of [(start, end), ...]."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def reduce_trace(trace: dict) -> dict:
    """Busy and idle time, per-program and per-operation device time, and
    the idle gaps named by the program that ended them. Seconds
    throughout; `busy_s` is averaged over the device planes, `window_s`
    runs from the device's first traced event to its last."""
    devices = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    # the window is what the device planes span: the host goes on writing
    # events while the profiler stops, long after the device's last one
    starts, ends = [], []
    for plane in devices:
        for line in plane["lines"]:
            for _, s, d in line["events"]:
                starts.append(s)
                ends.append(s + d)
    if not starts:
        return {"window_s": 0.0, "busy_s": 0.0, "devices": 0, "modules": {},
                "ops_s": {}, "op_count": {}, "device_ops": [],
                "idle_gaps": []}
    t0, t1 = min(starts), max(ends)
    busy, ops, modules = [], defaultdict(float), {}
    op_count = defaultdict(int)
    gaps = defaultdict(float)
    for plane in devices:
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        op_events = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        length, merged = union_length(
            (s, s + d) for _, s, d in op_events)
        busy.append(length)
        for name, _, d in lines.get(OPS_LINE, []):
            if not _is_container(name):
                ops[short_op(name)] += d
                op_count[short_op(name)] += 1
        mods = sorted(lines.get(MODULES_LINE, []), key=lambda e: e[1])
        for name, s, d in mods:
            m = modules.setdefault(short_module(name),
                                   {"count": 0, "total_s": 0.0,
                                    "durations_s": []})
            m["count"] += 1
            m["total_s"] += d / 1e9
            m["durations_s"].append(d / 1e9)
        # idle gaps, each named by the program whose operation ended it
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        spans = [(s, s + d, short_module(n)) for n, s, d in mods]
        k = 0
        for i in range(0, len(edges), 2):
            g0, g1 = edges[i], edges[i + 1]
            if g1 <= g0:
                continue
            # the first program that has not ended when the gap ends
            while k < len(spans) and spans[k][1] < g1:
                k += 1
            nxt = spans[k][2] if k < len(spans) else "end"
            gaps["before_" + nxt] += g1 - g0
    n = max(1, len(devices))

    def top(d, scale):
        return [[k, v / scale] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP_N]]

    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "devices": len(devices),
        "modules": modules,
        "ops_s": {k: v / 1e9 / n for k, v in ops.items()},
        "op_count": dict(op_count),
        "device_ops": top(ops, 1e9 * n),
        "idle_gaps": top(gaps, 1e9 * n),
    }


def kernel_time_s(reduced: dict, prefix: str) -> tuple:
    """(seconds, calls) of the operations whose name starts with `prefix`
    (a Pallas kernel's stable name, e.g. 'tony_flash_fwd')."""
    t = sum(v for k, v in reduced.get("ops_s", {}).items()
            if k.startswith(prefix))
    c = sum(v for k, v in reduced.get("op_count", {}).items()
            if k.startswith(prefix))
    return t, c


def main(argv) -> int:
    """python benchmark/lib/trace.py <dir-or-xplane.pb> <out.json>"""
    import glob
    import os
    src, dst = argv
    if os.path.isdir(src):
        found = sorted(glob.glob(os.path.join(src, "**", "*.xplane.pb"),
                                 recursive=True))
        if not found:
            print(f"no .xplane.pb under {src}", file=sys.stderr)
            return 1
        src = found[-1]
    with open(dst, "w", encoding="utf-8") as f:
        json.dump(reduce_trace(load_xplane(src)), f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
