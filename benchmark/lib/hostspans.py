"""What the host was doing while the device sat idle: the program's own
`tony.*` spans (serve/engine.py, serve/frontend.py), read off the host
plane of the same profile whose device planes `lib/trace.py` reduces.

`lib/trace.py` names an idle gap by the device program that ended it,
which says nothing of what the host did in it. The serving engine wraps
every phase of its loop in a `jax.profiler.TraceAnnotation`
(`tony.engine.step` > `reap`, `admit` > `admit.prepare|dispatch|wait|book`,
`decode.prepare|dispatch|wait`, `emit`, `release`; `tony.engine.idle_wait`
between steps; `tony.frontend.write` on the handler threads), so host and
device events lie in one profile. This module lays the device's idle
intervals over those spans, after checking how far the two planes' clocks
agree (`clock_check` below: on a v5e they did not, by ~2 ms).

Two steps, as in `lib/trace.py`: `load` turns an `.xplane.pb` into plain
lists (needs jax's ProfileData, so it runs in a process of its own, never
in the harness) and `reduce` is pure Python on those lists. A trace here
is {"planes": [{"name", "lines": [{"name", "events": [...]}]}]}: a device
plane's events are [name, start_ns, duration_ns] on its `XLA Ops` and
`XLA Modules` lines; a host line keeps only its `tony.*` events, as
[name, start_ns, duration_ns, {attribute: value}].

The engine's thread is the host line that holds the `tony.engine.step`
events (the profiler names a Python thread's line `python`, whatever the
thread is called). On it every instant lies in at most one *leaf*: an
event that contains no other. Time inside an event but outside its
children is reported as `<name>/self` and does not count as attributed.

`reduce` gives, in seconds unless the key says otherwise:

- `window_s`, `busy_s`, `idle_s`: as `lib/trace.py` takes them (union of
  `XLA Ops`; first device event to last);
- `idle_by_span`: device-idle seconds under each leaf (and `/self`) of
  the engine's thread; `unattributed_s`: the idle seconds under none;
- `span_s`, `span_count`: the spans' own time and number in the window;
- `write_overlap_s`: per leaf, its seconds that coincide with a
  `tony.frontend.write` on another thread; `idle_write_overlap_s`: the
  same, of its device-idle seconds only;
- `admissions`: per `tony.engine.admit` inside the window its duration,
  the device-busy time inside it, their difference (the host's share)
  and its phases, in ms;
- `step_host_ms`: per decode step the time from the previous step's
  `decode.wait` ending to this step's `decode.dispatch` ending, less the
  admissions in between: EngineStats' `step_host_s`, from the spans;
- `clock_check`: of the traced decode steps, those whose device program
  (`jit__decode_sample_step`) starts after the start of the same step's
  `decode.dispatch` and ends before the end of its `decode.wait`, with
  the planes' times as the profile gives them (`inside`): the two
  planes are on one clock if (nearly) all do. A program cannot start
  before the call that dispatches it nor end after the wait for it
  returned, so each step bounds the offset to add to the device's times
  from below (`dispatch` start - program start) and from above (`wait`
  end - program end): `offset_low_ms`, `offset_high_ms` are the tightest
  of each over the steps. Where 0 lies between them nothing is shifted;
  where it does not (on a v5e the device plane read ~2 ms early) every
  device time is shifted by the middle of the two (`offset_used_ms`)
  before anything above is computed, and what remains unknown is half
  their distance at each edge of a gap.

The profiler slows the host, so these are for attribution; magnitudes
come from the program's host-clock counters (`/v1/metrics`).

  python benchmark/lib/hostspans.py <dir-or-xplane.pb> <out.json>

prints one line, `idle_by_span {...}`. A metric reader calls
`of_run(run)`: the first call runs this file on the run's profile and
writes `host_spans.json` under the run's `--out`, later calls read it.
"""

from __future__ import annotations

import bisect
import json
import os
import statistics
import subprocess
import sys
from collections import defaultdict

if __name__ == "__main__":      # run as a file: `lib` is the package
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from lib.readers import DECODE_PROGRAM  # noqa: E402
from lib.trace import (  # noqa: E402
    DEVICE_PLANE, MODULES_LINE, OPS_LINE, short_module, union_length,
)

SPAN_PREFIX = "tony."
STEP = "tony.engine.step"
ADMIT = "tony.engine.admit"
DISPATCH, WAIT = "tony.engine.decode.dispatch", "tony.engine.decode.wait"
IDLE_WAIT = "tony.engine.idle_wait"
WRITE = "tony.frontend.write"
OUT_NAME = "host_spans.json"
# a step and its device program are paired by their starts: an offset
# between the planes is far under this, a step's period far over it
PAIR_WITHIN_NS = 20e6


def load(path: str) -> dict:
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if device:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                          for e in line.events]
            else:
                events = [[e.name, float(e.start_ns), float(e.duration_ns),
                           dict(e.stats)]
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _complement(merged, t0, t1) -> list:
    """The intervals of [t0, t1] that `merged` (sorted, disjoint) leaves."""
    out, at = [], t0
    for s, e in merged:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if t1 > at:
        out.append((at, t1))
    return out


def _overlap(a: float, b: float, intervals) -> float:
    """Length of [a, b] inside sorted disjoint `intervals` (a profile has
    tens of thousands: found by bisection, not scanned)."""
    total = 0.0
    i = max(0, bisect.bisect_right(intervals, a, key=lambda iv: iv[0]) - 1)
    while i < len(intervals) and intervals[i][0] < b:
        s, e = intervals[i]
        if e > a:
            total += min(b, e) - max(a, s)
        i += 1
    return total


def _intersect(a, b) -> list:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def segments(events) -> list:
    """The thread's timeline cut so that every piece belongs to the
    deepest event that covers it: [(start, end, key)], where key is the
    event's name for an event with no child and `<name>/self` for what a
    parent keeps outside its children. `events` are [name, start, dur,
    ...] of one thread, which nest and never cross."""
    out, stack = [], []         # stack of [name, end, cursor, had_child]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, end, cursor, had_child = stack.pop()
            if end > cursor:
                out.append((cursor, end,
                            name + "/self" if had_child else name))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for name, start, dur, *_ in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            parent = stack[-1]
            if start > parent[2]:
                out.append((parent[2], start, parent[0] + "/self"))
            parent[3] = True
        stack.append([name, start + dur, start, False])
    close(float("inf"))
    return sorted(out)


def _engine_line(host_lines):
    """The host line that holds the engine loop's step spans."""
    best, count = None, 0
    for line in host_lines:
        n = sum(1 for e in line["events"] if e[0] == STEP)
        if n > count:
            best, count = line, n
    return best


def _median(values):
    return statistics.median(values) if values else None


def clock_offset(steps: dict, decode_runs: list) -> dict:
    """The bounds each traced decode step puts on the offset between the
    planes (see the module's text), and the offset to use. `steps` maps a
    step to its {DISPATCH: (start, end), WAIT: (start, end)}; a step's
    program is the run of `decode_runs` that starts nearest its dispatch."""
    low, high, checked, inside = float("-inf"), float("inf"), 0, 0
    for spans in steps.values():
        if not decode_runs:
            break
        lo, hi = spans[DISPATCH][0], spans[WAIT][1]
        s, e = min(decode_runs, key=lambda r: abs(r[0] - lo))
        if abs(s - lo) > PAIR_WITHIN_NS:
            continue        # a step whose program the profile did not catch
        checked += 1
        inside += lo <= s and e <= hi
        low, high = max(low, lo - s), min(high, hi - e)
    used = 0.0
    if checked and low <= high and not low <= 0.0 <= high:
        used = (low + high) / 2
    return {"steps": checked, "inside": inside,
            "offset_low_ms": low / 1e6 if checked else None,
            "offset_high_ms": high / 1e6 if checked else None,
            "offset_used_ms": used / 1e6}


def reduce(trace: dict) -> dict:
    devices = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    hosts = [ln for p in trace["planes"] if not DEVICE_PLANE.match(p["name"])
             for ln in p["lines"]]
    starts, ends, busy_iv, decode_runs = [], [], [], []
    for plane in devices[:1]:       # the spans are one replica's: one chip
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        for evs in lines.values():
            starts += [e[1] for e in evs]
            ends += [e[1] + e[2] for e in evs]
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        busy_iv = union_length((s, s + d) for _, s, d in ops)[1]
        decode_runs = sorted((s, s + d) for n, s, d in
                             lines.get(MODULES_LINE, [])
                             if short_module(n) == DECODE_PROGRAM)
    engine = _engine_line(hosts)
    if not starts or engine is None:
        return {"window_s": 0.0, "busy_s": 0.0, "idle_s": 0.0,
                "devices": len(devices), "engine_spans": 0,
                "idle_by_span": {}, "unattributed_s": 0.0}
    by_step = defaultdict(dict)
    for name, s, d, stats in engine["events"]:
        if name in (DISPATCH, WAIT) and "step" in stats:
            by_step[stats["step"]][name] = (s, s + d)
    by_step = {k: v for k, v in by_step.items() if len(v) == 2}
    clock = clock_offset(by_step, decode_runs)
    shift = clock["offset_used_ms"] * 1e6
    t0, t1 = min(starts) + shift, max(ends) + shift
    busy_iv = [(s + shift, e + shift) for s, e in busy_iv]
    idle_iv = _complement(busy_iv, t0, t1)
    busy = sum(e - s for s, e in busy_iv)
    idle = sum(e - s for s, e in idle_iv)
    writes = union_length(
        (e[1], e[1] + e[2]) for ln in hosts if ln is not engine
        for e in ln["events"] if e[0] == WRITE)[1]
    idle_writes = _intersect(idle_iv, [tuple(iv) for iv in writes])

    inside = [e for e in engine["events"]
              if e[1] < t1 and e[1] + e[2] > t0]
    idle_by, span_s = defaultdict(float), defaultdict(float)
    wr_by, idle_wr_by = defaultdict(float), defaultdict(float)
    for s, e, key in segments(inside):
        s, e = max(s, t0), min(e, t1)
        if e <= s:
            continue
        span_s[key] += e - s
        idle_by[key] += _overlap(s, e, idle_iv)
        wr_by[key] += _overlap(s, e, writes)
        idle_wr_by[key] += _overlap(s, e, idle_writes)
    counts = defaultdict(int)
    for e in inside:
        counts[e[0]] += 1

    whole = sorted((e for e in inside if e[1] >= t0 and e[1] + e[2] <= t1),
                   key=lambda e: e[1])
    admissions = []
    for name, s, d, stats in whole:
        if name != ADMIT:
            continue
        busy_in = _overlap(s, s + d, busy_iv)
        phases = defaultdict(float)
        for n2, s2, d2, _ in whole:
            if n2.startswith(ADMIT + ".") and s <= s2 and s2 + d2 <= s + d:
                phases[n2[len(ADMIT) + 1:]] += d2 / 1e6
        admissions.append({
            "request_id": stats.get("request_id"),
            "prompt_tokens": stats.get("prompt_tokens"),
            "admit_ms": d / 1e6, "busy_ms": busy_in / 1e6,
            "host_ms": (d - busy_in) / 1e6, "phases_ms": dict(phases)})

    # per decode step: the host's share of the gap, as the counter has it
    steps = sorted(by_step)
    breaks = sorted(e[1] for e in engine["events"] if e[0] == IDLE_WAIT)
    admits = [(e[1], e[1] + e[2]) for e in engine["events"]
              if e[0] == ADMIT]
    host_ms = []
    for prev, this in zip(steps, steps[1:]):
        a = by_step[prev][WAIT][1]
        b = by_step[this][DISPATCH][1]
        if this != prev + 1 or any(a <= x < b for x in breaks):
            continue
        host_ms.append((b - a - sum(e - s for s, e in admits
                                    if a <= s and e <= b)) / 1e6)

    def secs(d):
        return {k: v / 1e9 for k, v in sorted(d.items())}

    leaf_idle = sum(v for k, v in idle_by.items() if not k.endswith("/self"))
    return {
        "window_s": (t1 - t0) / 1e9, "busy_s": busy / 1e9,
        "idle_s": idle / 1e9, "devices": len(devices),
        "engine_spans": len(inside),
        "idle_by_span": secs(idle_by),
        "unattributed_s": (idle - sum(idle_by.values())) / 1e9,
        "idle_in_leaves_s": leaf_idle / 1e9,
        "span_s": secs(span_s), "span_count": dict(sorted(counts.items())),
        "write_overlap_s": secs(wr_by),
        "idle_write_overlap_s": secs(idle_wr_by),
        "admissions": admissions,
        "admit_host_ms_p50": _median([a["host_ms"] for a in admissions]),
        "step_host_ms": {"steps": len(host_ms), "p50": _median(host_ms),
                         "mean": (sum(host_ms) / len(host_ms)
                                  if host_ms else None)},
        "clock_check": clock,
    }


def summary_line(r: dict) -> str:
    """The one line a run prints beside the harness's `split`."""
    keep = ("window_s", "busy_s", "idle_s", "idle_by_span",
            "unattributed_s", "idle_write_overlap_s", "admit_host_ms_p50",
            "step_host_ms", "clock_check")
    return "idle_by_span " + json.dumps({k: r[k] for k in keep if k in r})


def find_profile(src: str):
    """`src` itself, or the newest .xplane.pb under it."""
    import glob
    if not os.path.isdir(src):
        return src
    found = sorted(glob.glob(os.path.join(src, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return found[-1] if found else None


def of_run(run):
    """The run's reduced host spans, or None where the run has no profile
    or the profile no engine spans (a program without them, the CPU
    rehearsal's missing device plane). The first call makes
    `host_spans.json` in a process of its own and prints its line."""
    if hasattr(run, "host_spans"):
        return run.host_spans
    run.host_spans = None
    out = os.path.join(run.out_dir, OUT_NAME)
    if not os.path.exists(out):
        src = find_profile(run.out_dir)
        if src is None:
            return None
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = subprocess.run([sys.executable, os.path.abspath(__file__), src,
                            out], env=env, capture_output=True, text=True,
                           timeout=600)
        if r.returncode != 0 or not os.path.exists(out):
            print(f"host spans not read: {r.stderr[-2000:]}", flush=True)
            return None
        print(r.stdout.strip(), flush=True)
    with open(out, encoding="utf-8") as f:
        got = json.load(f)
    if got.get("engine_spans") and got.get("window_s"):
        run.host_spans = got
    return run.host_spans


def main(argv) -> int:
    src, dst = argv
    path = find_profile(src)
    if path is None:
        print(f"no .xplane.pb under {src}", file=sys.stderr)
        return 1
    reduced = reduce(load(path))
    with open(dst, "w", encoding="utf-8") as f:
        json.dump(reduced, f)
    print(summary_line(reduced), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
