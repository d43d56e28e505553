"""A traced decode step's own counts beside its own device time: the
attributes the serving engine puts on its `tony.engine.decode.dispatch`,
`decode.wait` and `emit` spans (serve/engine.py), paired with the
execution of `jit__decode_sample_step` each step ran as.

The engine's counters on `/v1/metrics` are read once, after the drain, so
they are means over ramp, window and drain; every device time comes from a
few seconds of profile inside the window. A roofline divides a count by a
time, and the two must come from the same steps. Since PR 43 a step's
spans carry its counts: `decode.dispatch` has `step` (the iteration),
`riders` and `context_rows` (the K/V rows of the context in flight);
`decode.wait` and `emit` have `lands`, the `step` of the dispatch whose
tokens they read (in the loop the iteration before: one step is in
flight), and `emit` has what the model counted in the step on the device,
under the model's own names (an expert model's `moe_experts_hit` and
`moe_rows`). This file names `step`, `lands`, `riders` and `context_rows`
and no count of a model's: what a family's counts mean is its own
`traced.py`'s to know (benchmark/families/<family>/traced.py).

Pairing. A landed step is a `decode.wait` with `lands` = L whose
`decode.dispatch` (`step` = L) and `emit` (`lands` = L) the profile also
holds. Its program started after that dispatch began and ended before that
wait returned, to within the planes' offset (on a v5e the device plane
read 0-3 ms early; `SLACK_NS` allows 4). Between two dispatches the
device runs nothing but the first's program, so the n-th dispatch in the
profile ran as its (n + shift)-th execution, one shift for the whole
profile (1 where the profile opened between a dispatch and its program,
else 0). Each landed step votes for the shift of the execution inside its
bounds whose end lies nearest its wait's end (`device_get` returns a
fraction of a ms after the program ends), and the most votes win. (Taking
the nearest end step by step is wrong behind an admission or a stalled
loop, where a wait returns long after its program ended and, now and
then, within the slack of the next one's end: on the chip it swapped such
a pair in each of two cells. Taking the earliest free execution is wrong
for a whole profile that opens on a program: a steady loop dispatches
step n+1 about 1 ms after program n starts, well inside the slack.) A
landed step whose execution by that shift lies outside its bounds or
outside the profile, and an execution no landed step took (the profile's
two edges), are dropped and counted. `lands` makes the pairing a fact of the trace: pairing a program
with the spans of the iteration whose dispatch starts nearest
(lib/hostspans.py `clock_offset`) crosses its bounds, because an
iteration's wait lands the step dispatched an iteration earlier.

Two steps, as in lib/trace.py and lib/hostspans.py: hostspans' `load`
turns an `.xplane.pb` into plain lists (needs jax's ProfileData, so it
runs in a process of its own, never in the harness) and `reduce` is pure
Python on those lists. A trace here is hostspans' ({"planes": [{"name",
"lines": [{"name", "events"}]}]}), of which this reads a device plane's
`XLA Modules` line and a host line's three kinds of span. The
device time of a step's stages (a kernel, a named scope) comes from
lib/stages.py's `group`, which lists the executions of a program in the
order they ran: the k-th there is the k-th here, checked by duration.

`reduce` gives `steps`, one record a paired step in the order they ran:
`step`, `device_ms`, `stages` ({stage: seconds} of that execution) and
every attribute its `decode.dispatch` and its `emit` carry beside `step`
and `lands` (`riders`, `context_rows`, the model's counts); and
`executions` (of the decode program in the profile), `paired`, `dropped`
(landed steps not paired), `riders_mean`, `clock_offset_ms` (the least
`wait.end - program.end` over the paired steps: the tight upper bound on
what to add to the device plane's times) and `clock_offset_low_ms` (the
greatest `dispatch.start - program.start`: the lower bound).

  python benchmark/lib/stepspans.py <dir-or-xplane.pb> <out.json> [stages.json]

prints one line, `steps_traced {...}`, with `offset_used_ms` of the
`host_spans.json` beside `<out.json>` next to the offset measured here. A
metric reader calls `of_run(run)`: the first call runs this file on the
run's profile and writes `step_spans.json` under the run's `--out`, later
calls read it. A program without the attributes (the parent of PR 43)
pairs nothing, and every reader returns None.
"""

from __future__ import annotations

import bisect
import json
import os
import subprocess
import sys
from collections import Counter

if __name__ == "__main__":      # run as a file: `lib` is the package
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from lib import hostspans, peaks, readers, spec, stages  # noqa: E402
from lib.trace import DEVICE_PLANE, MODULES_LINE, short_module  # noqa: E402

DISPATCH, WAIT = hostspans.DISPATCH, hostspans.WAIT
EMIT = "tony.engine.emit"
OUT_NAME = "step_spans.json"
SLACK_NS = 4e6
# what the spans say to pair a step by, not a count of the step's own
PAIRED_BY = ("step", "lands")


def _engine_line(trace: dict) -> list:
    """The events of the host line that lands the most steps."""
    best, count = [], 0
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            n = sum(1 for e in line["events"]
                    if e[0] == WAIT and "lands" in e[3])
            if n > count:
                best, count = line["events"], n
    return best


def reduce(trace: dict, staged: list | None = None) -> dict:
    """`staged` is lib/stages.py `group`'s list for the decode program
    (absent: no stage times)."""
    programs = []
    for plane in trace["planes"]:
        if DEVICE_PLANE.match(plane["name"]):   # one replica: one chip
            programs = sorted(
                (s, s + d, d) for ln in plane["lines"]
                if ln["name"] == MODULES_LINE for n, s, d in ln["events"]
                if short_module(n) == readers.DECODE_PROGRAM)
            break
    if staged is None or len(staged) != len(programs) or any(
            abs(g["dur_s"] - d / 1e9) > 1e-9
            for g, (_, _, d) in zip(staged, programs)):
        staged = None
    dispatched, booked, waits = {}, {}, []
    for name, s, d, stats in _engine_line(trace):
        if name == DISPATCH and "riders" in stats:
            dispatched[stats["step"]] = (s, stats)
        elif name == EMIT and "lands" in stats:
            booked[stats["lands"]] = stats
        elif name == WAIT and "lands" in stats:
            waits.append((s + d, stats["lands"]))
    ends = [e for _, e, _ in programs]
    # the executions each landed step's own spans allow it; the n-th
    # dispatch ran as the (n + shift)-th execution, by the shift that most
    # steps' nearest end votes for
    nth = {step: j for j, step in enumerate(
        sorted(dispatched, key=lambda step: dispatched[step][0]))}
    allowed, shifts, dropped = {}, Counter(), 0
    for wait_end, lands in waits:
        if lands not in dispatched or lands not in booked:
            dropped += 1
            continue
        began = dispatched[lands][0]
        k = bisect.bisect_right(ends, wait_end + SLACK_NS) - 1
        inside = set()
        while k >= 0 and programs[k][0] >= began - SLACK_NS:
            inside.add(k)
            k -= 1
        allowed[lands] = (wait_end, inside)
        if inside:
            nearest = min(inside, key=lambda k: abs(wait_end - ends[k]))
            shifts[nearest - nth[lands]] += 1
    shift = max(shifts, key=lambda c: (shifts[c], -abs(c)), default=0)
    steps = []
    high, low = float("inf"), float("-inf")
    for lands, (wait_end, inside) in sorted(allowed.items(),
                                            key=lambda kv: kv[1][0]):
        k = nth[lands] + shift
        if k not in inside:
            dropped += 1
            continue
        began, counts = dispatched[lands]
        start, end, dur = programs[k]
        high, low = min(high, wait_end - end), max(low, began - start)
        step = {key: n for key, n in {**counts, **booked[lands]}.items()
                if key not in PAIRED_BY}
        step.update(step=lands, device_ms=dur / 1e6,
                    stages={} if staged is None else {
                        name: secs for name, (_, secs)
                        in staged[k]["stages"].items()})
        steps.append(step)
    return {
        "executions": len(programs), "paired": len(steps),
        "dropped": dropped,
        "riders_mean": (sum(s["riders"] for s in steps) / len(steps)
                        if steps else None),
        "clock_offset_ms": high / 1e6 if steps else None,
        "clock_offset_low_ms": low / 1e6 if steps else None,
        "steps": steps}


def summary_line(r: dict, offset_used_ms=None) -> str:
    """The one line a run prints beside hostspans' `idle_by_span`."""
    keep = {k: v for k, v in r.items() if k != "steps"}
    keep["offset_used_ms"] = offset_used_ms
    return "steps_traced " + json.dumps(keep)


def of_run(run):
    """The run's reduced step spans, or None where the run has no
    profile, the profile no step that carries its counts (a program
    without them), or no device plane (the CPU rehearsal). The first call
    makes `step_spans.json` in a process of its own and prints its
    line."""
    if hasattr(run, "step_spans"):
        return run.step_spans
    run.step_spans = None
    out = os.path.join(run.out_dir, OUT_NAME)
    if not os.path.exists(out):
        src = hostspans.find_profile(run.out_dir)
        if src is None:
            return None
        hostspans.of_run(run)       # its `offset_used_ms`, for the line
        argv = [sys.executable, os.path.abspath(__file__), src, out]
        family = getattr(run, "family", None)
        if family and os.path.exists(os.path.join(family.directory,
                                                  "stages.py")):
            # what the family's own readers reduce, and keep on the run
            if stages.of(run, stages.family_stages(run).STAGES):
                argv.append(os.path.join(run.out_dir, "stages.json"))
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = subprocess.run(argv, env=env, capture_output=True, text=True,
                           timeout=600)
        if r.returncode != 0 or not os.path.exists(out):
            print(f"step spans not read: {r.stderr[-2000:]}", flush=True)
            return None
        print(r.stdout.strip(), flush=True)
    with open(out, encoding="utf-8") as f:
        got = json.load(f)
    if got.get("paired"):
        run.step_spans = got
    return run.step_spans


# -- what the per-layer readers (benchmark/metrics/*.traced.py) make of it --

def paired_steps(run):
    """The paired steps of a chip's profile; None without."""
    got = of_run(run) if readers.on_chip(run) else None
    return got["steps"] if got else None


def batch_per_step(run):
    got = of_run(run) if readers.on_chip(run) else None
    return got["riders_mean"] if got else None


def family_traced(run):
    """The `traced.py` of the run's family (what its readers make of the
    counts its model puts on a step's spans), loaded once a run; None for
    a family whose model counts nothing of its own."""
    if not hasattr(run, "_family_traced"):
        path = os.path.join(run.family.directory, "traced.py")
        run._family_traced = spec._load_module(
            f"benchmark_family_{run.family.name}_traced", path) \
            if os.path.exists(path) else None
    return run._family_traced


def family_reader(run, name: str):
    """What the family's `traced.py` reads as `name` over the paired
    steps; None where nothing paired or the family has no such reader."""
    steps = paired_steps(run)
    read = getattr(family_traced(run), name, None) if steps else None
    return read(run, steps) if read else None


def decode_hbm_pct(run):
    """Bytes each paired step must read, by the family's
    `counts.decode_step_bytes` for that step's own `riders`, each at the
    step's mean context (`context_rows` / `riders`: the spans say the sum,
    and a count is per slot where a family's bytes are not linear in the
    context, as a recurrent state a rider or a cap on the rows attended
    to) and, where the family's `traced.py` has `step_bytes_kw`, what that
    makes of the step's own counts; summed, over the steps' summed device
    time, as a share of the chip's peak memory bandwidth."""
    steps = paired_steps(run)
    if not steps:
        return None
    kw_of = getattr(family_traced(run), "step_bytes_kw", None)
    need = sum(run.family.counts.decode_step_bytes(
        run.config, [s["context_rows"] / s["riders"]] * s["riders"],
        **(kw_of(run, s) if kw_of else {})) for s in steps)
    took_s = sum(s["device_ms"] for s in steps) / 1e3
    peak = peaks.peaks_of(run.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * need / took_s / peak


def main(argv) -> int:
    src, dst, *staged = argv
    path = hostspans.find_profile(src)
    if path is None:
        print(f"no .xplane.pb under {src}", file=sys.stderr)
        return 1
    by_program = {}
    if staged:
        with open(staged[0], encoding="utf-8") as f:
            by_program = json.load(f)
    reduced = reduce(hostspans.load(path),
                     by_program.get(readers.DECODE_PROGRAM))
    with open(dst, "w", encoding="utf-8") as f:
        json.dump(reduced, f)
    used = None
    beside = os.path.join(os.path.dirname(os.path.abspath(dst)),
                          hostspans.OUT_NAME)
    if os.path.exists(beside):
        with open(beside, encoding="utf-8") as f:
            used = json.load(f).get("clock_check", {}).get("offset_used_ms")
    print(summary_line(reduced, used), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
