"""Device time of named stages, execution by execution of each jitted
program, from a traced serving run's profile. `lib/trace.py` sums a
kernel's time over the whole trace; a metric that is "per decode step" or
"of the admissions the trace holds" needs the operations grouped by the
program execution they ran in, and a stage that is plain XLA operations
(not a Pallas kernel with a name of its own) is only told by the scope its
operations were traced under.

A stage is a name the program gives: a Pallas kernel's (`name=` of its
pallas_call; the operation's own name then starts with it) or a
`jax.named_scope`'s (it is part of the operation's `op_name`, which the
profile carries among the operation's stats). An operation belongs to the
first stage of `names` its name or any of its stats holds.

Two steps, as in lib/trace.py: `load_xplane` needs a reader of the
profile (the protobuf's, which has the metadata; else jax's ProfileData,
which has the names) and so runs in a process of its own (`python lib/stages.py <trace> <out.json>
<name> ...`), `group` is pure Python on plain lists. `of(run, names)` does
both for a run and keeps the result on it. Where there is no trace, or the
profile cannot be read, it returns None and the reader leaves its metric
out. Which stages a program has is its family's to know
(benchmark/families/<family>/stages.py); nothing here names one.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))      # run as a script too

from lib import trace  # noqa: E402


def _events_of_protobuf(path: str):
    """(plane, line, name, start ns, duration ns, texts) of every event,
    read from the profile's protobuf itself: `texts` are the strings of
    the event's metadata (its long name, and stats such as the `op_name`
    the operation was traced under), which jax's ProfileData leaves out."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    for plane in space.planes:
        stat_name = {k: m.name for k, m in plane.stat_metadata.items()}

        def strings(stats):
            for st in stats:
                if st.WhichOneof("value") == "str_value":
                    yield st.str_value
                elif st.WhichOneof("value") == "ref_value":
                    yield stat_name.get(st.ref_value, "")

        texts_of = {}
        for line in plane.lines:
            for e in line.events:
                md = plane.event_metadata[e.metadata_id]
                if e.metadata_id not in texts_of:
                    texts_of[e.metadata_id] = [md.display_name,
                                               *strings(md.stats)]
                yield (plane.name, line.name, md.name,
                       line.timestamp_ns + e.offset_ps / 1e3,
                       e.duration_ps / 1e3,
                       texts_of[e.metadata_id] + list(strings(e.stats)))


def _events_of_profiledata(path: str):
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                yield (plane.name, line.name, e.name, float(e.start_ns),
                       float(e.duration_ns),
                       [v for _, v in e.stats if isinstance(v, str)])


def load_xplane(path: str, names) -> dict:
    """{"planes": [{"name", "modules": [[name, start, dur]], "ops":
    [[stage, start, dur]]}]}: every executed program, and the operations
    that belong to a stage of `names` (the others are dropped)."""
    try:
        events = list(_events_of_protobuf(path))
    except ImportError:         # no protobuf reader: names only
        events = list(_events_of_profiledata(path))
    planes: dict = {}
    for plane, line, name, start, dur, texts in events:
        if not trace.DEVICE_PLANE.match(plane):
            continue
        got = planes.setdefault(plane, {"name": plane, "modules": [],
                                        "ops": []})
        if line == trace.MODULES_LINE:
            got["modules"].append([trace.short_module(name), start, dur])
        elif line == trace.OPS_LINE and not trace._is_container(name):
            stage = stage_of(trace.short_op(name), [name, *texts], names)
            if stage:
                got["ops"].append([stage, start, dur])
    return {"planes": list(planes.values())}


def stage_of(op_name: str, stats, names):
    for name in names:
        if op_name.startswith(name):
            return name
    text = [s for s in stats if isinstance(s, str)]
    for name in names:
        if any(name in s for s in text):
            return name
    return None


def group(loaded: dict) -> dict:
    """{program: [{"dur_s": s, "stages": {stage: [operations, seconds]},
    "ops": [[stage, seconds], ...]}]} over the executions of every
    program, in the order they ran, `ops` in the order the operations
    ran: an operation belongs to the execution whose span holds its
    start."""
    out: dict = {}
    for plane in loaded["planes"]:
        mods = sorted(plane["modules"], key=lambda m: m[1])
        runs = [{"dur_s": d / 1e9, "stages": {}, "ops": []}
                for _, _, d in mods]
        k = 0
        for stage, start, dur in sorted(plane["ops"], key=lambda o: o[1]):
            while k < len(mods) and mods[k][1] + mods[k][2] < start:
                k += 1
            if k == len(mods):
                break
            if mods[k][1] <= start:
                got = runs[k]["stages"].setdefault(stage, [0, 0.0])
                got[0] += 1
                got[1] += dur / 1e9
                runs[k]["ops"].append([stage, dur / 1e9])
        for (name, _, _), r in zip(mods, runs):
            out.setdefault(name, []).append(r)
    return out


def of(run, names) -> dict | None:
    """`group` of the run's profile for the stages `names`; None without
    one. Kept on the run: several readers ask for the same stages."""
    names = tuple(names)
    kept = getattr(run, "_stages", None)
    if kept is not None and kept[0] == names:
        return kept[1]
    src = os.path.join(run.out_dir, "ctl", "trace")
    got = None
    if run.trace and os.path.isdir(src):
        dst = os.path.join(run.out_dir, "stages.json")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = subprocess.run([sys.executable, os.path.abspath(__file__), src,
                            dst, *names], env=env, capture_output=True,
                           text=True, timeout=300)
        if r.returncode == 0 and os.path.exists(dst):
            with open(dst, encoding="utf-8") as f:
                got = json.load(f)
        else:
            run.log(f"stage reduction failed: {r.stderr[-1500:]}")
    run._stages = (names, got)
    return got


def family_stages(run):
    """The `stages.py` of the run's family (which stages its programs
    have, and what its readers make of them), loaded once a run."""
    from lib import spec
    if getattr(run, "_family_stages", None) is None:
        run._family_stages = spec._load_module(
            f"benchmark_family_{run.family.name}_stages",
            os.path.join(run.family.directory, "stages.py"))
    return run._family_stages


def stage_ms_per_step(run, names, stage: str):
    """Device time of `stage` (one of the stages `names`, all reduced in
    one pass over the profile) in one decode step, ms, mean over the traced
    decode steps that ran it; None without a chip's profile."""
    from lib import readers
    steps = (of(run, names) or {}).get(readers.DECODE_PROGRAM)
    if not steps or not readers.on_chip(run):
        return None
    times = [s["stages"][stage][1] for s in steps if stage in s["stages"]]
    return 1e3 * sum(times) / len(times) if times else None


def main(argv) -> int:
    """python benchmark/lib/stages.py <dir-or-xplane.pb> <out.json> <name>..."""
    import glob
    src, dst, *names = argv
    if os.path.isdir(src):
        found = sorted(glob.glob(os.path.join(src, "**", "*.xplane.pb"),
                                 recursive=True))
        if not found:
            print(f"no .xplane.pb under {src}", file=sys.stderr)
            return 1
        src = found[-1]
    with open(dst, "w", encoding="utf-8") as f:
        json.dump(group(load_xplane(src, names)), f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
