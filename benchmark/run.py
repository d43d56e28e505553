#!/usr/bin/env python3
"""The benchmark's one command: run one cell of BENCHMARK.json once.

  python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (benchmark/configs/) and a traffic mix
(benchmark/traffic/); the mix's `kind` chooses the runner, and the
configuration's `family` the files that know its architecture
(benchmark/families/). With --trace 0
the last line carries the cell's end-to-end metrics, with --trace 1 its
per-layer metrics (each read by benchmark/metrics/<name>.py), `busy_s` /
`window_s` and the breakdown. This process never imports jax: the launched
worker or replica holds the chip, and the reference runs after it is freed.
Without a TPU it exits non-zero and prints no result.

  --control        serving cells: a lower-precision control, for which
                   `correct` must come out false. `program-int8-cache`
                   launches the replica on the program's own int8 K/V
                   cache (told by its device bytes: it moves no logit by
                   more than bfloat16 rounding does). `int8` runs the
                   program as it is and puts the reference in int8
                   (weights and activations) in its place at the check
                   (told by the served tokens' logits)
  --rehearse       tiny sizes on the CPU (benchmark/tests): prints
                   `platform: cpu`, never a result line, and exits 3
  --benchmark-file another BENCHMARK.json (the rehearsal's, a test's)
  --out            where logs and records go (default benchmark_out/<cell>)
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from lib import orchestrate, runners, spec  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", default="none",
                   choices=("none", "int8", "program-int8-cache"))
    p.add_argument("--sabotage", default="none",
                   help="tests only: break the timed path (noop | flip)")
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--benchmark-file",
                   default=os.path.join(spec.ROOT, "BENCHMARK.json"))
    p.add_argument("--out", default="")
    args = p.parse_args()

    orchestrate.import_program()        # exits 2 without the program
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if not args.rehearse and platforms and "tpu" not in platforms.split(","):
        print(f"benchmark: JAX_PLATFORMS={platforms} holds jax off the TPU; "
              f"run on the chip, or rehearse with --rehearse",
              file=sys.stderr)
        return 2
    s = spec.load(args.benchmark_file, args.workload)
    run = runners.Run(args, s, T_START)
    kind = s["mix"]["kind"]
    if kind not in runners.RUNNERS:
        print(f"benchmark: traffic {s['mix']['name']} has unknown kind "
              f"{kind!r}", file=sys.stderr)
        return 2
    try:
        runners.RUNNERS[kind](run)
    except runners.NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 3
    orchestrate.require_no_jax()

    if run.compiles_in_window is not None:
        run.compare("compiles_in_window", run.compiles_in_window, 0,
                    "compiles and cache loads stamped inside the window")
    run.compare("failed", run.failed, 0,
                f"of {run.attempted} attempted")
    correct = bool(run.compares) and all(c["ok"] for c in run.compares)
    # each number compared beside its limit: the last lines on standard
    # error, and the last key of the result line
    compared = {c["name"]: {
        "value": c["value"] if math.isfinite(c["value"]) else str(c["value"]),
        "limit": c["limit"], "ok": c["ok"]} for c in run.compares}
    if run.device.get("platform") == "tpu":
        print("facts " + json.dumps(run.facts), flush=True)

    cell = run.cell["name"]
    metrics = {}
    if args.trace:
        for m in spec.metrics_for(run.bench, "per_layer", cell):
            value = spec.load_reader(run.metrics_dir, m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in spec.metrics_for(run.bench, "end_to_end", cell):
            if m["name"] in run.facts:
                metrics[m["name"]] = {"value": run.facts[m["name"]],
                                      "unit": m["unit"]}
    device = {"platform": run.device.get("platform"),
              "kind": run.device.get("kind"),
              "count": run.device.get("count"),
              "memory_peak_bytes": run.device.get("memory_peak_bytes"),
              "compiles_in_window": run.compiles_in_window}
    result = {"correct": correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": device}
    if args.trace and run.trace:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {"device_ops": run.trace.get("device_ops", []),
                               "idle_gaps": run.trace.get("idle_gaps", [])}
    result["compared"] = compared
    sys.stdout.flush()
    for name, c in compared.items():
        print(f"compared {name} value={c['value']} limit={c['limit']} "
              f"{'ok' if c['ok'] else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    if device["platform"] != "tpu":
        # a rehearsal: show what a run would print, claim nothing
        print(f"platform: {device['platform']} (a rehearsal: no device "
              f"metric, no result line)\nrehearsal " + json.dumps(
                  {"correct": correct, "attempted": run.attempted,
                   "failed": run.failed, "reported": sorted(metrics)}),
              flush=True)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
