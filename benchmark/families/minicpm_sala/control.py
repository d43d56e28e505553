#!/usr/bin/env python3
"""A control of the `minicpm_sala` family that needs a replica of its own:
the lightning layers' state kept in bfloat16 where the configuration
states float32 (the program's `state_dtype`). It moves no served logit by
more than the limit allows; the replica's device bytes tell it, and
`correct` must come out false by `resident_bytes_gap`.

  python3 benchmark/families/minicpm_sala/control.py --out DIR \\
      [--benchmark-file BENCHMARK.json] [--workload sala-longdoc]

writes DIR/BENCHMARK.json, DIR/configs/<config>.json (the cell's
configuration with `run.program.state_dtype` = "bfloat16") and
DIR/traffic/<traffic>.json (a copy), and prints the command that runs the
cell on them: benchmark/run.py with `--benchmark-file DIR/BENCHMARK.json`,
the harness as it is. No jax here.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def write(benchmark_file: str, workload: str, out: str) -> str:
    with open(benchmark_file, encoding="utf-8") as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    src = os.path.join(ROOT, conf["file"])
    with open(src, encoding="utf-8") as f:
        cfg = json.load(f)
    cfg["run"].setdefault("program", {})["state_dtype"] = "bfloat16"
    out = os.path.abspath(out)
    for sub in ("configs", "traffic"):
        os.makedirs(os.path.join(out, sub), exist_ok=True)
    conf["file"] = os.path.join(out, "configs", os.path.basename(src))
    with open(conf["file"], "w", encoding="utf-8") as f:
        json.dump(cfg, f, indent=1)
    mix = cell["traffic"] + ".json"
    shutil.copy(os.path.join(os.path.dirname(os.path.dirname(src)),
                             "traffic", mix),
                os.path.join(out, "traffic", mix))
    path = os.path.join(out, "BENCHMARK.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(bench, f, indent=1)
    return path


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--out", required=True)
    p.add_argument("--benchmark-file",
                   default=os.path.join(ROOT, "BENCHMARK.json"))
    p.add_argument("--workload", default="sala-longdoc")
    args = p.parse_args()
    path = write(args.benchmark_file, args.workload, args.out)
    print(f"python3 benchmark/run.py --benchmark-file {path} --workload "
          f"{args.workload} --seed <n> --seconds <s> --trace 0")
    return 0


if __name__ == "__main__":
    sys.exit(main())
