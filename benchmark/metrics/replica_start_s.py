"""Submission: the replica's age when its endpoint came up, from the one
`SERVE_STARTUP` line it prints before `SERVING_UP` (serve/__main__.py):
`process_age_s` (from the operating system's start of the process), else
`total_s` (from main()'s entry). Its parts — runtime_init, load_model,
engine_init, frontend_start — go to the run's log. Moves setup_s."""

import glob
import json
import os

from lib import orchestrate


def read(run):
    found = []
    for path in sorted(glob.glob(os.path.join(run.out_dir, "logs",
                                              "*.stdout"))):
        with open(path, encoding="utf-8", errors="replace") as f:
            found += orchestrate.marked_json(f.read(), "SERVE_STARTUP")
    if not found:
        return None
    line = found[-1]
    print("serve_startup " + json.dumps(line), flush=True)
    return line.get("process_age_s", line.get("total_s"))
