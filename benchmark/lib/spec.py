"""Reading BENCHMARK.json and finding, by name, the files that belong to a
cell: its configuration, its traffic mix and its per-layer metric readers.
Nothing here names a cell, a configuration, a mix or a metric: a later PR
adds any of them as new files and new entries."""

from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load(benchmark_file: str, workload: str) -> dict:
    with open(benchmark_file, encoding="utf-8") as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: no workload {workload!r}; there are "
                         f"{sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config_path = os.path.join(ROOT, conf["file"])
    mix_path = os.path.join(os.path.dirname(os.path.dirname(config_path)),
                            "traffic", cell["traffic"] + ".json")
    with open(config_path, encoding="utf-8") as f:
        config = json.load(f)
    with open(mix_path, encoding="utf-8") as f:
        mix = json.load(f)
    mix["name"] = cell["traffic"]
    return {"bench": bench, "cell": cell, "config": config,
            "config_path": config_path, "mix": mix, "mix_path": mix_path,
            "metrics_dir": os.path.join(
                os.path.dirname(os.path.dirname(config_path)), "metrics")}


def metrics_for(bench: dict, group: str, workload: str) -> list:
    """The metrics of `group` ('end_to_end' or 'per_layer') that apply to
    the cell: those without a `workloads` key, and those that list it."""
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


def load_reader(metrics_dir: str, name: str):
    """The `read(run)` function of benchmark/metrics/<name>.py."""
    path = os.path.join(metrics_dir, name + ".py")
    if not os.path.exists(path):        # a cell outside benchmark/ (tests)
        path = os.path.join(ROOT, "benchmark", "metrics", name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"benchmark: per-layer metric {name!r} has no "
                         f"reader at {path}")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
