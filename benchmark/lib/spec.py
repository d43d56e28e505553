"""Reading BENCHMARK.json and finding, by name, the files that belong to a
cell: its configuration, its traffic mix, its per-layer metric readers and
the family of its architecture. Nothing here names a cell, a configuration,
a mix or a metric: a later PR adds any of them as new files and new
entries. The one name it holds is the family of a configuration file that
states none."""

from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_FAMILY = "llama"    # of a configuration file without `family`


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
            "family": load_family(config_path, config),
            "metrics_dir": os.path.join(
                os.path.dirname(os.path.dirname(config_path)), "metrics")}


def metrics_for(bench: dict, group: str, workload: str) -> list:
    """The metrics of `group` ('end_to_end' or 'per_layer') that apply to
    the cell: those without a `workloads` key, and those that list it."""
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


def _load_module(label: str, path: str):
    spec = importlib.util.spec_from_file_location(
        label.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metrics_dir: str, name: str):
    """The `read(run)` function of benchmark/metrics/<name>.py."""
    path = os.path.join(metrics_dir, name + ".py")
    if not os.path.exists(path):        # a cell outside benchmark/ (tests)
        path = os.path.join(ROOT, "benchmark", "metrics", name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"benchmark: per-layer metric {name!r} has no "
                         f"reader at {path}")
    return _load_module("benchmark_metric_" + name, path).read


class Family:
    """What depends on a configuration's architecture, as the three
    modules of benchmark/families/<name>/: `program` (the only one that
    imports the program), `reference` (plain jax.numpy, nothing of the
    program) and `counts` (operations and bytes from shapes, no import at
    all). Each is loaded when it is first asked for: the harness's own
    process asks for `counts` alone and so stays off jax."""

    PARTS = ("program", "reference", "counts")

    def __init__(self, name: str, directory: str):
        self.name, self.directory = name, directory

    def __getattr__(self, part: str):
        if part not in self.PARTS:
            raise AttributeError(part)
        path = os.path.join(self.directory, part + ".py")
        if not os.path.exists(path):
            raise SystemExit(f"benchmark: family {self.name!r} has no "
                             f"{part}.py in {self.directory}")
        mod = _load_module(f"benchmark_family_{self.name}_{part}", path)
        setattr(self, part, mod)
        return mod


def load_family(config_path: str, config: dict) -> Family:
    """The family a configuration file names under `family` (without the
    key, the one that was there before families were). Its directory is
    looked for as a reader's file is: beside the configuration first, then
    under benchmark/."""
    name = config.get("family", DEFAULT_FAMILY)
    beside = os.path.dirname(os.path.dirname(os.path.abspath(config_path)))
    looked = [os.path.join(beside, "families", name),
              os.path.join(ROOT, "benchmark", "families", name)]
    for directory in looked:
        if os.path.isdir(directory):
            return Family(name, directory)
    raise SystemExit(f"benchmark: configuration {config_path} is of family "
                     f"{name!r}, and there is no such directory: looked in "
                     f"{' and '.join(dict.fromkeys(looked))}")
