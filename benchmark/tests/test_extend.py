"""A later PR adds a configuration, a traffic mix, a cell and a per-layer
metric as new files and new entries only. Shown on a temporary copy of the
benchmark: nothing that is there is edited, and the harness finds all four
by the names in BENCHMARK.json."""

import hashlib
import json
import os
import shutil
import types

from conftest import BENCH, ROOT


def _digests(root):
    out = {}
    for d, _, files in os.walk(root):
        if "__pycache__" in d:
            continue
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_a_cell_a_config_a_mix_and_a_metric_are_found_as_new_files(tmp_path):
    copy = tmp_path / "checkout"
    shutil.copytree(BENCH, copy / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(copy / "benchmark")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)

    # new files only
    with open(copy / "benchmark/configs/mistral-7b-serve.json") as f:
        cfg = json.load(f)
    cfg["run"]["slots"] = 8
    with open(copy / "benchmark/configs/throwaway-serve.json", "w") as f:
        json.dump(cfg, f)
    with open(copy / "benchmark/traffic/throwaway-open.json", "w") as f:
        json.dump({"kind": "serve-open", "rate_rps": 0.5, "ramp_periods": 1,
                   "drain_s": 60, "period": [[64, 20, 1], [128, 32, 1]]}, f)
    with open(copy / "benchmark/metrics/throwaway_requests.py", "w") as f:
        f.write('"""Harness: requests the window judged."""\n\n\n'
                'def read(run):\n    return float(run.attempted)\n')
    # new entries only
    bench["configs"].append({
        "name": "throwaway-serve", "source": "test",
        "file": "benchmark/configs/throwaway-serve.json",
        "reduced": ["num_hidden_layers"], "why": "test"})
    bench["workloads"].append({
        "name": "throwaway", "config": "throwaway-serve",
        "traffic": "throwaway-open", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "itl_p95_ms":
            pass        # the new cell reports setup_s and its own metric
    bench["per_layer"].append({
        "name": "throwaway_requests", "unit": "requests", "better": "higher",
        "source": "host_clock", "layer": "harness", "moves": "setup_s",
        "workloads": ["throwaway"]})
    with open(copy / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)

    # the copy's own spec module finds them by name
    import importlib.util
    spec_py = copy / "benchmark/lib/spec.py"
    s = importlib.util.spec_from_file_location("copied_spec", spec_py)
    spec = importlib.util.module_from_spec(s)
    s.loader.exec_module(spec)
    assert spec.ROOT == str(copy)
    got = spec.load(str(copy / "BENCHMARK.json"), "throwaway")
    assert got["config"]["run"]["slots"] == 8
    assert got["mix"]["kind"] == "serve-open"
    assert got["mix"]["name"] == "throwaway-open"
    names = [m["name"] for m in
             spec.metrics_for(got["bench"], "per_layer", "throwaway")]
    assert names == ["launch_s", "throwaway_requests"]
    assert [m["name"] for m in spec.metrics_for(
        got["bench"], "end_to_end", "throwaway")] == ["setup_s"]
    read = spec.load_reader(got["metrics_dir"], "throwaway_requests")
    assert read(types.SimpleNamespace(attempted=7)) == 7.0
    # the old cells are as they were, and no file that was there changed
    assert spec.load(str(copy / "BENCHMARK.json"),
                     "chat-steady")["config"]["run"]["slots"] == 32
    after = _digests(copy / "benchmark")
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "configs/throwaway-serve.json", "metrics/throwaway_requests.py",
        "traffic/throwaway-open.json"]


def test_every_metric_of_the_benchmark_has_its_reader_and_every_cell_its_files():
    from lib import runners, spec
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["paths"] == ["benchmark"]
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert callable(spec.load_reader(os.path.join(BENCH, "metrics"),
                                         m["name"]))
        assert m["moves"] in e2e
    for cell in bench["workloads"]:
        got = spec.load(os.path.join(ROOT, "BENCHMARK.json"), cell["name"])
        assert got["mix"]["kind"] in runners.RUNNERS
        names = {m["name"] for m in spec.metrics_for(
            bench, "end_to_end", cell["name"])}
        assert "setup_s" in names and len(names) >= 2
        assert spec.metrics_for(bench, "per_layer", cell["name"])
        for key in got["config"]["reduced"]:
            assert key in got["config"]["changed"]
