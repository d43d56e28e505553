"""A later PR adds a configuration, a traffic mix, a cell, a per-layer
metric and the family of an architecture that is not Llama's as new files
and new entries only. Shown on a temporary copy of the benchmark: nothing
that is there is edited, and the harness finds all five by the names in
BENCHMARK.json and in the configuration files."""

import hashlib
import json
import os
import shutil
import subprocess
import sys
import types

import pytest
from conftest import BENCH, ROOT, rehearsal_line

THROWAWAY = os.path.join(BENCH, "tests", "data", "throwaway")


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


@pytest.fixture(scope="module")
def with_a_family(tmp_path_factory):
    """A copy of the benchmark beside the program, with the throwaway
    family `moe_tiny` (the program's models/moe.py: a router and experts
    where Llama's block has one MLP), a serving and a training
    configuration of it, and a cell for each, all as new files. Returns
    a function that rehearses one of the cells in the copy."""
    copy = tmp_path_factory.mktemp("family") / "checkout"
    shutil.copytree(BENCH, copy / "benchmark", ignore=shutil.ignore_patterns(
        "__pycache__", "throwaway"))
    os.symlink(os.path.join(ROOT, "tony_tpu"), copy / "tony_tpu")
    before = _digests(copy / "benchmark")
    data = copy / "benchmark/tests/data"
    shutil.copytree(os.path.join(THROWAWAY, "families"),
                    copy / "benchmark/families", dirs_exist_ok=True)
    shutil.copytree(os.path.join(THROWAWAY, "configs"), data / "configs",
                    dirs_exist_ok=True)
    with open(data / "BENCHMARK.rehearse.json") as f:
        bench = json.load(f)
    for kind, traffic, metric in (
            ("train", "tiny-train", "train_tokens_per_s"),
            ("serve", "tiny-open", "itl_p95_ms")):
        bench["configs"].append({
            "name": f"moe-tiny-{kind}", "source": "none", "reduced": [],
            "file": f"benchmark/tests/data/configs/moe-tiny-{kind}.json",
            "why": "a family that is not Llama's"})
        bench["workloads"].append({
            "name": f"{kind}-moe", "config": f"moe-tiny-{kind}",
            "traffic": traffic, "chips": 1, "why": "rehearsal"})
        for m in bench["end_to_end"]:
            if m["name"] == metric:
                m["workloads"].append(f"{kind}-moe")
    with open(data / "BENCHMARK.family.json", "w") as f:
        json.dump(bench, f)
    after = _digests(copy / "benchmark")
    assert {k: after[k] for k in before} == before
    assert sorted(set(after) - set(before)) == [
        "families/moe_tiny/counts.py", "families/moe_tiny/program.py",
        "families/moe_tiny/reference.py",
        "tests/data/BENCHMARK.family.json",
        "tests/data/configs/moe-tiny-serve.json",
        "tests/data/configs/moe-tiny-train.json"]

    def rehearse(workload, *extra):
        r = subprocess.run(
            [sys.executable, str(copy / "benchmark/run.py"), "--rehearse",
             "--benchmark-file", str(data / "BENCHMARK.family.json"),
             "--workload", workload, "--seed", "3000000041", "--seconds",
             "4", "--trace", "0", "--out", str(copy / "out" / workload),
             *extra], env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=600)
        return dict(rehearsal_line(r), out=str(copy / "out" / workload))
    return rehearse


@pytest.mark.parametrize("workload, compared", [
    ("train-moe", "grad_proj_gap"), ("serve-moe", "served_logit_gap")])
def test_a_family_that_is_not_llamas_runs_on_new_files_alone(
        with_a_family, workload, compared):
    got = with_a_family(workload)
    assert got["correct"] is True and got["failed"] == 0, got["stdout"]
    assert got["attempted"] > 0
    assert f"compare {compared} " in got["stdout"]
    # each number compared is also among the last lines on standard error
    last = [ln.split()[:2] for ln in got["stderr"].splitlines()[-12:]]
    assert ["compared", compared] in last and last[-1] == ["compared",
                                                           "failed"]
    if workload == "train-moe":     # the program trained a router
        with open(os.path.join(got["out"], "worker",
                               "worker_record.json")) as f:
            assert "layers.router" in json.load(f)["grad_norms"]


@pytest.mark.parametrize("workload, sabotage, told_by", [
    ("train-moe", "noop", "grad_norm_gap"),
    ("serve-moe", "flip", "served_logit_gap")])
def test_a_broken_timed_path_of_that_family_is_not_correct(
        with_a_family, workload, sabotage, told_by):
    got = with_a_family(workload, "--sabotage", sabotage)
    assert got["correct"] is False
    assert [ln for ln in got["stdout"].splitlines()
            if ln.startswith(f"compare {told_by} ") and "FAIL" in ln]


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
