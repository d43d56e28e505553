"""The `lfm2_moe` family (conv and attention layers, dense and expert MLPs,
a cache by layer kind with a conv state) through the harness: rehearsed on
the CPU at the tiny size of tests/data/configs/lfm2-tiny-serve.json to
`correct: true`, a broken timed path and the program's int8 K/V cache
told, its counters on the replica's /v1/metrics, and its counts against a
hand count at the served size."""

import json
import os
import subprocess
import sys

import pytest
from conftest import BENCH, REHEARSE, rehearsal_line

from lib import spec


@pytest.fixture(scope="module")
def rehearse(tmp_path_factory):
    """A rehearsal BENCHMARK file with one more configuration and cell,
    written beside nothing that is there."""
    tmp = tmp_path_factory.mktemp("lfm2")
    with open(REHEARSE) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "lfm2-tiny-serve", "source": "none", "reduced": [],
        "file": "benchmark/tests/data/configs/lfm2-tiny-serve.json",
        "why": "conv and attention layers with experts"})
    bench["workloads"].append({
        "name": "lfm2-tiny", "config": "lfm2-tiny-serve",
        "traffic": "lfm2-tiny-open", "chips": 1, "why": "rehearsal"})
    for m in bench["end_to_end"]:
        if m["name"] == "itl_p95_ms":
            m["workloads"].append("lfm2-tiny")
    path = tmp / "BENCHMARK.lfm2.json"
    path.write_text(json.dumps(bench))

    def run(*extra, line=True):
        r = subprocess.run(
            [sys.executable, os.path.join(BENCH, "run.py"), "--rehearse",
             "--benchmark-file", str(path), "--workload", "lfm2-tiny",
             "--seed", "3000000041", "--seconds", "2", "--trace", "0",
             "--out", str(tmp / "out"), *extra],
            env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
            text=True, timeout=900)
        return rehearsal_line(r) if line else r
    run.tmp = tmp
    return run


def _compared(out, name):
    ln = [ln for ln in out.splitlines()
          if ln.startswith(f"compare {name} ")][-1]
    return float(ln.split("value=")[1].split()[0]), "FAIL" in ln


def test_the_family_is_rehearsed_to_correct(rehearse):
    got = rehearse()
    assert got["correct"] is True and got["failed"] == 0, got["stdout"]
    assert got["attempted"] > 0
    assert _compared(got["stdout"], "served_logit_gap")[1] is False
    assert _compared(got["stdout"], "resident_bytes_gap") == (
        pytest.approx(0.0, abs=1e-3), False)
    # what the step counted on the device is on the replica's /v1/metrics
    with open(os.path.join(rehearse.tmp, "out", "client.json")) as f:
        eng = json.load(f)["engine"]
    assert eng["moe_layer_steps_total"] == 6 * eng["decode_steps_total"]
    assert eng["moe_layer_steps_total"] <= eng["moe_experts_hit_total"] \
        <= eng["moe_rows_total"]


def test_a_broken_timed_path_is_not_correct(rehearse):
    got = rehearse("--sabotage", "flip")
    assert got["correct"] is False
    assert _compared(got["stdout"], "served_logit_gap")[1] is True


def test_the_programs_int8_cache_is_told_by_the_replicas_bytes(rehearse):
    """`--control program-int8-cache`: this family's K/V rows take the
    program's int8 form; the served tokens stay inside their limit and
    `resident_bytes_gap` does not."""
    got = rehearse("--control", "program-int8-cache")
    assert got["correct"] is False
    gap, failed = _compared(got["stdout"], "resident_bytes_gap")
    assert failed and gap > 0.008


def test_the_counts_at_the_served_size():
    """benchmark/configs/lfm2-24b-a2b-serve.json against a hand count."""
    path = os.path.join(BENCH, "configs", "lfm2-24b-a2b-serve.json")
    with open(path) as f:
        cfg = json.load(f)
    counts = spec.load_family(path, cfg).counts
    d, f_, fe, v = 2048, 11776, 1536, 65536
    conv, attn = d * 3 * d + d * d, 2 * d * d + 2 * d * 512
    shared = 8 * conv + 2 * attn + 2 * 3 * d * f_ + 8 * d * 64 + d * v
    assert counts.shared_matmul_params(cfg) == shared
    assert counts.expert_params(cfg) == 3 * d * fe
    assert counts.total_params(cfg) == 5_267_090_176
    assert counts.cache_bytes(cfg, 64, 8192) == 2_160_066_560
    # one rider: top-4 experts a layer
    one = counts.decode_step_bytes(cfg, [1000])
    assert one == 2 * shared + 8 * 4 * 2 * 3 * d * fe + 4096 * 1000 \
        + 2 * 8 * 3 * d * 4
    assert counts.expert_layer_flops(cfg, 34) == 2 * 34 * 4 * 3 * d * fe
