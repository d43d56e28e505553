"""The whole harness on the CPU at tiny sizes, through client -> AM ->
executor, skipping only the look for a chip (--rehearse): a sound run comes
out correct, the lower-precision control does not, and a timed path broken
underneath does not. Each run takes some tens of seconds."""

import json
import os
import subprocess
import sys

import pytest
from conftest import BENCH, rehearse

TINY = os.path.join(BENCH, "tests", "data")


@pytest.mark.parametrize("workload, trace", [
    ("train-tiny", 0), ("chat-tiny", 0), ("train-tiny", 1), ("chat-tiny", 1)])
def test_a_sound_run_is_correct_and_prints_no_device_metric(workload, trace,
                                                            tmp_path):
    got = rehearse(workload, "--trace", str(trace), seed=3000000041,
                   out=str(tmp_path / "out"))
    assert got["correct"] is True and got["failed"] == 0
    assert got["attempted"] > 0
    assert "facts" not in got["stdout"] and "tokens/s" not in got["stdout"]
    assert "compare compiles_in_window value=0" in got["stdout"]
    # the readers that find something to read off a chip, and no other
    want = {("train-tiny", 0): ["setup_s", "train_tokens_per_s"],
            ("chat-tiny", 0): ["itl_p95_ms", "setup_s"],
            ("train-tiny", 1): ["compile_s.train", "input_stall_ms",
                                "launch_s"],
            ("chat-tiny", 1): ["client_ttft_p90_ms", "frontend_ms",
                               "launch_s", "loadgen_late_p95_ms"]}
    assert got["reported"] == want[workload, trace]
    # logs and records sit under the one output directory
    kept = {f for _, _, files in os.walk(tmp_path / "out") for f in files}
    want = ({"worker_record.json", "worker_0_s0.stdout"}
            if workload == "train-tiny"
            else {"client.json", "serving_0_s0.stderr", "check_sample.json"})
    assert want <= kept, kept


def test_a_step_that_returns_its_state_unchanged_is_not_correct():
    got = rehearse("train-tiny", "--sabotage", "noop")
    assert got["correct"] is False
    assert "compare grad_norm_gap" in got["stdout"]
    assert "FAIL" in got["stdout"]


def test_a_token_altered_where_it_is_sampled_is_not_correct():
    got = rehearse("chat-tiny", "--sabotage", "flip")
    assert got["correct"] is False and got["failed"] == 0
    assert [ln for ln in got["stdout"].splitlines()
            if ln.startswith("compare served_logit_gap")
            and "FAIL" in ln]


@pytest.mark.parametrize("control, told_by", [
    ("program-int8-cache", "resident_bytes_gap"),
    ("int8", "served_logit_gap")])
def test_the_int8_controls_of_a_serving_cell_are_not_correct(control,
                                                             told_by):
    """`program-int8-cache`: the replica on the program's own int8 K/V
    cache, told by its device bytes; `int8`: the reference in int8 in the
    program's place at the check, told by the served tokens' logits."""
    got = rehearse("chat-tiny", "--control", control, seed=13)
    assert got["correct"] is False and got["failed"] == 0
    failed = [ln.split()[1] for ln in got["stdout"].splitlines()
              if ln.startswith("compare ") and "FAIL" in ln]
    assert failed == [told_by]


def test_a_window_that_is_not_whole_periods_is_refused():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--rehearse",
         "--benchmark-file", os.path.join(TINY, "BENCHMARK.rehearse.json"),
         "--workload", "chat-tiny", "--seed", "1", "--seconds", "6",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=120)
    assert r.returncode not in (0, 3)
    assert "whole periods" in r.stderr and "rehearsal" not in r.stdout


def test_the_float8_control_of_the_training_cell_is_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "lib", "check.py"),
         "control-train", "--rehearse", "--config",
         os.path.join(TINY, "configs", "tiny-train.json"), "--traffic",
         os.path.join(TINY, "traffic", "tiny-train.json"), "--seeds",
         "1,2147483777,3999999979"],
        env=env, capture_output=True, text=True, timeout=600)
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("CONTROL ")]
    assert line, r.stdout + r.stderr[-2000:]
    assert json.loads(line[0][8:])["correct"] == [False, False, False]


def test_without_a_tpu_the_command_exits_non_zero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "train-4k", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
