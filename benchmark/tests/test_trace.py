import json
import os

import pytest
from conftest import BENCH

from lib import trace


@pytest.fixture(scope="module")
def small():
    """Cut from PR 24's trace of the train step on the chip: two executions
    of jit_train_step with the first six, one flash and the last three
    operations of each, and the host's two PjitFunction spans."""
    with open(os.path.join(BENCH, "tests", "data", "trace_small.json")) as f:
        return json.load(f)


def test_reduction_gives_the_hand_counted_numbers(small):
    dev = next(p for p in small["planes"] if p["name"].startswith("/device"))
    ops = next(ln for ln in dev["lines"] if ln["name"] == "XLA Ops")["events"]
    mods = next(ln for ln in dev["lines"]
                if ln["name"] == "XLA Modules")["events"]
    every = [e for ln in dev["lines"] for e in ln["events"]]
    t0 = min(s for _, s, _ in every)
    t1 = max(s + d for _, s, d in every)
    got = trace.reduce_trace(small)
    assert got["devices"] == 1
    assert got["window_s"] == pytest.approx((t1 - t0) / 1e9)
    # the kept operations do not overlap, so busy is the sum of their times
    ordered = sorted(ops, key=lambda e: e[1])
    assert all(a[1] + a[2] <= b[1] for a, b in zip(ordered, ordered[1:]))
    assert got["busy_s"] == pytest.approx(sum(d for *_, d in ops) / 1e9)
    assert got["busy_s"] < got["window_s"]
    # programs: two executions of 464.4 ms
    step = got["modules"]["jit_train_step"]
    assert step["count"] == 2
    assert step["durations_s"] == pytest.approx([d / 1e9 for *_, d in mods])
    assert step["durations_s"][0] == pytest.approx(0.4644, abs=1e-4)
    # per operation, by its short name
    flash = [e for e in ops if "tony_flash_fwd" in e[0]]
    assert len(flash) == 2
    t, calls = trace.kernel_time_s(got, "tony_flash_fwd")
    assert calls == 2 and t == pytest.approx(sum(d for *_, d in flash) / 1e9)
    assert got["device_ops"][0][1] >= got["device_ops"][1][1]
    assert sum(got["ops_s"].values()) == pytest.approx(got["busy_s"])
    # idle: everything that is not busy, named by the program that ran next
    idle = sum(s for _, s in got["idle_gaps"])
    assert idle == pytest.approx(got["window_s"] - got["busy_s"])
    assert {n for n, _ in got["idle_gaps"]} <= {"before_jit_train_step",
                                                 "before_end"}
    # every gap here ends inside an execution of the step (the trailing
    # 1.75 us still lie inside the second one's span)
    gaps = dict(got["idle_gaps"])
    assert gaps["before_jit_train_step"] == pytest.approx(
        got["window_s"] - got["busy_s"] - gaps.get("before_end", 0.0))


def test_short_names_and_containers():
    assert trace.short_op("%fusion.12 = bf16[2]{0} fusion(...)") == "fusion.12"
    assert trace.short_module("jit_train_step(978618332)") == "jit_train_step"
    t = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_f(1)", 0.0, 100.0]]},
        {"name": "XLA Ops", "events": [
            ["%while.1 = () while(...)", 0.0, 100.0],
            ["%fusion.1 = f32[] fusion()", 10.0, 30.0],
            ["%fusion.1 = f32[] fusion()", 50.0, 30.0]]}]}]}
    got = trace.reduce_trace(t)
    # the loop only contains the fusions: it is busy time, not an operation
    assert got["busy_s"] == pytest.approx(100e-9)
    assert got["ops_s"] == {"fusion.1": pytest.approx(60e-9)}
    assert got["op_count"] == {"fusion.1": 2}


def test_an_empty_trace_reads_nothing():
    assert trace.reduce_trace({"planes": []})["busy_s"] == 0.0
    assert trace.union_length([(0, 2), (1, 3), (5, 6)])[0] == 4
