"""lib/hostspans.py on a small hand-built trace (tests/data/
hostspans_small.json, times in ns): three engine steps, the second with
an admission, over four device programs; two handler threads writing.

Device: busy [1000,3000) [3500,6000) [8000,10000) [12000,17000)
[19000,24000) = 16500 of a 23000 window, so idle 6500 in four gaps:
[3000,3500) [6000,8000) [10000,12000) [17000,19000)."""

import copy
import json
import os
import types

import pytest
from conftest import BENCH, ROOT

from lib import hostspans, spec

NEW_METRICS = ("step_host_ms", "batch_per_step", "admit_host_ms",
               "idle_attributed_pct", "replica_start_s")
NS = 1e-9


@pytest.fixture(scope="module")
def small():
    with open(os.path.join(BENCH, "tests", "data",
                           "hostspans_small.json")) as f:
        return json.load(f)


def test_idle_seconds_fall_under_the_spans_as_hand_counted(small):
    got = hostspans.reduce(small)
    assert got["window_s"] == pytest.approx(23000 * NS)
    assert got["busy_s"] == pytest.approx(16500 * NS)
    assert got["idle_s"] == pytest.approx(6500 * NS)
    E = "tony.engine."
    assert got["idle_by_span"] == pytest.approx({
        # gap 1 lies wholly in step 7's wait; gap 2: the wait's last 400,
        # the emit's 500, [6900,7000) under no span, 10 + 50 of step 8's
        # own time around its reap of 40, the admission's prepare (700)
        # and the first 200 of its dispatch; gap 3: the last 300 of its
        # wait, its book (700), 100 of the step's own, the decode's
        # prepare (500) and 400 of its dispatch; gap 4: 300 of the wait,
        # the emit (700), [18000,18100) under no span, step 9's reap (50),
        # prepare (550) and 300 of its dispatch
        E + "decode.wait": (500 + 400 + 300) * NS,
        E + "emit": (500 + 700) * NS,
        E + "reap": (40 + 50) * NS,
        E + "step/self": (10 + 50 + 100) * NS,
        E + "admit.prepare": 700 * NS, E + "admit.dispatch": 200 * NS,
        E + "admit.wait": 300 * NS, E + "admit.book": 700 * NS,
        E + "decode.prepare": (500 + 550) * NS,
        E + "decode.dispatch": (400 + 300) * NS})
    assert got["unattributed_s"] == pytest.approx(200 * NS)
    # the parts make the whole: what the acceptance check asks of a run
    assert sum(got["idle_by_span"].values()) + got["unattributed_s"] \
        == pytest.approx(got["window_s"] - got["busy_s"])
    # a parent's own time is named but does not count as attributed
    assert got["idle_in_leaves_s"] == pytest.approx((6500 - 160 - 200) * NS)
    assert got["span_count"][E + "step"] == 3
    assert got["span_count"][E + "admit"] == 1
    # the spans' own time, clipped to the window: step 7's wait from 1000
    assert got["span_s"][E + "decode.wait"] == pytest.approx(
        ((6400 - 1000) + 5200 + (24000 - 19100)) * NS)


def test_an_admissions_host_share_is_its_span_less_the_device_time(small):
    got = hostspans.reduce(small)
    assert len(got["admissions"]) == 1
    adm = got["admissions"][0]
    assert adm["request_id"] == 3 and adm["prompt_tokens"] == 384
    assert adm["admit_ms"] == pytest.approx(3900e-6)
    assert adm["busy_ms"] == pytest.approx(2000e-6)      # [8000,10000)
    assert adm["host_ms"] == pytest.approx(1900e-6)
    assert adm["phases_ms"] == pytest.approx({
        "prepare": 700e-6, "dispatch": 300e-6, "wait": 2200e-6,
        "book": 700e-6})
    assert got["admit_host_ms_p50"] == pytest.approx(1900e-6)


def test_overlap_with_the_handler_threads_writes(small):
    got = hostspans.reduce(small)
    E = "tony.engine."
    # thread 1 writes [6450,6650) (in step 7's emit, device idle),
    # [13000,13400) (in step 8's wait, device busy) and [17200,17500)
    # (100 of the wait, 200 of the emit, idle); thread 2 [7700,7900):
    # 100 of the admission's prepare, 100 of its dispatch, idle
    wr = {k: v for k, v in got["write_overlap_s"].items() if v}
    assert wr == pytest.approx({
        E + "emit": 400 * NS, E + "decode.wait": 500 * NS,
        E + "admit.prepare": 100 * NS, E + "admit.dispatch": 100 * NS})
    idle_wr = {k: v for k, v in got["idle_write_overlap_s"].items() if v}
    assert idle_wr == pytest.approx({
        E + "emit": 400 * NS, E + "decode.wait": 100 * NS,
        E + "admit.prepare": 100 * NS, E + "admit.dispatch": 100 * NS})


def test_step_host_time_and_the_clock_check(small):
    got = hostspans.reduce(small)
    # 7 -> 8: wait ends 6400, dispatch ends 12100, less the admission's
    # 3900; 8 -> 9: wait ends 17300, dispatch ends 19100
    assert got["step_host_ms"] == {"steps": 2, "p50": pytest.approx(1800e-6),
                                   "mean": pytest.approx(1800e-6)}
    # every program runs between its step's dispatch and its wait's end;
    # the steps allow an offset between -300 ns (step 9's program starts
    # 300 after its dispatch does) and +200 (it ends 200 before its wait
    # does): 0 is inside, so nothing is shifted
    assert got["clock_check"] == {
        "steps": 3, "inside": 3, "offset_low_ms": pytest.approx(-300e-6),
        "offset_high_ms": pytest.approx(200e-6), "offset_used_ms": 0.0}


def test_a_device_plane_that_reads_early_is_shifted_by_what_the_steps_allow(
        small):
    """As on the chip: the device's times read 2000 ns early. A program
    then starts before its own dispatch; the steps bound the offset to
    [1700, 2200], and with the middle of that added to the device's times
    the attribution is the true one to within the 250 that stay unknown."""
    early = copy.deepcopy(small)
    for plane in early["planes"]:
        if plane["name"].startswith("/device"):
            for line in plane["lines"]:
                for e in line["events"]:
                    e[1] -= 2000.0
    got = hostspans.reduce(early)
    assert got["clock_check"] == {
        "steps": 3, "inside": 0, "offset_low_ms": pytest.approx(1700e-6),
        "offset_high_ms": pytest.approx(2200e-6),
        "offset_used_ms": pytest.approx(1950e-6)}
    true = hostspans.reduce(small)
    assert got["idle_s"] == pytest.approx(true["idle_s"])
    assert got["window_s"] == pytest.approx(true["window_s"])
    for key, value in true["idle_by_span"].items():
        # a gap's two edges each move by 50 ns; four gaps
        assert got["idle_by_span"][key] == pytest.approx(value, abs=200 * NS)
    assert sum(got["idle_by_span"].values()) + got["unattributed_s"] \
        == pytest.approx(got["idle_s"])
    assert got["admissions"][0]["busy_ms"] == pytest.approx(2000e-6)
    # a step whose program the profile did not catch bounds nothing
    ms = 1e6
    steps = {1: {hostspans.DISPATCH: (10 * ms, 10.4 * ms),
                 hostspans.WAIT: (10.4 * ms, 70 * ms)},
             2: {hostspans.DISPATCH: (74 * ms, 74.4 * ms),
                 hostspans.WAIT: (74.4 * ms, 134 * ms)}}
    got = hostspans.clock_offset(steps, [(8.5 * ms, 67.3 * ms)])
    assert got["steps"] == 1 and got["inside"] == 0
    assert got["offset_low_ms"] == pytest.approx(1.5)
    assert got["offset_high_ms"] == pytest.approx(2.7)
    assert got["offset_used_ms"] == pytest.approx(2.1)


def test_segments_give_every_instant_to_the_deepest_span():
    ev = [["a", 0.0, 100.0, {}], ["b", 10.0, 30.0, {}], ["c", 40.0, 20.0, {}],
          ["d", 45.0, 5.0, {}], ["e", 120.0, 10.0, {}]]
    assert hostspans.segments(ev) == [
        (0.0, 10.0, "a/self"), (10.0, 40.0, "b"), (40.0, 45.0, "c/self"),
        (45.0, 50.0, "d"), (50.0, 60.0, "c/self"), (60.0, 100.0, "a/self"),
        (120.0, 130.0, "e")]


def test_a_trace_without_engine_spans_or_without_a_device_reads_nothing(
        small, tmp_path):
    parent = {"planes": [p for p in small["planes"]
                         if p["name"].startswith("/device")]}
    assert hostspans.reduce(parent)["engine_spans"] == 0
    rehearsal = {"planes": [p for p in small["planes"]
                            if p["name"].startswith("/host")]}
    assert hostspans.reduce(rehearsal)["window_s"] == 0.0
    # a reader's view of both, and of a run that took no profile
    for trace in (parent, rehearsal):
        out = tmp_path / f"run{id(trace)}"
        out.mkdir()
        with open(out / hostspans.OUT_NAME, "w") as f:
            json.dump(hostspans.reduce(trace), f)
        assert hostspans.of_run(types.SimpleNamespace(
            out_dir=str(out))) is None
    empty = tmp_path / "untraced"
    empty.mkdir()
    assert hostspans.of_run(types.SimpleNamespace(out_dir=str(empty))) is None


def _run(tmp_path, small=None, **kw):
    out = tmp_path / "out"
    (out / "logs").mkdir(parents=True, exist_ok=True)
    if small is not None:
        with open(out / hostspans.OUT_NAME, "w") as f:
            json.dump(hostspans.reduce(small), f)
    kw.setdefault("engine", None)
    return types.SimpleNamespace(
        out_dir=str(out), device={"platform": "tpu", "kind": "TPU v5 lite"},
        **kw)


def test_the_five_new_metrics_are_found_as_files_and_read_their_sources(
        small, tmp_path):
    mdir = os.path.join(BENCH, "metrics")
    read = {n: spec.load_reader(mdir, n) for n in NEW_METRICS}
    run = _run(tmp_path, small, engine={
        "step_host_ms_p50": 5.5, "decode_steps_total": 1000,
        "decode_slot_steps_total": 19500, "admissions_total": 150})
    with open(os.path.join(run.out_dir, "logs",
                           "serving_0.stdout"), "w") as f:
        f.write('BENCH_START {"t": 1.0}\nSERVE_STARTUP {"runtime_init_s": '
                '9.5, "load_model_s": 5.0, "engine_init_s": 1.0, '
                '"frontend_start_s": 0.1, "total_s": 16.0, '
                '"process_age_s": 19.25}\nSERVING_UP http://h:1\n')
    assert read["step_host_ms"](run) == 5.5
    assert read["batch_per_step"](run) == 19.5
    assert read["admit_host_ms"](run) == pytest.approx(1900e-6)
    assert read["idle_attributed_pct"](run) == pytest.approx(
        100 * 6140 / 6500)
    assert read["replica_start_s"](run) == 19.25
    # the file was read once and kept on the run
    assert run.host_spans["idle_s"] == pytest.approx(6500 * NS)


def test_the_new_metrics_read_nothing_from_a_program_without_them(tmp_path):
    """The parent commit: no spans in its profile, no step counters on
    /v1/metrics, no SERVE_STARTUP line. Nothing raises."""
    mdir = os.path.join(BENCH, "metrics")
    run = _run(tmp_path, engine={"tokens_emitted": 5, "itl_p50_ms": 64.0})
    with open(os.path.join(run.out_dir, "logs",
                           "serving_0.stdout"), "w") as f:
        f.write("SERVING_UP http://h:1\n")
    for name in NEW_METRICS:
        assert spec.load_reader(mdir, name)(run) is None
    run = _run(tmp_path)            # and none from a training run
    for name in NEW_METRICS:
        assert spec.load_reader(mdir, name)(run) is None


def test_replica_start_falls_back_to_main_s_total(tmp_path):
    run = _run(tmp_path)
    with open(os.path.join(run.out_dir, "logs", "x.stdout"), "w") as f:
        f.write('SERVE_STARTUP {"total_s": 12.5}\n')
    read = spec.load_reader(os.path.join(BENCH, "metrics"),
                            "replica_start_s")
    assert read(run) == 12.5


def test_the_new_entries_only_append_to_per_layer():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    tail = bench["per_layer"][-len(NEW_METRICS):]
    assert [m["name"] for m in tail] == list(NEW_METRICS)
    assert all(m["workloads"] == ["chat-steady"] for m in tail)
    assert {m["moves"] for m in tail} == {"itl_p95_ms", "setup_s"}
    assert [m["name"] for m in spec.metrics_for(
        bench, "per_layer", "train-4k")][-1] == "flash_roofline"


def test_a_rehearsed_serving_run_reports_the_counters_and_the_startup(
        tmp_path):
    """The whole harness on the CPU with the five entries appended to the
    rehearsal's benchmark file: the program's counters and its
    SERVE_STARTUP line are read; the two metrics that need a device plane
    are left out, and the span reader's line says why (no window)."""
    from conftest import REHEARSE, rehearse
    with open(REHEARSE) as f:
        bench = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    for m in real["per_layer"][-len(NEW_METRICS):]:
        bench["per_layer"].append(dict(m, workloads=["chat-tiny"]))
    path = tmp_path / "BENCHMARK.rehearse.json"
    with open(path, "w") as f:
        json.dump(bench, f)
    out = tmp_path / "out"
    got = rehearse("chat-tiny", "--benchmark-file", str(path), "--trace",
                   "1", out=str(out))
    assert got["correct"] and got["failed"] == 0
    assert {"step_host_ms", "batch_per_step", "replica_start_s"} \
        <= set(got["reported"])
    assert not {"admit_host_ms", "idle_attributed_pct"} & set(got["reported"])
    line = [ln for ln in got["stdout"].splitlines()
            if ln.startswith("idle_by_span ")]
    assert len(line) == 1
    assert json.loads(line[0][len("idle_by_span "):])["window_s"] == 0.0
    with open(out / "client.json") as f:
        engine = json.load(f)["engine"]
    assert engine["decode_steps_total"] > 0
    assert engine["admissions_total"] >= got["attempted"]
    assert 1.0 <= engine["decode_slot_steps_total"] \
        / engine["decode_steps_total"] <= 4.0
    assert engine["step_host_ms_p50"] > 0
