"""The serving loop's own measurement: `tony.engine.*` spans on the
profiler's clock, the step counters in EngineStats, the SERVE_STARTUP
line, and the program names the benchmark's readers find in a trace.

The spans are read back the way `benchmark/lib/hostspans.py` reads them:
from the host plane of the `.xplane.pb` a `jax.profiler` session writes,
here on the CPU backend. All tier-1 fast.
"""

from __future__ import annotations

import glob
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models.llama import get_config, llama_init
from tony_tpu.serve import engine as engine_mod
from tony_tpu.serve.engine import ContinuousBatchingEngine
from tony_tpu.serve.frontend import ServeFrontend

pytestmark = pytest.mark.serving

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

STEP_LEAVES = ("tony.engine.reap", "tony.engine.decode.prepare",
               "tony.engine.decode.dispatch", "tony.engine.decode.wait",
               "tony.engine.emit", "tony.engine.release")
ADMIT_LEAVES = ("tony.engine.admit.prepare", "tony.engine.admit.dispatch",
                "tony.engine.admit.wait", "tony.engine.admit.book")
LEAVES = STEP_LEAVES + ADMIT_LEAVES + ("tony.engine.idle_wait",)
SLACK_NS = 50.0     # the reader hands times back as floats


@pytest.fixture(scope="module")
def model():
    cfg = get_config("tiny")
    return llama_init(cfg, jax.random.PRNGKey(0)), cfg


def _prompt(cfg, n, seed):
    rng = np.random.RandomState(seed)
    return [int(t) for t in rng.randint(0, cfg.vocab_size, size=n)]


def _generate(port, prompt, n, stream):
    body = json.dumps({"prompt": prompt, "max_new_tokens": n,
                       "stream": stream}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/generate", data=body,
        headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=120).read()


def _host_lines(path):
    """The `tony.*` and `test.*` events of each host thread line."""
    from jax.profiler import ProfileData
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = [{"name": e.name, "start": float(e.start_ns),
                       "end": float(e.start_ns) + float(e.duration_ns),
                       "stats": dict(e.stats)}
                      for e in line.events
                      if e.name.startswith(("tony.", "test."))]
            if events:
                lines.append(sorted(events, key=lambda e: (e["start"],
                                                           -e["end"])))
    return lines


@pytest.fixture(scope="module")
def traced(model, tmp_path_factory):
    """One profile of a live engine behind its front end: two requests
    (one streamed, one admitted while the other decodes), then an idle
    stretch. Gives the `tony.*` events of each thread line of the host
    plane as dicts, and the two request ids."""
    from tony_tpu import constants as C
    params, cfg = model
    with pytest.MonkeyPatch.context() as mp:
        # the chaos seam: 5 ms a decode step, so that the second request
        # surely arrives while the first still decodes
        mp.setenv(C.TEST_SERVE_DECODE_DELAY, "5")
        engine = ContinuousBatchingEngine(params, cfg, n_slots=2,
                                          token_budget=48, queue_depth=8)
    engine.start()
    frontend = ServeFrontend(engine, port=0, host="127.0.0.1")
    frontend.start()
    out = str(tmp_path_factory.mktemp("engine_profile"))
    try:
        # every shape compiled before the profile opens
        _generate(frontend.port, _prompt(cfg, 5, 1), 3, False)
        _generate(frontend.port, _prompt(cfg, 7, 2), 3, False)
        first_id = engine.stats.requests_submitted
        jax.profiler.start_trace(out)
        try:
            a = threading.Thread(target=_generate, args=(
                frontend.port, _prompt(cfg, 5, 3), 30, True))
            a.start()
            while engine.active_slots() == 0:   # the second is admitted
                time.sleep(0.002)               # while the first decodes
            _generate(frontend.port, _prompt(cfg, 7, 4), 4, False)
            a.join()
            time.sleep(0.1)         # the loop finds nothing: idle_wait
        finally:
            jax.profiler.stop_trace()
    finally:
        frontend.stop()
        engine.stop()
    path = sorted(glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    return {"lines": _host_lines(path),
            "request_ids": {first_id, first_id + 1}}


def _engine_line(traced):
    holding = [ln for ln in traced["lines"]
               if any(e["name"] == "tony.engine.step" for e in ln)]
    assert len(holding) == 1, "the loop's spans lie on one thread line"
    return holding[0]


def _within(child, parent):
    return (parent["start"] - SLACK_NS <= child["start"]
            and child["end"] <= parent["end"] + SLACK_NS)


def _parent_of(event, candidates):
    found = [p for p in candidates if _within(event, p)]
    assert len(found) == 1, (event, len(found))
    return found[0]


def test_every_span_of_the_table_lies_on_the_loops_one_thread_line(traced):
    line = _engine_line(traced)
    names = {e["name"] for e in line}
    assert names == set(LEAVES) | {"tony.engine.step", "tony.engine.admit"}
    # and none of them on any other thread's line
    for other in traced["lines"]:
        if other is not line:
            assert {e["name"] for e in other} == {"tony.frontend.write"}


def test_spans_nest_as_stated_and_leaves_do_not_overlap(traced):
    line = _engine_line(traced)
    by = {}
    for e in line:
        by.setdefault(e["name"], []).append(e)
    steps, admits = by["tony.engine.step"], by["tony.engine.admit"]
    for name in STEP_LEAVES:
        for e in by[name]:
            step = _parent_of(e, steps)
            assert e["stats"]["step"] == step["stats"]["step"]
    for adm in admits:
        _parent_of(adm, steps)
    for name in ADMIT_LEAVES:
        for e in by[name]:
            adm = _parent_of(e, admits)
            assert e["stats"]["request_id"] == adm["stats"]["request_id"]
    for e in by["tony.engine.idle_wait"]:
        assert not any(_within(e, s) for s in steps)
    # an admission is tiled by its four phases, in order, without a hole
    for adm in admits:
        kids = [e for e in line if e["name"] in ADMIT_LEAVES
                and _within(e, adm)]
        assert [k["name"] for k in kids] == list(ADMIT_LEAVES)
        assert kids[0]["start"] - adm["start"] < 5e6        # < 5 ms
        for x, y in zip(kids, kids[1:]):
            assert 0 <= y["start"] - x["end"] + SLACK_NS < 5e6
    # a step that dispatches one decode step and reads the one before
    # holds its six phases; the first after an idle engine has nothing to
    # read, the last of a stream nothing to dispatch, an idle one neither
    reap, prepare, dispatch, wait, emit, release = STEP_LEAVES
    shapes = {"full": list(STEP_LEAVES),
              "first": [reap, prepare, dispatch, release],
              "last": [reap, wait, emit, release], "idle": [reap]}
    seen = []
    for step in steps:
        kids = [e["name"] for e in line if e["name"] in STEP_LEAVES
                and _within(e, step)]
        assert kids in shapes.values(), kids
        assert (dispatch in kids) == (step["stats"]["active"] > 0)
        seen.append(next(k for k, v in shapes.items() if v == kids))
    assert seen.count("full") > 20      # what a profile mostly holds
    assert {"first", "last", "idle"} <= set(seen)
    for before, after in zip(seen, seen[1:]):
        if after in ("full", "last"):   # a read follows a dispatch
            assert before in ("first", "full")
    leaves = [e for e in line if e["name"] in LEAVES]
    for x, y in zip(leaves, leaves[1:]):
        assert x["end"] <= y["start"] + SLACK_NS, (x, y)


def test_step_counts_up_by_one_and_admissions_carry_their_request(traced):
    line = _engine_line(traced)
    steps = [e for e in line if e["name"] == "tony.engine.step"]
    numbers = [e["stats"]["step"] for e in steps]
    assert len(numbers) > 5
    assert numbers == list(range(numbers[0], numbers[0] + len(numbers)))
    admits = [e for e in line if e["name"] == "tony.engine.admit"]
    assert {a["stats"]["request_id"] for a in admits} \
        == traced["request_ids"]
    assert sorted(a["stats"]["prompt_tokens"] for a in admits) == [5, 7]
    assert {a["stats"]["slot"] for a in admits} == {0, 1}
    # a step says how many it admitted and how many slots it then decoded
    assert sum(s["stats"]["admitted"] for s in steps) == 2
    assert max(s["stats"]["active"] for s in steps) == 2


def test_handler_threads_mark_each_streamed_chunk(traced):
    writes = [e for ln in traced["lines"] for e in ln
              if e["name"] == "tony.frontend.write"]
    # the streamed request: 30 tokens and the done record
    streamed = min(traced["request_ids"])
    assert [w["stats"]["request_id"] for w in writes] == [streamed] * 31


def test_step_counters_are_exact_for_a_known_occupancy(model):
    """A admits with B in step 1 (each gets its first token from the
    prefill); A then needs 3 decode steps, B 5; C arrives after the
    engine has sat idle."""
    params, cfg = model
    engine = ContinuousBatchingEngine(params, cfg, n_slots=2,
                                      token_budget=32, queue_depth=8)
    snap = engine.snapshot()
    assert snap["decode_steps_total"] == 0
    assert snap["step_host_ms_p50"] is None         # the key is there
    a = engine.submit(_prompt(cfg, 5, 5), 4)
    b = engine.submit(_prompt(cfg, 7, 6), 6)
    while not (a.done.is_set() and b.done.is_set()):
        assert engine.step()
    assert not engine.step()                        # idle: nothing active
    snap = engine.snapshot()
    assert snap["admissions_total"] == 2
    assert snap["decode_steps_total"] == 5
    assert snap["decode_slot_steps_total"] == 3 * 2 + 2 * 1
    # one sample a decode step, but for the first after an idle engine
    assert len(engine.stats.step_host_s) == 4
    c = engine.submit(_prompt(cfg, 5, 7), 3)
    while not c.done.is_set():
        assert engine.step()
    snap = engine.snapshot()
    assert snap["admissions_total"] == 3
    assert snap["decode_steps_total"] == 7
    assert snap["decode_slot_steps_total"] == 10
    assert len(engine.stats.step_host_s) == 5
    for tag in ("p50", "p95", "p99"):
        assert snap[f"step_host_ms_{tag}"] > 0
    assert snap["step_host_ms_p50"] <= snap["step_host_ms_p99"]
    # the admissions in between are not in it: a sample is the loop's
    # own bookkeeping and two dispatches, far under a tiny prefill + step
    assert all(0 < s < 0.5 for s in engine.stats.step_host_s)
    # /v1/metrics only: nothing new rides the metrics RPC
    rpc_names = {m["name"] for m in engine.metrics()}
    assert not [n for n in rpc_names
                if "STEP" in n or "ADMISSION" in n]


@pytest.mark.parametrize("stepper", ["step", "_step"])
def test_step_host_time_leaves_the_admission_out(model, monkeypatch,
                                                 stepper):
    """By hand and as the loop steps (a step in flight): a sample is the
    loop thread's time from one read of a step's tokens to the next, and
    neither the wait on the device nor an admission is in it."""
    params, cfg = model
    with monkeypatch.context() as mp:
        from tony_tpu import constants as C
        mp.setenv(C.TEST_SERVE_DECODE_DELAY, "300")     # the device's wait
        engine = ContinuousBatchingEngine(params, cfg, n_slots=2,
                                          token_budget=32, queue_depth=8)
    a = engine.submit(_prompt(cfg, 5, 8), 8)
    step = getattr(engine, stepper)
    step()
    step()
    step()
    assert 1 <= len(engine.stats.step_host_s) <= 2
    assert all(0 < s < 0.25 for s in engine.stats.step_host_s)
    # B's admission is made slow; the step that carries it must not say so
    admit = engine._admit

    def slow_admit(slot, handle, ph):
        time.sleep(0.3)
        admit(slot, handle, ph)

    monkeypatch.setattr(engine, "_admit", slow_admit)
    engine.submit(_prompt(cfg, 7, 9), 2)
    step()
    assert engine.stats.admissions_total == 2
    assert engine.stats.step_host_s[-1] < 0.25
    assert a.finish_reason is None
    engine.stop()


@pytest.mark.parametrize("program", ["_decode_sample_step", "_admit_step"])
def test_the_programs_keep_the_names_the_benchmarks_readers_find(
        model, program):
    """benchmark/lib/readers.py finds the device's events by these names
    (`DECODE_PROGRAM`, `ADMIT_PROGRAM`): a rename would blind
    decode_step_ms.steady, prefill_step_ms and the clock check."""
    params, cfg = model
    engine = ContinuousBatchingEngine(params, cfg, n_slots=2,
                                      token_budget=32, queue_depth=8)
    key = jax.random.PRNGKey(0)
    tokens = jnp.zeros((2,), jnp.int32)
    if program == "_decode_sample_step":
        lowered = engine_mod._decode_sample_step.lower(
            params, cfg, engine._cache, tokens, np.zeros((2,), np.int32),
            key, np.int32(1), 0.0, 0, 1.0)
    else:
        lowered = engine_mod._admit_step.lower(
            params, cfg, engine._cache, tokens, np.zeros((5,), np.int32),
            np.int32(0), key, np.int32(1), 0.0, 0, 1.0, False, np.int32(0),
            False)
    assert f"module @jit_{program} " in lowered.as_text()


def test_serve_main_prints_its_startup_phases_before_serving_up(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    for key in ("TONY_CONF_PATH", "AM_HOST", "AM_PORT"):
        env.pop(key, None)
    proc = subprocess.Popen(
        [sys.executable, "-m", "tony_tpu.serve", "--config", "tiny",
         "--port", "0", "--host", "127.0.0.1", "--slots", "2",
         "--token-budget", "32"],
        cwd=str(tmp_path), env=env, stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL, text=True)
    lines: list = []

    def read() -> None:
        for line in proc.stdout:
            lines.append(line.rstrip("\n"))
            if line.startswith("SERVING_UP "):
                return

    reader = threading.Thread(target=read, daemon=True)
    reader.start()
    try:
        reader.join(timeout=180)
        assert lines and lines[-1].startswith("SERVING_UP "), lines
    finally:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
    marked = [ln for ln in lines if ln.startswith("SERVE_STARTUP ")]
    assert len(marked) == 1
    assert lines.index(marked[0]) == len(lines) - 2     # just before
    got = json.loads(marked[0][len("SERVE_STARTUP "):])
    parts = ("runtime_init_s", "load_model_s", "engine_init_s",
             "frontend_start_s")
    assert set(got) - {"process_age_s"} == set(parts) | {"total_s"}
    assert all(got[p] >= 0 for p in parts)
    assert 0.9 * got["total_s"] <= sum(got[p] for p in parts) \
        <= got["total_s"]
    if os.path.exists("/proc/self/stat"):
        # the interpreter's start and the imports came before main()
        assert got["process_age_s"] >= got["total_s"] - 0.02


# -- a step's counts on its own spans (PR 43) --------------------------------

def _family_models():
    from tony_tpu.models import lfm2, sala
    llama = get_config("tiny")
    sala_tiny = sala.get_sala_config("sala_tiny")
    lfm2_tiny = lfm2.get_config("lfm2_tiny")
    key = jax.random.PRNGKey(0)
    return {"llama": (llama_init(llama, key), llama),
            "sala": (sala.sala_init(sala_tiny, key), sala_tiny),
            "lfm2": (lfm2.lfm2_init(lfm2_tiny, key), lfm2_tiny)}


FAMILIES = ("llama", "sala", "lfm2")
# the counters of /v1/metrics a step's span attributes sum to
COUNTERS = ("decode_steps_total", "decode_slot_steps_total",
            "decode_slot_steps_discarded_total", "state_slots_moved_total",
            "moe_experts_hit_total", "moe_rows_total")


@pytest.fixture(scope="module")
def counted(tmp_path_factory):
    """One profile over three engines stepped by hand at a known
    occupancy (A: prompt 5, 4 tokens; B: prompt 7, 6 tokens, admitted in
    the same step: three decode steps carry both, two carry B alone), a
    llama-shaped, a SALA-shaped and an LFM2-shaped tiny model, each under
    a `test.run` span of its own; then a loop thread stopped with a step
    in flight; then a `Phases` of the test's own. Gives per run the spans
    under its mark and the growth of the counters over it."""
    from tony_tpu import constants as C
    from tony_tpu.observability.spans import Phases, span
    engines = {}
    for family, (params, cfg) in _family_models().items():
        engine = ContinuousBatchingEngine(params, cfg, n_slots=3,
                                          token_budget=128, queue_depth=8)
        for n in (5, 7):        # every shape compiled before the profile
            engine.submit(_prompt(cfg, n, n), 3)
            while engine.step():
                pass
        engines[family] = engine
    params, cfg = _family_models()["llama"]
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv(C.TEST_SERVE_DECODE_DELAY, "5")
        looped = ContinuousBatchingEngine(params, cfg, n_slots=3,
                                          token_budget=128, queue_depth=8)
    out = str(tmp_path_factory.mktemp("counted_profile"))
    grown = {}
    jax.profiler.start_trace(out)
    try:
        for family, engine in engines.items():
            before = engine.snapshot()
            with span("test.run", which=family):
                engine.submit(_prompt(cfg, 5, 11), 4)
                engine.submit(_prompt(cfg, 7, 12), 6)
                while engine.step():
                    pass
            after = engine.snapshot()
            grown[family] = {k: after[k] - before[k] for k in COUNTERS
                             if k in after}
        with span("test.run", which="stop"):
            looped.start()
            looped.submit(_prompt(cfg, 5, 13), 100)
            while looped.stats.decode_steps_total < 4:
                time.sleep(0.002)
            looped.stop()
        with span("test.run", which="phases"):
            with Phases("test.parent", step=3) as ph:
                ph.enter("test.first", riders=2)
                ph.enter("test.second")
                ph.enter("test.third", lands=2)
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    lines = _host_lines(path)
    marks = {e["stats"]["which"]: e for ln in lines for e in ln
             if e["name"] == "test.run"}
    runs = {which: [[e for e in ln if _within(e, mark)
                     and e["name"] != "test.run"] for ln in lines]
            for which, mark in marks.items()}
    return {"runs": runs, "grown": grown}


def _named(events, name):
    return [e for e in events if e["name"] == "tony.engine." + name]


def _threads(counted, which):
    """The spans of a run, thread line by thread line, the busiest last."""
    return sorted((ln for ln in counted["runs"][which] if ln), key=len)


def _own_thread(counted, which):
    """The spans of a run that was made on one thread."""
    lines = _threads(counted, which)
    assert len(lines) == 1
    return lines[0]


@pytest.mark.parametrize("family", FAMILIES)
def test_a_steps_counts_on_its_spans_sum_to_the_counters_growth(counted,
                                                                family):
    events = _own_thread(counted, family)
    grown = counted["grown"][family]
    dispatches, emits = _named(events, "decode.dispatch"), _named(events,
                                                                  "emit")

    def total(spans, key):
        return sum(e["stats"][key] for e in spans)

    assert len(dispatches) == len(emits) == grown["decode_steps_total"] == 5
    assert [d["stats"]["riders"] for d in dispatches] == [2, 2, 2, 1, 1]
    # the K/V rows of the context in flight: A at 5, 6, 7 beside B at
    # 7, 8, 9, then B alone at 10 and 11
    assert [d["stats"]["context_rows"] for d in dispatches] \
        == [12, 14, 16, 10, 11]
    # every rider's token was kept or thrown away (a stream that ended
    # with the step in flight)
    assert grown["decode_slot_steps_total"] == 8
    assert total(dispatches, "riders") == grown["decode_slot_steps_total"] \
        + grown["decode_slot_steps_discarded_total"]
    if family == "sala":    # the riders are the slots whose state moved
        assert total(dispatches, "riders") \
            == grown["state_slots_moved_total"]
    # what the model counted on the device rides on the `emit` that
    # landed it, under the counts' own names; nothing else rides: the
    # leaves share `step`, each has its own
    counts = {"moe_experts_hit", "moe_rows"} if family == "lfm2" else set()
    for attr in counts:
        assert total(emits, attr) == grown[attr + "_total"] > 0, attr
    for d in dispatches:
        assert set(d["stats"]) == {"step", "riders", "context_rows"}
    for e in emits:
        assert set(e["stats"]) == {"step", "lands"} | counts
    for name in ("reap", "decode.prepare", "release"):
        assert all(set(e["stats"]) == {"step"} for e in _named(events, name))


@pytest.mark.parametrize("how", ["loop", "step", "stop"])
def test_lands_names_the_step_whose_dispatch_made_the_tokens(traced, counted,
                                                             how):
    """Since PR 31 an iteration of the loop reads the step dispatched an
    iteration earlier; a caller's own `step()` and `stop()` land the one
    just dispatched."""
    if how == "loop":
        events = _engine_line(traced)
        behind = 1
    elif how == "step":
        events = _own_thread(counted, "llama")
        behind = 0
    else:
        # the mark's own thread holds only what `stop()` itself landed;
        # the loop's dispatches lie on the loop thread's line
        events, loop = _threads(counted, "stop")
        assert len(_named(events, "step")) == 1
        assert not _named(events, "decode.dispatch")
        behind = 0
    waits, emits = _named(events, "decode.wait"), _named(events, "emit")
    assert waits and len(waits) == len(emits)
    dispatched = {d["stats"]["step"]: d for d in _named(
        loop if how == "stop" else events, "decode.dispatch")}
    for wait, emit in zip(waits, emits):
        assert wait["stats"]["lands"] == emit["stats"]["lands"] \
            == wait["stats"]["step"] - behind
        assert set(wait["stats"]) == {"step", "lands"}
        made = dispatched[wait["stats"]["lands"]]
        assert made["end"] <= wait["start"] + SLACK_NS
    if how == "stop":
        # the loop's own waits ran a step behind, to the last but one
        assert [w["stats"]["lands"] for w in _named(loop, "decode.wait")] \
            == sorted(dispatched)[:-1]
        assert waits[0]["stats"]["lands"] == max(dispatched)


def test_a_leafs_own_attributes_stay_on_that_leaf(counted):
    events = {e["name"]: e["stats"]
              for e in _own_thread(counted, "phases")}
    assert events == {"test.parent": {"step": 3},
                      "test.first": {"step": 3, "riders": 2},
                      "test.second": {"step": 3},
                      "test.third": {"step": 3, "lands": 2}}


def test_with_no_profile_open_the_attributes_outlive_no_call(model):
    """The counts go to the annotation's constructor and to nothing else:
    no field of the engine, of its stats or of the step in flight grows
    by them, and the spans' share of an iteration stays a few
    microseconds (PR 26: under 10 us a step)."""
    from tony_tpu.observability.spans import Phases
    params, cfg = model
    engine = ContinuousBatchingEngine(params, cfg, n_slots=2,
                                      token_budget=32, queue_depth=8)
    fields = (set(vars(engine)), set(vars(engine.stats)))
    engine.submit(_prompt(cfg, 5, 14), 6)
    engine._step()
    engine._step()
    flight = engine._in_flight
    assert set(vars(flight)) == {"tokens", "counts", "riders", "step"}
    assert flight.step == engine._steps == 2
    assert (set(vars(engine)), set(vars(engine.stats))) == fields
    engine.stop()
    with Phases("test.parent", step=1) as ph:
        ph.enter("test.leaf", riders=2, context_rows=9)
        assert ph._attrs == {"step": 1}
    n = 2000
    t = time.perf_counter()
    for i in range(n):
        with Phases("tony.engine.step", step=i) as ph:
            ph.enter("tony.engine.reap")
            ph.enter("tony.engine.decode.prepare")
            ph.enter("tony.engine.decode.dispatch", riders=3,
                     context_rows=1000)
            ph.enter("tony.engine.decode.wait", lands=i)
            ph.enter("tony.engine.emit", lands=i, moe_experts_hit=400,
                     moe_rows=96)
            ph.enter("tony.engine.release")
    per_step = (time.perf_counter() - t) / n
    assert per_step < 50e-6, per_step     # ~8 us here; a loaded CI host
