"""The serving loop keeps one decode step in flight: step N+1 is dispatched
from the device's own token vector before step N's tokens are read, so the
host's bookkeeping runs beside the device. What must hold whatever the
timing: the same tokens as a caller stepping by hand and as offline greedy
`generate`; an `eos` seen one step late costs one dropped slot-step and
touches no one else's stream; every stream ends once, after its tokens;
and a plain decode iteration runs one program, `jit__decode_sample_step`.

A tiny Llama and a tiny model with recurrent state (models/sala.py: its
admission replaces a slot's whole leaf of every kind), on the CPU; where
timing matters the device's wait is played by the chaos seam
(TEST_SERVE_DECODE_DELAY, a sleep after each read). All tier-1 fast.
"""

from __future__ import annotations

import importlib
import queue
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu import constants as C
from tony_tpu.models import sala
from tony_tpu.models.llama import get_config, llama_init
from tony_tpu.serve import engine as engine_mod
from tony_tpu.serve.engine import (
    ContinuousBatchingEngine, admit_step_cache_size, decode_step_cache_size,
)

# (tony_tpu.models exports the function `generate` over the module's name)
gen = importlib.import_module("tony_tpu.models.generate")

pytestmark = pytest.mark.serving

# prompt lengths and the budget of a slot; the recurrent model's prompts
# lie past its dense_len (64), so every sparse layer selects blocks, and
# prompt + tokens is a multiple of its block (8), which offline `generate`
# needs of its cache
SHAPES = {"llama": {"budget": 48, "prompts": (5, 7, 9), "new": 8},
          "sala": {"budget": 128, "prompts": (72, 80, 88), "new": 8}}


def _model(name):
    if name == "llama":
        cfg = get_config("tiny")
        params = llama_init(cfg, jax.random.PRNGKey(0))
    else:
        cfg = sala.get_sala_config("sala_tiny")
        params = sala.sala_init(cfg, jax.random.PRNGKey(5))
    return {"params": params, "cfg": cfg, **SHAPES[name]}


@pytest.fixture(scope="module", params=list(SHAPES))
def model(request):
    return _model(request.param)


@pytest.fixture(scope="module")
def llama():
    return _model("llama")


def _prompt(model, n, seed):
    rng = np.random.RandomState(seed)
    return [int(t) for t in rng.randint(0, model["cfg"].vocab_size, size=n)]


def _engine(model, delay_ms=0, **kw):
    kw = {"n_slots": 2, "token_budget": model["budget"], "queue_depth": 8,
          **kw}
    with pytest.MonkeyPatch.context() as mp:
        if delay_ms:
            mp.setenv(C.TEST_SERVE_DECODE_DELAY, str(delay_ms))
        return ContinuousBatchingEngine(model["params"], model["cfg"], **kw)


def _woken(handle):
    """What a waiter on the stream has been handed so far."""
    out = []
    while True:
        try:
            out.append(handle._queue.get_nowait())
        except queue.Empty:
            return out


def _run(engine, stepper="_step", limit=400):
    """Step until the engine has nothing left, as the loop thread steps
    (`_step`: a step stays in flight) or as a caller does (`step`)."""
    step = getattr(engine, stepper)
    for _ in range(limit):
        if not step():
            return
    raise AssertionError("the engine did not come to rest")


def _by_hand(model, requests, **kw):
    """Each (prompt, max_new) alone through a fresh engine stepped by
    hand: what its stream is, whoever else is served beside it."""
    out = []
    for prompt, new in requests:
        engine = _engine(model, **kw)
        handle = engine.submit(prompt, new)
        _run(engine, "step")
        out.append(handle)
    return out


def test_loop_by_hand_and_offline_generate_give_the_same_tokens(model):
    new = model["new"]
    prompts = [_prompt(model, n, 10 + i)
               for i, n in enumerate(model["prompts"])]
    want = [[int(t) for t in gen.generate(
        model["params"], model["cfg"], jnp.asarray([p], jnp.int32), new)[0]]
        for p in prompts]
    by_hand = _engine(model)
    handles = [by_hand.submit(p, new) for p in prompts]   # 3 into 2 slots
    _run(by_hand, "step")
    assert [h.tokens for h in handles] == want
    assert by_hand.stats.decode_steps_overlapped_total == 0
    engine = _engine(model)
    engine.start()
    try:
        handles = [engine.submit(p, new) for p in prompts]
        streamed = [list(h.iter_tokens(timeout=120)) for h in handles]
    finally:
        engine.stop()
    assert streamed == want
    assert [h.finish_reason for h in handles] == ["length"] * 3
    stats = engine.stats
    assert stats.decode_steps_overlapped_total > 0
    assert stats.decode_slot_steps_discarded_total == 0
    assert stats.decode_slot_steps_total == 3 * (new - 1)


def test_with_a_slow_device_every_dispatch_precedes_the_read_before_it(
        model, monkeypatch):
    """15 ms a step on the device: the loop dispatches N+1, then reads N."""
    engine = _engine(model, delay_ms=15)
    new = 24
    events = []
    program, device_get = engine_mod._decode_sample_step, jax.device_get

    def dispatch(*args, **kw):
        events.append("dispatch")
        return program(*args, **kw)

    def reading(x):
        if getattr(x, "shape", None) == (engine.n_slots,):
            events.append("read")       # a step's tokens, not a first one
        return device_get(x)

    monkeypatch.setattr(engine_mod, "_decode_sample_step", dispatch)
    monkeypatch.setattr(engine_mod.jax, "device_get", reading)
    engine.start()
    try:
        handle = engine.submit(_prompt(model, model["prompts"][0], 3), new)
        assert len(handle.result(timeout=120)) == new
    finally:
        engine.stop()
    steps = new - 1
    assert events.count("dispatch") == events.count("read") == steps
    assert events == (["dispatch"] + ["dispatch", "read"] * (steps - 1)
                      + ["read"])
    snap = engine.snapshot()
    assert snap["decode_steps_total"] == steps
    assert snap["decode_steps_overlapped_total"] / steps >= 0.9
    assert snap["decode_slot_steps_discarded_total"] == 0
    # the time that has to fit under a device step: none of the 15 ms
    assert 0 < snap["step_host_ms_p50"] < 10


def _first_fresh(tokens, after=2):
    """The first token past `after` that the stream had not made before:
    as the eos, it ends the stream there and nowhere earlier."""
    return next(i for i in range(after, len(tokens))
                if tokens[i] not in tokens[:i])


def _eos_case(model, **kw):
    """A ends on an eos with B queued for its slot (one slot). Returns
    (A, B, engine, A's stream up to its eos, the eos)."""
    budget_kw = {"n_slots": 1, **kw}
    prompt_a = _prompt(model, model["prompts"][2], 21)
    # B shares A's first tokens: with prefix_sharing on its admission
    # gathers A's sealed pages and prefills only the rest
    prompt_b = prompt_a[:model["prompts"][0] - 2] + _prompt(model, 2, 22)
    whole, = _by_hand(model, [(prompt_a, 8)], **budget_kw)
    at = _first_fresh(whole.tokens)
    assert at < 7
    eos = whole.tokens[at]
    engine = _engine(model, eos_id=eos, **budget_kw)
    a = engine.submit(prompt_a, 8)
    b = engine.submit(prompt_b, 5)
    return a, b, engine, whole.tokens[:at + 1], eos


def test_an_eos_is_seen_a_step_late_and_its_slots_next_occupant_is_untouched(
        model):
    _check_the_eos_case(model)


def test_an_eos_seen_late_leaves_a_prefix_sharing_admission_untouched(llama):
    """`shared=True`: the admission behind the late step gathers pages
    into rows [0, start) and prefills only the rest of the prompt."""
    b = _check_the_eos_case(llama, prefix_sharing=True, kv_page_size=2)
    assert b.kv_matched_tokens > 0


def _check_the_eos_case(model, **kw):
    a, b, engine, want_a, eos = _eos_case(model, **kw)
    _run(engine)
    assert a.finish_reason == "eos" and a.tokens == want_a
    # the slot rode in the step after the eos; that token reached no one
    assert _woken(a) == want_a + [engine_mod._DONE]
    assert engine.stats.decode_slot_steps_discarded_total == 1
    # B was admitted into the slot behind that step, and streams what a
    # fresh engine streams for it
    fresh_engine = _engine(model, eos_id=eos, n_slots=1, **kw)
    fresh = fresh_engine.submit(b.prompt, 5)
    _run(fresh_engine, "step")
    assert b.tokens == fresh.tokens and b.finish_reason == "length"
    assert _woken(b) == b.tokens + [engine_mod._DONE]
    assert engine.stats.decode_slot_steps_total == \
        len(a.tokens) - 1 + len(b.tokens) - 1
    assert engine.stats.decode_steps_total == \
        engine.stats.decode_slot_steps_total + 1
    return b


def test_by_hand_an_eos_is_seen_on_its_own_step_and_discards_nothing(model):
    a, b, engine, want_a, _ = _eos_case(model)
    _run(engine, "step")
    assert a.tokens == want_a and a.finish_reason == "eos"
    assert engine.stats.decode_slot_steps_discarded_total == 0
    assert engine.stats.decode_steps_overlapped_total == 0


def test_a_finish_by_length_is_known_a_step_ahead_and_discards_nothing(
        model):
    engine = _engine(model)
    lengths = (3, 6)
    handles = [engine.submit(_prompt(model, n, 30 + i), new)
               for i, (n, new) in enumerate(zip(model["prompts"], lengths))]
    engine._step()
    engine._step()          # the shorter stream's last step is in flight
    assert len(engine._in_flight.riders) == 2
    engine._step()          # and it does not ride in the one after
    assert handles[0].finish_reason == "length"
    assert [s.index for s, _, _ in engine._in_flight.riders] == [1]
    _run(engine)
    assert [len(h.tokens) for h in handles] == list(lengths)
    stats = engine.stats
    assert stats.decode_slot_steps_discarded_total == 0
    assert stats.decode_slot_steps_total == sum(n - 1 for n in lengths)
    assert stats.decode_steps_total == max(lengths) - 1


@pytest.mark.parametrize("ending", ["cancel", "stop"])
def test_a_stream_ended_with_a_step_in_flight_ends_once_after_its_tokens(
        model, ending):
    engine = _engine(model, delay_ms=15)
    engine.start()
    try:
        a = engine.submit(_prompt(model, model["prompts"][0], 40), 30)
        b = engine.submit(_prompt(model, model["prompts"][1], 41), 30)
        deadline = time.monotonic() + 120
        while len(a.tokens) < 4 and time.monotonic() < deadline:
            time.sleep(0.002)
        if ending == "cancel":
            a.cancel()
            assert a.done.wait(timeout=60)
            assert a.finish_reason == "cancelled"
            assert b.finish_reason is None      # the other stream goes on
            more = len(b.tokens)
            while len(b.tokens) < more + 3 and time.monotonic() < deadline:
                time.sleep(0.002)
            assert len(b.tokens) >= more + 3
    finally:
        engine.stop()
    assert b.finish_reason == "shutdown"
    assert len(a.tokens) >= 4
    for handle in (a, b):
        assert len(handle.tokens) < 30
        assert _woken(handle) == handle.tokens + [engine_mod._DONE]
    assert engine._in_flight is None
    stats = engine.stats
    assert stats.tokens_emitted == len(a.tokens) + len(b.tokens)
    # a cancelled stream's step in flight was run for no one
    assert stats.decode_slot_steps_discarded_total == (ending == "cancel")


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_a_warm_plain_decode_iteration_runs_one_program_and_nothing_eager(
        model, monkeypatch, temperature):
    engine = _engine(model, temperature=temperature, top_k=8)
    a = engine.submit(_prompt(model, model["prompts"][0], 50), 12)
    for _ in range(3):
        engine._step()
    compiled = (decode_step_cache_size(), admit_step_cache_size(),
                engine_mod._seed_token._cache_size())
    calls = []
    program = engine_mod._decode_sample_step

    def dispatch(*args, **kw):
        calls.append(1)
        return program(*args, **kw)

    def eager(*args, **kw):
        raise AssertionError("an eager jax operation between two steps")

    monkeypatch.setattr(engine_mod, "_decode_sample_step", dispatch)
    for module, names in ((jax.random, ("split", "fold_in")),
                          (jnp, ("asarray", "array", "zeros", "full",
                                 "int32", "where")),
                          (jax, ("device_put",)),
                          (engine_mod, ("_admit_step", "_seed_token"))):
        for name in names:
            monkeypatch.setattr(module, name, eager)
    before = len(a.tokens)
    for _ in range(5):
        assert engine._step()
    monkeypatch.undo()
    assert len(calls) == 5 and len(a.tokens) == before + 5
    assert (decode_step_cache_size(), admit_step_cache_size(),
            engine_mod._seed_token._cache_size()) == compiled
    _run(engine)
    assert len(a.tokens) == 12


def test_sampled_streams_are_reproducible_by_seed_in_the_loop_and_by_hand(
        llama):
    """temperature > 0: a draw's key is the engine's base key with the
    draw's number folded in (inside the jitted step), so a stream depends
    on the seed and the order of draws, not on who steps the engine."""
    prompt = _prompt(llama, 6, 60)

    def sampled(seed, stepper):
        engine = _engine(llama, temperature=1.0, seed=seed)
        handle = engine.submit(prompt, 16)
        _run(engine, stepper)
        return handle.tokens

    first = sampled(7, "step")
    assert sampled(7, "_step") == first
    assert sampled(7, "step") == first
    assert sampled(8, "step") != first
    greedy, = _by_hand(llama, [(prompt, 16)])
    assert greedy.tokens != first


# -- the decode step reads only the rows a slot attends to (PR 35) ----------

def _recorded_dispatches(monkeypatch):
    """Every dispatched decode step's (pos, attend), as the host arrays
    the loop handed to the program."""
    seen = []
    program = engine_mod._decode_sample_step

    def dispatch(*args, **kw):
        seen.append((np.array(args[4]), np.array(kw["attend"])))
        return program(*args, **kw)

    monkeypatch.setattr(engine_mod, "_decode_sample_step", dispatch)
    return seen


def test_a_slot_that_does_not_ride_attends_to_nothing_where_it_is_parked(
        model, monkeypatch):
    """Three slots: A decodes on, B ends by length and its slot is parked
    at the last budget row, the third is never used and sits at row 0.
    Both are stepped at the row they were stepped at before PR 35, and
    read no row of the cache; a rider attends to exactly the rows below
    its position.
    A model whose cache is by layer kind is handed the same array and
    keeps no count of it."""
    seen = _recorded_dispatches(monkeypatch)
    engine = _engine(model, n_slots=3)
    budget, (pa, pb) = model["budget"], model["prompts"][:2]
    a = engine.submit(_prompt(model, pa, 70), 8)
    b = engine.submit(_prompt(model, pb, 71), 3)
    _run(engine)
    assert (len(a.tokens), len(b.tokens)) == (8, 3) and len(seen) == 7
    for step, (pos, attend) in enumerate(seen):
        # B's last step (by length, known ahead) is the second; in the
        # third its slot stays at its next row, and is parked once that
        # last step has landed
        at = pb + step if step <= 2 else budget - 1
        assert list(pos) == [pa + step, at, 0]
        assert list(attend) == [pa + step, at if step < 2 else 0, 0]
    snap = engine.snapshot()
    if model["cfg"].__class__.__name__ == "SalaConfig":
        assert "cache_rows_read_total" not in snap
        return
    chunk = engine._read_chunk
    assert chunk == 16 and budget % chunk == 0
    want = sum(int(-(-n // chunk) * chunk) for _, attend in seen
               for n in attend)
    assert snap["cache_rows_read_total"] == want > 0
    assert snap["cache_rows_budget_total"] == len(seen) * 3 * budget
    assert want < snap["cache_rows_budget_total"]


@pytest.mark.parametrize("kernel", ["jnp", "interpreted"])
def test_a_stream_keeps_its_greedy_tokens_with_slots_freed_and_refilled_around_it(
        llama, monkeypatch, kernel):
    """A long stream in slot 0 while slot 1 is freed, parked and admitted
    again twice around it, in the loop thread's order (a step in flight):
    its tokens are offline `generate`'s, and so are the others'. Once on
    the CPU's jnp branch and once with the kernel itself (interpret mode;
    a budget no other test compiles, since the branch is taken when the
    step is traced)."""
    from tony_tpu.ops import cache_attention as ca

    new = (20, 4, 5, 3)
    prompts = [_prompt(llama, n, 80 + i) for i, n in enumerate((9, 5, 7, 6))]
    want = [[int(t) for t in gen.generate(
        llama["params"], llama["cfg"], jnp.asarray([p], jnp.int32), n)[0]]
        for p, n in zip(prompts, new)]
    budget = llama["budget"]
    if kernel == "interpreted":
        monkeypatch.setattr(ca, "_INTERPRET", True)
        budget = 80
    seen = _recorded_dispatches(monkeypatch)
    engine = _engine(llama, token_budget=budget)
    handles = [engine.submit(p, n) for p, n in zip(prompts, new)]
    _run(engine)
    assert [h.tokens for h in handles] == want
    # slot 1 was parked between its occupants and read nothing there
    parked = [attend[1] for pos, attend in seen if pos[1] == budget - 1]
    assert parked and not any(parked)
    stats = engine.stats
    assert stats.decode_slot_steps_discarded_total == 0
    assert stats.decode_steps_overlapped_total >= stats.decode_steps_total - 1
    assert 0 < stats.cache_rows_read_total <= stats.cache_rows_budget_total
    assert stats.cache_rows_read_total == sum(
        int(-(-n // 16) * 16) for _, attend in seen for n in attend)


# -- a slot that does not ride moves no recurrent state (PR 38) --------------

@pytest.mark.parametrize("stepper", ["_step", "step"])
def test_a_slot_admitted_again_after_a_pause_serves_what_it_would_alone(
        model, monkeypatch, stepper):
    """A ends early and its slot sits parked for some steps beside B's
    stream (for the model with lightning layers: its state is dead and no
    step moves it any more); C is then admitted into the SAME slot. C's
    tokens and B's are those of each alone in a fresh engine and of
    offline `generate`: the pause changes nothing. The state counters,
    which only a model whose step moves the riders' state alone keeps:
    every rider of every dispatched step moved its state, and no other
    slot did."""
    seen = _recorded_dispatches(monkeypatch)
    budget, (pa, pb, pc) = model["budget"], model["prompts"]
    requests = [(_prompt(model, pa, 90), 3), (_prompt(model, pb, 91), 16),
                (_prompt(model, pc, 92), 8)]
    engine = _engine(model)
    step = getattr(engine, stepper)
    a, b = (engine.submit(p, n) for p, n in requests[:2])
    while not a.done.is_set():
        assert step()
    for _ in range(4):
        assert step()
    paused = [(pos, attend) for pos, attend in seen if pos[0] == budget - 1]
    assert len(paused) >= 4 and not any(att[0] for _, att in paused)
    c = engine.submit(*requests[2])
    step()
    assert engine._slots[0].handle is c and engine._slots[1].handle is b
    _run(engine, stepper)
    seen = list(seen)       # the engines below are recorded too
    alone = _by_hand(model, requests)
    assert [h.tokens for h in (a, b, c)] == [h.tokens for h in alone]
    for (prompt, new), h in zip(requests[1:], (b, c)):
        assert h.tokens == [int(t) for t in gen.generate(
            model["params"], model["cfg"],
            jnp.asarray([prompt], jnp.int32), new)[0]]
    snap = engine.snapshot()
    if not getattr(model["cfg"], "moves_state_by_riding", False):
        assert "state_slots_moved_total" not in snap
        assert "state_slots_total" not in snap
        return
    assert snap["state_slots_moved_total"] == (
        snap["decode_slot_steps_total"]
        + snap["decode_slot_steps_discarded_total"]) \
        == sum(int(np.count_nonzero(att)) for _, att in seen) > 0
    assert snap["state_slots_total"] == 2 * snap["decode_steps_total"] \
        == 2 * len(seen)
    assert snap["state_slots_moved_total"] < snap["state_slots_total"]
