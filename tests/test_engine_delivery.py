"""When a decode step's tokens reach the callers. The loop keeps one step
in flight: step N's tokens are read, and recorded, after step N+1 was
dispatched, and their waiters woken once step N+2 was (or before an
admission, or when nothing is active), never out of order, and all of them
by the time a caller's own `step()` returns. All tier-1 fast.
"""

from __future__ import annotations

import queue
import time

import jax
import numpy as np
import pytest

from tony_tpu.models.llama import get_config, llama_init
from tony_tpu.serve import engine as engine_mod
from tony_tpu.serve.engine import ContinuousBatchingEngine

pytestmark = pytest.mark.serving


@pytest.fixture(scope="module")
def model():
    cfg = get_config("tiny")
    return llama_init(cfg, jax.random.PRNGKey(0)), cfg


def _prompt(cfg, n, seed):
    rng = np.random.RandomState(seed)
    return [int(t) for t in rng.randint(0, cfg.vocab_size, size=n)]


def _engine(model, **kw):
    params, cfg = model
    return ContinuousBatchingEngine(params, cfg, n_slots=2, token_budget=32,
                                    queue_depth=8, **kw)


def _woken(handle):
    """What a waiter on the stream has been handed so far."""
    out = []
    while True:
        try:
            out.append(handle._queue.get_nowait())
        except queue.Empty:
            return out


def test_the_loops_step_records_a_token_and_wakes_its_waiter_a_step_later(
        model):
    engine = _engine(model)
    a = engine.submit(_prompt(model[1], 5, 1), 6)
    assert engine._step()               # admission (handed over) + dispatch
    assert len(a.tokens) == 1           # that step is in flight, unread
    assert _woken(a) == a.tokens[:1]
    assert engine._step()               # the next dispatch, then its read
    assert len(a.tokens) == 2
    assert _woken(a) == []              # recorded; no one woken yet
    assert engine._step()
    assert len(a.tokens) == 3
    assert _woken(a) == a.tokens[1:2]   # the step before's, not this one's
    engine._deliver()
    assert _woken(a) == a.tokens[2:3]
    engine._deliver()                   # nothing twice
    assert _woken(a) == []


def test_the_waiters_are_woken_after_the_next_dispatch(model, monkeypatch):
    """... and both before the read of the step in flight: an iteration is
    dispatch N+1, the wake-ups of N-1's tokens, the read of N."""
    engine = _engine(model)
    a = engine.submit(_prompt(model[1], 5, 2), 8)
    engine._step()
    engine._step()
    events = []
    program = engine_mod._decode_sample_step
    deliver, device_get = engine._deliver, jax.device_get

    def dispatch(*args, **kw):
        events.append("dispatch")
        return program(*args, **kw)

    def delivering():
        if engine._undelivered:
            events.append("wake")
        deliver()

    def reading(x):
        events.append("read")
        return device_get(x)

    monkeypatch.setattr(engine_mod, "_decode_sample_step", dispatch)
    monkeypatch.setattr(engine, "_deliver", delivering)
    monkeypatch.setattr(engine_mod.jax, "device_get", reading)
    engine._step()
    engine._step()
    assert events == ["dispatch", "wake", "read"] * 2
    assert a.finish_reason is None


def test_a_callers_own_step_hands_everything_over(model):
    engine = _engine(model)
    a = engine.submit(_prompt(model[1], 5, 3), 3)
    while engine.step():
        assert not engine._undelivered
        assert (_woken(a) or [None])[-1] is (
            engine_mod._DONE if a.finish_reason else a.tokens[-1])
    assert a.done.is_set() and a.finish_reason == "length"


def test_the_end_follows_the_last_token_and_is_not_seen_before_it(model):
    engine = _engine(model)
    a = engine.submit(_prompt(model[1], 5, 4), 3)
    engine._step()
    engine._step()                      # the last step a length allows
    assert engine._in_flight is not None and a.finish_reason is None
    assert engine._step()               # dispatches nothing, reads it:
    assert engine._in_flight is None    # the third token ends the request
    assert a.finish_reason == "length" and len(a.tokens) == 3
    assert not a.done.is_set()
    assert _woken(a) == a.tokens[:2]
    assert not engine._step()           # nothing active: handed over, idle
    assert a.done.is_set()
    assert _woken(a) == [a.tokens[2], engine_mod._DONE]


def test_an_admission_does_not_hold_back_the_step_before_it(
        model, monkeypatch):
    engine = _engine(model)
    a = engine.submit(_prompt(model[1], 5, 5), 8)
    engine._step()
    engine._step()
    engine._step()
    assert engine._undelivered
    admit = engine._admit
    seen = []

    def slow_admit(slot, handle, ph):
        seen.append((list(engine._undelivered), len(_woken(a))))
        admit(slot, handle, ph)

    monkeypatch.setattr(engine, "_admit", slow_admit)
    b = engine.submit(_prompt(model[1], 7, 6), 2)
    engine._step()
    assert seen == [([], 3)]            # all three of a's were out already
    assert _woken(b) == b.tokens[:1]    # b's first token is not deferred
    # the step in flight across the admission was read after it, and the
    # next one carries both streams
    assert len(a.tokens) == 4 and a.finish_reason is None
    assert len(engine._in_flight.riders) == 2


def test_a_cancelled_stream_ends_after_its_tokens(model):
    engine = _engine(model)
    a = engine.submit(_prompt(model[1], 5, 7), 8)
    engine._step()
    a.cancel()
    engine._step()                      # reaped; nothing active
    assert a.finish_reason == "cancelled"
    got = _woken(a)
    assert got == a.tokens + [engine_mod._DONE]
    assert a.done.is_set()


def test_stop_hands_over_what_the_loop_still_held(model):
    engine = _engine(model)
    a = engine.submit(_prompt(model[1], 5, 8), 8)
    engine._step()
    engine._step()
    held = len(engine._undelivered)
    assert held == 1 and engine._in_flight is not None
    engine.stop()                       # lands the step in flight, too
    assert len(a.tokens) == 3
    assert _woken(a) == a.tokens + [engine_mod._DONE]
    assert a.finish_reason == "shutdown"


@pytest.mark.parametrize("streams", [1, 2])
def test_the_running_loop_streams_what_stepping_by_hand_returns(
        model, streams):
    by_hand = _engine(model)
    want = [by_hand.submit(_prompt(model[1], 5 + i, 20 + i), 6)
            for i in range(streams)]
    while by_hand.step():
        pass
    engine = _engine(model)
    engine.start()
    try:
        handles = [engine.submit(_prompt(model[1], 5 + i, 20 + i), 6)
                   for i in range(streams)]
        for h, w in zip(handles, want):
            t0 = time.monotonic()
            assert list(h.iter_tokens(timeout=60)) == w.tokens
            assert h.done.is_set() and time.monotonic() - t0 < 60
    finally:
        engine.stop()
