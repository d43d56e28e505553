"""Overlapped input pipeline contracts (docs/HOTLOOP.md):

- PrefetchIterator yields a byte-identical stream to the synchronous
  global_batch_iterator path (same seed/step/process_index determinism);
- an early close never leaks the producer thread;
- the device queue is bounded at `depth` (the producer blocks, it never
  runs ahead unboundedly);
- producer-side exceptions and exhaustion surface on the consumer;
- the vectorized synthetic_tokens matches the O(seq) loop reference
  bit-for-bit and beats it by >=5x host-side at long sequence lengths.
"""

import queue
import sys
import threading
import time

import numpy as np
import pytest

from tony_tpu.train.data import (
    PrefetchIterator, _affine_prefix_tokens, _synthetic_tokens_loop,
    global_batch_iterator,
    synthetic_linreg, synthetic_mnist, synthetic_tokens,
)


def _host(batch):
    return {k: np.asarray(v) for k, v in batch.items()}


# --------------------------------------------------------------------------
# PrefetchIterator
# --------------------------------------------------------------------------

def test_prefetch_byte_identical_to_sync_path():
    """Same (seed, step, process_index) source -> identical streams; the
    background thread must consume the local iterator strictly in order."""
    kw = dict(batch_size=4, seq_len=33, vocab_size=256, seed=5,
              process_index=2)
    sync = global_batch_iterator(synthetic_tokens(**kw))
    with PrefetchIterator(synthetic_tokens(**kw), depth=3) as pre:
        for _ in range(8):
            a, b = _host(next(sync)), _host(next(pre))
            assert a.keys() == b.keys()
            for k in a:
                assert a[k].dtype == b[k].dtype
                np.testing.assert_array_equal(a[k], b[k])


def test_prefetch_identical_for_all_synthetic_families():
    for make in (lambda: synthetic_mnist(8, seed=1),
                 lambda: synthetic_linreg(8, seed=1)):
        sync = global_batch_iterator(make())
        with PrefetchIterator(make()) as pre:
            for _ in range(3):
                a, b = _host(next(sync)), _host(next(pre))
                for k in a:
                    np.testing.assert_array_equal(a[k], b[k])


def test_prefetch_bounds_queue_depth():
    """With no consumer, the producer may be at most depth batches in the
    queue plus one in flight — never further into the source."""
    pulled = [0]

    def counting():
        while True:
            pulled[0] += 1
            yield {"x": np.zeros(4, np.float32)}

    with PrefetchIterator(counting(), depth=2,
                          transfer=lambda b: b) as pre:
        deadline = time.monotonic() + 2.0
        while pulled[0] < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.2)   # would overshoot here if the queue were unbounded
        assert pulled[0] <= 3, pulled[0]
        # draining frees slots and the producer advances again
        for _ in range(4):
            next(pre)
        deadline = time.monotonic() + 2.0
        while pulled[0] < 5 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert pulled[0] >= 5


def test_prefetch_early_close_joins_thread():
    """close() mid-stream (producer blocked on a full queue) must stop and
    join the thread — no leak, and it must be idempotent."""
    pre = PrefetchIterator(synthetic_tokens(2, 16, 64), depth=1,
                           transfer=lambda b: b)
    next(pre)
    thread = pre._thread
    assert thread.is_alive()
    pre.close()
    assert not thread.is_alive()
    pre.close()   # idempotent
    with pytest.raises(StopIteration):
        next(pre)
    assert all(t.name != "tony-prefetch" for t in threading.enumerate())


def test_prefetch_propagates_producer_exception():
    def boom():
        yield {"x": np.zeros(1)}
        raise RuntimeError("generator exploded")

    with PrefetchIterator(boom(), transfer=lambda b: b) as pre:
        next(pre)
        with pytest.raises(RuntimeError, match="generator exploded"):
            next(pre)


def test_prefetch_finite_source_stops_cleanly():
    src = [{"x": np.full(2, i, np.int32)} for i in range(3)]
    with PrefetchIterator(iter(src), transfer=lambda b: b) as pre:
        got = list(pre)
    assert [int(b["x"][0]) for b in got] == [0, 1, 2]


def test_prefetch_stall_accounting():
    with PrefetchIterator(synthetic_tokens(2, 8, 64),
                          transfer=lambda b: b) as pre:
        s0, n0 = pre.stall_snapshot()
        assert (s0, n0) == (0.0, 0)
        next(pre)
        next(pre)
        s1, n1 = pre.stall_snapshot()
        assert n1 == 2 and s1 >= 0.0


def test_prefetch_rejects_bad_depth():
    with pytest.raises(ValueError, match="depth"):
        PrefetchIterator(iter([]), depth=0)


def test_prefetch_close_hands_undelivered_batches_to_successor():
    """Batches the producer pulled from the shared source but never
    yielded survive close() on .leftover; a successor constructed with
    initial=leftover resumes the stream with no gap and no duplicates —
    regardless of how far the producer had run ahead."""
    src = iter([{"x": np.full(1, i, np.int32)} for i in range(6)])
    pre = PrefetchIterator(src, depth=2, transfer=lambda b: b)
    assert not pre.closed
    first = next(pre)
    assert int(first["x"][0]) == 0
    time.sleep(0.2)   # let the producer run ahead into the queue
    pre.close()
    assert pre.closed
    with PrefetchIterator(src, depth=2, transfer=lambda b: b,
                          initial=pre.leftover) as succ:
        rest = [int(b["x"][0]) for b in succ]
    assert rest == [1, 2, 3, 4, 5]


def test_prefetch_terminal_item_survives_get_timeout_race():
    """The lost-wakeup interleaving: the consumer's queue poll times out
    just as the producer enqueues its terminal item and exits. The final
    non-blocking drain must still observe it — a producer error must
    never be swallowed as clean exhaustion."""
    def boom():
        raise RuntimeError("terminal explosion")
        yield  # pragma: no cover — makes this a generator

    pre = PrefetchIterator(boom(), transfer=lambda b: b)
    pre._thread.join(2.0)
    assert not pre._thread.is_alive()
    real_get = pre._q.get

    def raced_get(*args, **kwargs):
        if kwargs.get("timeout") is not None:
            raise queue.Empty       # the poll that lost the race
        return real_get(*args, **kwargs)

    pre._q.get = raced_get
    try:
        with pytest.raises(RuntimeError, match="terminal explosion"):
            next(pre)
    finally:
        pre._q.get = real_get
        pre.close()


# --------------------------------------------------------------------------
# synthetic_tokens vectorization
# --------------------------------------------------------------------------

@pytest.mark.parametrize("batch,seq,vocab", [
    (4, 1, 7), (3, 37, 256), (2, 128, 2), (2, 100, 128256), (1, 64, 3),
])
def test_vectorized_tokens_match_loop_exactly(batch, seq, vocab):
    """The affine prefix scan must be BIT-identical to the loop reference
    — same RNG draw order, same int32 result — across vocab sizes
    including tiny moduli and odd (non-power-of-2) sequence lengths."""
    vec = synthetic_tokens(batch, seq, vocab, seed=9, process_index=3)
    ref = _synthetic_tokens_loop(batch, seq, vocab, seed=9,
                                 process_index=3)
    for _ in range(4):
        a, b = next(vec)["tokens"], next(ref)["tokens"]
        assert a.dtype == b.dtype == np.int32
        np.testing.assert_array_equal(a, b)


def test_vectorized_tokens_obey_recurrence():
    toks = next(synthetic_tokens(4, 50, 101, seed=2))["tokens"]
    assert ((0 <= toks) & (toks < 101)).all()
    diff = (toks[:, 1:] - 3 * toks[:, :-1]) % 101
    assert np.isin(diff, (0, 1)).all()


def _lines_run_for_a_batch(it, *functions) -> int:
    """Python lines executed inside `functions` while `it` makes one
    batch. Every such line is a bounded number of numpy calls over whole
    (batch, ...) arrays, so this counts the host's dispatches — the work
    — and times nothing."""
    codes = {f.__code__ for f in functions}
    lines = 0

    def tracer(frame, event, _arg):
        nonlocal lines
        if frame.f_code not in codes:
            return None
        if event == "line":
            lines += 1
        return tracer

    before = sys.gettrace()
    sys.settrace(tracer)
    try:
        next(it)
    finally:
        sys.settrace(before)
    return lines


def test_vectorized_tokens_speedup_at_long_seq():
    """What the vectorization is for, counted and not timed: the loop
    reference pays O(seq) numpy dispatches a batch, the scan
    O(log2(seq)) — at seq 2048 well over the 5x the change was accepted
    on, and one more round, not twice the work, for twice the length."""
    batch, vocab = 4, 128256

    def scan_lines(seq):
        return _lines_run_for_a_batch(
            synthetic_tokens(batch, seq, vocab), synthetic_tokens,
            _affine_prefix_tokens)

    def loop_lines(seq):
        return _lines_run_for_a_batch(
            _synthetic_tokens_loop(batch, seq, vocab),
            _synthetic_tokens_loop)

    rounds = 11                                     # log2(2048)
    scan, loop = scan_lines(2048), loop_lines(2048)
    assert loop >= 2 * 2048                         # a step: `for` + update
    assert scan <= 5 * rounds + 20, scan
    assert loop / scan >= 5.0, (loop, scan)
    per_round = scan - scan_lines(1024)
    assert 0 < per_round <= 5
    assert scan_lines(4096) - scan == per_round
    assert loop_lines(4096) >= 2 * loop - 20
