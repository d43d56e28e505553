"""Data pipelines: synthetic generators for benchmarks/tests + shard-aware
batching + the overlapped device prefetcher.

The reference's examples downloaded MNIST inside user scripts; in this
zero-egress build the equivalent workloads run on synthetic data with a
learnable structure (so loss curves actually descend and E2E tests can
assert learning, not just execution). Batches are host-local: each process
generates its per-process shard deterministically from (seed, step,
process_index) — the data-parallel equivalent of the reference's per-worker
input pipelines.

Hot-loop overlap (docs/HOTLOOP.md): `PrefetchIterator` runs batch
generation AND the host->device transfer on a background thread with an
N-deep device-resident queue, so input work overlaps the previous train
step instead of serializing with it — the first-order TPU MFU lever per
"Exploring the limits of Concurrency in ML Training on Google TPUs"
(arxiv 2011.03641). `global_batch_iterator` remains the synchronous
reference path; both yield byte-identical streams from the same source
iterator (pinned by tests/test_prefetch.py).
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from typing import Any, Callable, Iterator, Optional

import jax
import numpy as np

LOG = logging.getLogger(__name__)


def _affine_prefix_tokens(first: np.ndarray, noise: np.ndarray,
                          vocab_size: int) -> np.ndarray:
    """Exact vectorized evaluation of the token recurrence
    ``toks[:, t+1] = (3*toks[:, t] + noise[:, t]) % vocab_size``.

    Each step is the affine map f_t(x) = (3x + n_t) mod V; the prefix
    composition g_t = f_{t-1} ∘ … ∘ f_0 is itself affine (A_t, B_t), so
    toks[:, t] = (A_t * toks[:, 0] + B_t) mod V. A Hillis-Steele doubling
    scan composes all prefixes in ceil(log2(S)) vectorized rounds —
    ~2*log2(S) numpy dispatches instead of the loop version's S, which is
    the dominant host cost at long sequence lengths. int64 intermediates
    keep every product < V^2 exact (V < ~3e9), and a mod after every
    round prevents overflow, so the result is bit-identical to the loop.
    """
    b, s = noise.shape
    v = int(vocab_size)
    a = np.full((b, s), 3 % v, dtype=np.int64)
    acc = noise.astype(np.int64) % v
    shift = 1
    while shift < s:
        hi = a[:, shift:]
        acc[:, shift:] = (hi * acc[:, :-shift] + acc[:, shift:]) % v
        a[:, shift:] = (hi * a[:, :-shift]) % v
        shift *= 2
    toks = np.empty((b, s + 1), np.int32)
    toks[:, 0] = first
    toks[:, 1:] = (a * first.astype(np.int64)[:, None] + acc) % v
    return toks


def synthetic_tokens(batch_size: int, seq_len: int, vocab_size: int,
                     seed: int = 0, process_index: int = 0
                     ) -> Iterator[dict[str, np.ndarray]]:
    """Markov-ish token stream: next token = (3*tok + noise) % vocab, so a
    language model can reduce loss well below uniform. Vectorized via the
    closed-form affine prefix scan (bit-identical to the loop reference
    `_synthetic_tokens_loop`, same RNG draw order)."""
    rng = np.random.default_rng(seed * 1_000_003 + process_index)
    while True:
        first = rng.integers(0, vocab_size, batch_size)
        noise = rng.integers(0, 2, (batch_size, seq_len))
        yield {"tokens": _affine_prefix_tokens(first, noise, vocab_size)}


def _synthetic_tokens_loop(batch_size: int, seq_len: int, vocab_size: int,
                           seed: int = 0, process_index: int = 0
                           ) -> Iterator[dict[str, np.ndarray]]:
    """Reference O(seq_len)-dispatch implementation of synthetic_tokens —
    the oracle the vectorized scan must match bit for bit, and the
    O(seq) side of its count of work (tests/test_prefetch.py)."""
    rng = np.random.default_rng(seed * 1_000_003 + process_index)
    while True:
        toks = np.empty((batch_size, seq_len + 1), np.int32)
        toks[:, 0] = rng.integers(0, vocab_size, batch_size)
        noise = rng.integers(0, 2, (batch_size, seq_len))
        for t in range(seq_len):
            toks[:, t + 1] = (toks[:, t] * 3 + noise[:, t]) % vocab_size
        yield {"tokens": toks}


def synthetic_mnist(batch_size: int, seed: int = 0, process_index: int = 0
                    ) -> Iterator[dict[str, np.ndarray]]:
    """Class-conditional Gaussian images: learnable by the MLP. Zero-copy
    assembly: noise is drawn directly in float32 and added in place into
    the fancy-index result — no post-hoc astype copies."""
    rng = np.random.default_rng(seed * 7_777_777 + process_index)
    protos = np.random.default_rng(42).normal(size=(10, 784)).astype(
        np.float32)
    while True:
        labels = rng.integers(0, 10, batch_size, dtype=np.int32)
        images = protos[labels]            # fancy index: fresh f32 buffer
        images += 0.5 * rng.standard_normal((batch_size, 784),
                                            dtype=np.float32)
        yield {"images": images, "labels": labels}


def synthetic_linreg(batch_size: int, num_features: int = 10, seed: int = 0,
                     process_index: int = 0) -> Iterator[dict[str, np.ndarray]]:
    rng = np.random.default_rng(seed * 31_337 + process_index)
    true_w = np.random.default_rng(7).normal(size=num_features).astype(
        np.float32)
    while True:
        x = rng.standard_normal((batch_size, num_features),
                                dtype=np.float32)
        y = x @ true_w                     # f32 all the way, no astype copy
        y += 0.01 * rng.standard_normal(batch_size, dtype=np.float32)
        yield {"x": x, "y": y}


def device_put_batch(batch: dict, mesh=None) -> dict:
    """Transfer ONE host batch to device: plain device_put on a single
    process; multi-host, form global arrays from process-local shards
    (jax.make_array_from_process_local_data). The single transfer
    implementation shared by the synchronous and prefetched paths — the
    two streams stay byte-identical by construction."""
    if jax.process_count() == 1:
        return {k: jax.device_put(v) for k, v in batch.items()}
    from jax.sharding import NamedSharding, PartitionSpec as P
    assert mesh is not None, "multi-host batching needs the mesh"
    sharding = NamedSharding(mesh, P(("dp", "fsdp")))
    return {
        k: jax.make_array_from_process_local_data(sharding, v)
        for k, v in batch.items()
    }


def global_batch_iterator(local_iter: Iterator[dict], mesh=None
                          ) -> Iterator[dict]:
    """Synchronous reference path: assemble per-process local batches into
    global sharded arrays, one at a time, on the caller's thread.
    PrefetchIterator is the overlapped equivalent."""
    for batch in local_iter:
        yield device_put_batch(batch, mesh)


_DONE = object()


class PrefetchIterator:
    """Overlapped input pipeline: a background thread pulls host batches
    from `local_iter`, transfers each to device (`device_put_batch`), and
    keeps up to `depth` already-on-device batches queued. Host generation
    and H2D copies therefore overlap the previous train step instead of
    serializing with it.

    Contracts (pinned by tests/test_prefetch.py):
      - **Determinism**: the single producer thread consumes `local_iter`
        strictly in order, so the yielded stream is byte-identical to
        ``global_batch_iterator(local_iter, mesh)``.
      - **Bounded**: at most `depth` batches are queued on device; the
        producer blocks (never drops, never runs ahead unboundedly) when
        the queue is full. Device residency is up to depth+1 batches
        (the queue plus the producer's in-flight transfer).
      - **Clean shutdown**: `close()` (or context-manager exit) stops and
        joins the producer thread even mid-put; an early close never
        leaks the thread.
      - **No lost batches**: batches the producer already pulled from the
        source but never yielded (queued + in-flight) are retained in
        order on `.leftover` after `close()`; a successor constructed
        with ``initial=old.leftover`` resumes the shared source stream
        with no gap (the trainer's re-setup/resume path relies on this).
      - **Error transparency**: a producer-side exception is re-raised on
        the consumer's next `next()`.

    Stall accounting: `stall_s` accumulates wall time the consumer spent
    blocked inside `next()` and `batches` counts yields — the source of
    the goodput ledger's `input_stall` phase (a healthy overlapped
    pipeline shows ~0 ms/step after the pipeline-fill first batch).
    """

    def __init__(self, local_iter: Iterator[dict], mesh=None,
                 depth: int = 2,
                 transfer: Optional[Callable[[dict], Any]] = None,
                 initial: Any = ()):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self.depth = int(depth)
        self._transfer = (transfer if transfer is not None
                          else lambda b: device_put_batch(b, mesh))
        self._local_iter = local_iter
        self._q: queue.Queue = queue.Queue(maxsize=self.depth)
        self._stop = threading.Event()
        self._closed = False
        self.stall_s = 0.0
        self.batches = 0
        from tony_tpu.observability.metrics import REGISTRY
        self._stall_counter = REGISTRY.counter(
            "tony_prefetch_stall_seconds_total")
        # already-transferred batches a predecessor never yielded
        # (its .leftover) — served first, ahead of this queue
        self._initial: list = list(initial)
        self._spill: list = []    # producer's in-flight batch on close
        self.leftover: list = []  # populated by close(), in order
        self._thread = threading.Thread(
            target=self._produce, name="tony-prefetch", daemon=True)
        self._thread.start()

    # -- producer ------------------------------------------------------
    def _produce(self) -> None:
        try:
            for batch in self._local_iter:
                item = self._transfer(batch)
                if not self._offer(item):
                    # closed mid-stream: the batch was already pulled
                    # from the shared source — hand it to close() so a
                    # successor sees no gap
                    self._spill.append(item)
                    return
            self._offer(_DONE)
        except BaseException as e:  # noqa: BLE001 — surfaced on next()
            self._offer(e)

    def _offer(self, item) -> bool:
        """put() that stays responsive to close(): the bounded-queue block
        polls the stop event instead of parking forever."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    # -- consumer ------------------------------------------------------
    def __iter__(self) -> "PrefetchIterator":
        return self

    def __next__(self):
        if self._closed:
            raise StopIteration
        if self._initial:
            self.batches += 1
            return self._initial.pop(0)
        t0 = time.perf_counter()
        while True:
            try:
                item = self._q.get(timeout=0.1)
                break
            except queue.Empty:
                if self._closed:
                    raise StopIteration from None
                if not self._thread.is_alive():
                    # the producer always enqueues a terminal item
                    # (batch, _DONE, or its exception) before exiting;
                    # it may have landed just after this poll timed
                    # out, so one final non-blocking drain must look
                    # before concluding exhaustion — otherwise a
                    # producer error is swallowed as clean StopIteration
                    try:
                        item = self._q.get_nowait()
                        break
                    except queue.Empty:
                        raise StopIteration from None
        stalled = time.perf_counter() - t0
        self.stall_s += stalled
        # self-health: stall seconds into the process registry so a
        # starved input pipeline shows up on any scrape of this process
        # (an in-process locked float add — no RPC, no I/O, ~µs)
        self._stall_counter.inc(stalled)
        if item is _DONE:
            self._closed = True
            raise StopIteration
        if isinstance(item, BaseException):
            self._closed = True
            raise item
        self.batches += 1
        return item

    def stall_snapshot(self) -> tuple[float, int]:
        """(stall_s, batches) — diff two snapshots around a timed region
        to get the region's input stall (excludes pipeline fill)."""
        return self.stall_s, self.batches

    @property
    def closed(self) -> bool:
        return self._closed

    # -- lifecycle -----------------------------------------------------
    def close(self, timeout: float = 5.0) -> None:
        """Stop the producer and join its thread. Idempotent; safe to
        call with the producer blocked on a full queue (it polls the stop
        event) or mid-transfer. Undelivered batches — unserved `initial`
        batches, the queue's contents, and the producer's in-flight
        batch — are retained in order on `.leftover` so a successor
        (``initial=self.leftover``) resumes the source stream with no
        gap."""
        self._closed = True
        self._stop.set()
        # join FIRST (the producer unparks on the stop event within its
        # 0.05s poll), so the queue and spill are quiescent when drained
        if self._thread.is_alive():
            self._thread.join(timeout)
            if self._thread.is_alive():
                # producer wedged in a slow transfer past the timeout:
                # its in-flight batch cannot be collected, so .leftover
                # may be one batch short — say so rather than let a
                # successor resume with a silent gap
                LOG.warning(
                    "prefetch producer did not exit within %.1fs; "
                    "leftover batches may be incomplete", timeout)
        kept, self._initial = self._initial, []
        try:
            while True:
                item = self._q.get_nowait()
                if item is not _DONE and not isinstance(item,
                                                        BaseException):
                    kept.append(item)
        except queue.Empty:
            pass
        kept.extend(self._spill)
        self._spill = []
        self.leftover.extend(kept)

    def __enter__(self) -> "PrefetchIterator":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):
        try:
            self.close(timeout=0.2)
        except Exception:  # noqa: BLE001 — interpreter shutdown
            pass
