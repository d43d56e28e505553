"""Continuous-batching inference engine over the static-shape decode core.

The concurrency-at-fixed-shapes discipline of the TPU training stack
(PAPERS.md: "Exploring the limits of Concurrency in ML Training on Google
TPUs") applied to online traffic: ONE persistent jitted decode step at a
fixed `(n_slots, token_budget)` shape, forever. Requests flow through it
without ever changing a shape:

- **Admission**: a request is admitted by prefilling its prompt (batch 1,
  the same `prefill` the offline path uses) and `dynamic_update_slice`-ing
  the resulting per-layer K/V into its slot's rows of the shared static
  cache `(L, n_slots, Hkv, token_budget, hd)`. One compile per distinct
  prompt length — exactly the offline `generate()` compile discipline.
- **Decode**: every engine step runs `decode_step` over ALL slots with
  per-row positions (each slot at its own sequence length); rows are
  independent, so an active slot's tokens are bit-identical to decoding
  that request alone — and therefore to the offline `generate()` oracle
  (pinned by tests/test_serve.py, staggered arrivals included). A slot
  whose token nobody will read (free, or past its stream's last step) is
  stepped too, attending to nothing: the step's attention moves only the
  rows below each slot's attend length (ops/cache_attention.py).
- **Latch + recycle**: per-slot eos/budget latches run host-side on the
  sampled tokens; the moment a row finishes its slot is recycled for the
  next queued request. Garbage K/V an idle slot may write is always masked
  (positions >= the slot's length) and overwritten by the next admission
  or decode write, so recycling needs no cache scrubbing.
- **One step in flight**: the sampled tokens stay on the device (a step's
  output vector is the next step's input as it is), so the loop dispatches
  step N+1 before it reads step N's tokens and the host's bookkeeping runs
  beside the device, not between two steps. A finish by length is known a
  step ahead; an `eos` is seen one step late (that slot rides in N+1 and
  the token is dropped, `decode_slot_steps_discarded_total`).

Composes with the offline path's levers: int8 KV cache (`quant_cache`,
shared `new_cache_rows`), int8 weights (quantized params pass straight
through), and the MoE/dense MLP dispatch in `models/generate._mlp` (MoE at
no-drop capacity routes each token independently, preserving row
independence).

Sampling: greedy (`temperature=0`) is THE contract — bit-identical to
offline greedy. Temperature/top-k/top-p are engine-wide settings (one
compiled step, not per-request variants); sampled streams fold the
draw's number into the engine's base key inside the jitted step and are
reproducible per (seed, admission order) but intentionally not pinned
against the offline oracle.

Prefix sharing (`prefix_sharing=True`, serve/kvcache.py): admission
first gathers any radix-indexed prefix pages into the slot row
on-device, then prefills ONLY the unmatched suffix (`_admit_step`'s
start operand), and seals the newly computed complete blocks back into
the page pool for the next sharer. Decode is untouched — same one
persistent step, zero recompiles after warmup. With sharing OFF
(default) the admission path is byte-identical to the pre-paging
engine; with sharing ON, greedy token streams are pinned identical
ON-vs-OFF by tests/test_kvcache.py.

Disaggregation: `role="prefill"` engines admit with `migrate_out=True`
and, instead of decoding, extract the slot's computed K/V + sampler
state into `handle.migration` (finish_reason "migrated"); a
`role="decode"` engine installs it via `submit_migration()` and decodes
from the exact transplanted bytes — greedy across a migrate is
bit-identical to decoding locally.
"""

from __future__ import annotations

import collections
import itertools
import logging
import os
import queue
import threading
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from tony_tpu import constants as C
from tony_tpu.models.generate import (
    _sample, _warn_moe_below_capacity, cache_by_kind, decode_step_counted,
    empty_cache, kind_module, prefill,
)
from tony_tpu.models.llama import LlamaConfig, Params
from tony_tpu.observability.spans import Phases, span
from tony_tpu.ops.cache_attention import read_chunk_rows
from tony_tpu.serve import kvcache as kvc

LOG = logging.getLogger(__name__)

_DONE = object()


class QueueFullError(RuntimeError):
    """Pending-request queue (or its token budget) is full — backpressure;
    the frontend maps this to HTTP 429."""


class DrainingError(RuntimeError):
    """The engine is draining (connection-draining contract, serve/router):
    in-flight requests finish, NEW submissions are refused — the frontend
    maps this to HTTP 503 and the fleet router routes around it."""


class BudgetExceededError(ValueError):
    """prompt + max_new_tokens exceeds the engine's per-slot token budget —
    a permanent rejection (429 retries would never help); HTTP 400."""


class RequestHandle:
    """Caller-side view of one request: a thread-safe token stream plus
    completion state and latency timestamps (TTFT / inter-token)."""

    def __init__(self, request_id: int, prompt: list[int],
                 max_new_tokens: int):
        self.request_id = request_id
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.tokens: list[int] = []
        # "eos"|"length"|"shutdown"|"cancelled"|"migrated"
        self.finish_reason: Optional[str] = None
        # disaggregation state: migrate_out marks a prefill-role request
        # whose decode is handed off; on finish_reason "migrated",
        # `migration` holds {"meta", "leaves"} for pack_migration. On the
        # decode side, `install` carries the unpacked payload until the
        # stepper installs it into a slot.
        self.migrate_out = False
        self.migration: Optional[dict] = None
        self.install: Optional[dict] = None
        self.submitted_at = time.monotonic()
        self.admitted_at: Optional[float] = None
        self.first_token_at: Optional[float] = None
        self.finished_at: Optional[float] = None
        # per-request latency breakdown, stamped by the engine: time spent
        # queued before a slot freed, and the admission prefill itself
        self.queue_wait_s: Optional[float] = None
        self.prefill_s: Optional[float] = None
        # prefill-phase split for the request trace: time spent matching/
        # gathering indexed prefix pages, and how many tokens matched
        self.kv_match_s: Optional[float] = None
        self.kv_matched_tokens = 0
        # True for a /v1/migrate install — its "prefill" is the row
        # install, traced as migrate.install instead of prefill_suffix
        self.migrated_in = False
        # request-trace carrier (observability/reqtrace.py): the frontend
        # attaches the RequestTrace + TraceContext so completion hooks
        # can record engine phases onto the SAME cross-process trace
        self.trace = None
        self.trace_ctx = None
        self.done = threading.Event()
        self.cancelled = threading.Event()
        self._queue: "queue.Queue" = queue.Queue()

    # engine side -------------------------------------------------------
    # A token or the end reaches the caller in two halves: the record
    # (`tokens`, the stamps, `finish_reason`) and the wake-up of whoever
    # waits on the stream. The engine's loop makes the second only after
    # it has dispatched the next step (`_deliver`), so the woken handler
    # thread runs beside the device and not before a dispatch.
    def _record(self, token: int, now: float) -> None:
        if self.first_token_at is None:
            self.first_token_at = now
        self.tokens.append(token)

    def _record_finish(self, reason: str, now: float) -> None:
        self.finish_reason = reason
        self.finished_at = now

    def _wake(self, item) -> None:
        if item is _DONE:
            self.done.set()
        self._queue.put(item)

    def _push(self, token: int, now: float) -> None:
        self._record(token, now)
        self._wake(token)

    def _finish(self, reason: str, now: float) -> None:
        self._record_finish(reason, now)
        self._wake(_DONE)

    # caller side -------------------------------------------------------
    def cancel(self) -> None:
        """Abandon this request: a pending request is dropped at admission
        time, an in-flight one frees its slot at the next step boundary —
        a timed-out or disconnected client must not keep the engine
        generating tokens nobody is waiting on."""
        self.cancelled.set()

    @property
    def ttft_s(self) -> Optional[float]:
        if self.first_token_at is None:
            return None
        return self.first_token_at - self.submitted_at

    @property
    def decode_s(self) -> Optional[float]:
        """Wall time spent decoding past the first token."""
        if self.first_token_at is None or self.finished_at is None:
            return None
        return self.finished_at - self.first_token_at

    def iter_tokens(self, timeout: Optional[float] = None):
        """Yield tokens as they are generated; returns on completion.
        Raises TimeoutError when the stream stalls past `timeout`."""
        while True:
            try:
                item = self._queue.get(timeout=timeout)
            except queue.Empty:
                raise TimeoutError(
                    f"request {self.request_id}: no token within "
                    f"{timeout}s") from None
            if item is _DONE:
                return
            yield item

    def result(self, timeout: Optional[float] = None) -> list[int]:
        """Block until the request finishes; returns all generated tokens."""
        if not self.done.wait(timeout=timeout):
            raise TimeoutError(f"request {self.request_id} not done "
                               f"within {timeout}s")
        return list(self.tokens)


@dataclass
class _Slot:
    index: int
    handle: Optional[RequestHandle] = None
    pos: int = 0          # cache position the next dispatched step writes at
    emitted: int = 0      # generated tokens so far (incl. the prefill one)
    dispatched: int = 0   # `emitted` plus the token of a step in flight
    last_emit_at: float = 0.0   # inter-token latency anchor

    @property
    def active(self) -> bool:
        return self.handle is not None

    @property
    def rides(self) -> bool:
        """Whether the next decode step makes a token for this slot: an
        active one whose stream the step in flight does not end by length
        (known a step ahead; an eos is not, and rides once more)."""
        return (self.handle is not None
                and self.dispatched < self.handle.max_new_tokens)


@dataclass
class _Flight:
    """A dispatched decode step whose tokens the host has not read."""
    tokens: jax.Array     # the step's sampled tokens, (n_slots,), on device
    # what the model counted on the device during the step (the module's
    # STEP_COUNTS), read back with the tokens; None for most models
    counts: Optional[jax.Array]
    # who held which slot when it was dispatched, and the row it wrote:
    # a token is booked only for a handle that still holds its slot
    riders: list[tuple[_Slot, RequestHandle, int]]
    # the iteration that dispatched it (`tony.engine.decode.dispatch`'s
    # `step`): what the `decode.wait` and `emit` spans that land it carry
    # as `lands`, an iteration later in the loop
    step: int


@dataclass
class EngineStats:
    """Aggregate serving metrics, guarded by the engine lock. Percentile
    sources are bounded deques — a gauge window, not an unbounded log."""
    tokens_emitted: int = 0
    requests_finished: int = 0
    # admission accounting: queue-eligible submissions that were accepted
    # vs shed with QueueFullError (the frontend's 429) — the first-class
    # SLI behind the reject-rate burn-rate alert rule. Cumulative
    # counters, never reset while the engine lives.
    requests_submitted: int = 0
    requests_rejected: int = 0
    started_at: float = field(default_factory=time.monotonic)
    ttft_s: collections.deque = field(
        default_factory=lambda: collections.deque(maxlen=512))
    itl_s: collections.deque = field(
        default_factory=lambda: collections.deque(maxlen=2048))
    # per-request phase breakdown (queue_wait / prefill; decode-per-token
    # is itl_s above) — same bounded-window discipline
    queue_wait_s: collections.deque = field(
        default_factory=lambda: collections.deque(maxlen=512))
    prefill_s: collections.deque = field(
        default_factory=lambda: collections.deque(maxlen=512))
    # disaggregation counters: requests handed off to a decode replica
    # (prefill role) / adopted from a prefill replica (decode role)
    migrated_out: int = 0
    migrated_in: int = 0
    # the loop's own counters (cumulative): decode steps dispatched, the
    # slots whose token was kept summed over them (their ratio is the mean
    # batch a step carried), and requests admitted into a slot (prefilled
    # or migrated in)
    decode_steps_total: int = 0
    decode_slot_steps_total: int = 0
    admissions_total: int = 0
    # the step in flight: steps dispatched while the one before had not
    # been read (over decode_steps_total: how often the pipeline engaged;
    # 0 for a caller that steps by hand), and slot-steps run for a stream
    # that had ended by the time their token was read (an eos is seen one
    # step late; a cancellation may be)
    decode_steps_overlapped_total: int = 0
    decode_slot_steps_discarded_total: int = 0
    # a model with sparse-attention layers (config.sparse_read_blocks): of
    # the blocks of context its decoded tokens had, summed over tokens (a
    # sparse layer each), how many one attended to — their ratio is how
    # sparse the cache reads were — and admissions whose prompt was short
    # enough to be attended densely
    sparse_blocks_attended_total: int = 0
    sparse_context_blocks_total: int = 0
    dense_path_admissions_total: int = 0
    # a model whose cache is K/V rows alone: of the rows the cache holds
    # (slots x token budget, a layer), summed over decode steps, how many a
    # step's attention read — each slot's attend length, 0 for one that
    # does not ride, rounded up to the kernel's chunk
    # (ops/cache_attention.py). Reckoned on the host by the TPU kernel's
    # rule, not read back from it (the jnp body of other platforms reads
    # the whole budget); their ratio is how much of the budget a step
    # still reads
    cache_rows_read_total: int = 0
    cache_rows_budget_total: int = 0
    # a model whose decode step reads and rewrites the recurrent state of
    # the slots that ride and of no other (config.moves_state_by_riding:
    # models/sala.py): slots whose state a step moved (those its `attend`
    # array names, every layer's slabs of each) and slots it was run
    # over, summed over the decode steps dispatched — their ratio is the
    # share of the state array a step still moves. Reckoned on the host
    state_slots_moved_total: int = 0
    state_slots_total: int = 0
    # a model with expert layers that counts on the device
    # (models/lfm2.py STEP_COUNTS): expert layers run summed over the
    # decode steps read (the layers a step has, reckoned here), and, READ
    # BACK from each step with its tokens, the experts that got at least
    # one row and the rows they got, summed over those layers. Their
    # ratios: the share of a layer's experts a step reads the weights of,
    # and the rows an expert hit serves
    moe_layer_steps_total: int = 0
    moe_experts_hit_total: int = 0
    moe_rows_total: int = 0
    # per decode iteration, the loop thread's time outside its wait on
    # the device: from the previous read of a step's tokens returning to
    # the next one starting (booking, release, reap, prepare, dispatch,
    # the wake-ups), less the admissions in between (`prefill_s` holds
    # those, device and host together). With a step in flight this is
    # the time that has to fit under a device step, not time added to
    # it. No sample for the first step after the engine sat idle.
    step_host_s: collections.deque = field(
        default_factory=lambda: collections.deque(maxlen=2048))


def _percentile(samples, q: float) -> Optional[float]:
    if not samples:
        return None
    ordered = sorted(samples)
    return ordered[min(len(ordered) - 1, int(q * len(ordered)))]


def _phase_percentiles(snap: dict, key: str, samples, scale: float = 1.0
                       ) -> None:
    """p50/p95/p99 of one latency phase into the snapshot (None-valued
    when the window is empty, so idle servers still expose the keys)."""
    for q, tag in ((0.50, "p50"), (0.95, "p95"), (0.99, "p99")):
        v = _percentile(samples, q)
        snap[f"{key}_{tag}"] = None if v is None else v * scale


# ---------------------------------------------------------------------------
# jitted kernels (module level: one compile cache per (config, shapes))
# ---------------------------------------------------------------------------

# the shared cache is DONATED through both jitted kernels: the caller
# rebinds self._cache to the output every call, so output and input are
# one buffer (on backends without buffer donation — CPU tests — jax warns
# and copies). Donation only aliases the two ends; whether the program
# between them copies the cache is the program's own doing: `decode_step`
# only reads it in its layer loop and writes the new rows in place after
# it (models/generate.py `write_cache_rows`), and tests/test_tpu_compile.py
# pins that in the compiled step (no slab-sized op, no cache-sized
# temporary — passing the slabs through the layer scan cost 41 of 58.8 ms
# a step under this same donation, PERF.md PR 27)
def _draw_key(key: jax.Array, draw: jax.Array, temperature: float):
    """The key of one sampling draw: the engine's base key with the draw's
    number folded in, inside the jitted program, so the host splits no key
    between two steps. Greedy sampling reads no key: both arguments are
    then unused by the program and never uploaded."""
    return jax.random.fold_in(key, draw) if temperature > 0.0 else key


@partial(jax.jit, static_argnames=("config", "temperature", "top_k",
                                   "top_p"), donate_argnames=("cache",))
def _decode_sample_step(params: Params, config: LlamaConfig, cache,
                        tokens: jax.Array, pos: jax.Array, key: jax.Array,
                        draw: jax.Array, temperature: float, top_k: int,
                        top_p: float, attend: Optional[jax.Array] = None):
    """One continuous-batching step: decode every slot's previous token at
    its own position, sample the next. ONE compile per (config, n_slots,
    token_budget) — slot occupancy, positions, and request boundaries are
    all data, never shapes. `tokens` is the vector the step (or admission)
    before returned, still on the device; the result is the next call's.
    `attend` is how many cached rows each slot attends to: its position,
    or 0 for a slot that does not ride, whose token nobody reads (absent:
    every slot's position). Returns (tokens, cache, what the model counted
    on the device during the step: None, no result at all, for a model
    that counts nothing)."""
    logits, cache, counts = decode_step_counted(params, config, cache,
                                                tokens, pos, attend)
    nxt = _sample(logits, temperature, top_k,
                  _draw_key(key, draw, temperature), top_p)
    return nxt, cache, counts


@partial(jax.jit, static_argnames=("config", "temperature", "top_k",
                                   "top_p", "quant_cache", "shared"),
         donate_argnames=("cache",))
def _admit_step(params: Params, config: LlamaConfig, cache,
                tokens: jax.Array, prompt: jax.Array, slot: jax.Array,
                key: jax.Array, draw: jax.Array, temperature: float,
                top_k: int, top_p: float, quant_cache: bool,
                start: jax.Array, shared: bool = False):
    """Admission: prefill one prompt (batch 1) and write its K/V (+ scales
    when int8) into the shared cache's `slot` row, and its first sampled
    token into the device's token vector. Returns (first sampled token,
    tokens, cache). One compile per distinct prompt length — the slot
    index is data.

    shared=False (the default engine path) is byte-identical to the
    pre-paging admission: full flash prefill of the whole prompt; `start`
    is an unused traced scalar. shared=True is the paged path: `prompt`
    is only the UNMATCHED SUFFIX, `start` the number of prefix tokens
    whose K/V the page gather already placed in rows [0, start) — the
    suffix prefill attends to them and writes rows [start, start+W).
    One compile per distinct suffix length."""
    if shared:
        logits, out = kvc.prefill_suffix(params, config, cache, prompt,
                                         start, slot, quant_cache)
    else:
        cache_len = cache["k"].shape[3]
        logits, pc = prefill(params, prompt[None, :], config, cache_len,
                             quant_cache=quant_cache)
        # what the admission writes: every leaf of the model's cache has
        # the slot on axis 1 (K/V rows (L, 1, Hkv, S, d); a model whose
        # cache is by layer kind adds leaves of its own)
        out = {}
        for name, arr in cache.items():
            row = pc[name].astype(arr.dtype)
            out[name] = lax.dynamic_update_slice_in_dim(arr, row, slot,
                                                        axis=1)
    tok0 = _sample(logits, temperature, top_k,
                   _draw_key(key, draw, temperature), top_p)[0]
    return tok0, tokens.at[slot].set(tok0), out


@jax.jit
def _seed_token(tokens: jax.Array, slot: jax.Array, token: jax.Array):
    """A migrated-in slot's next input token into the device's vector."""
    return tokens.at[slot].set(token)


def decode_step_cache_size() -> int:
    """Compile count of the persistent decode step (all configs) — the
    zero-recompile contract's measurement hook (tests/test_serve.py pins
    that a staggered workload adds no entries after warmup)."""
    return _decode_sample_step._cache_size()


def admit_step_cache_size() -> int:
    return _admit_step._cache_size()


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

class ContinuousBatchingEngine:
    """Slot-managed online decode over one shared static KV cache.

    Thread model: `submit()` is called from any number of frontend threads;
    a single loop thread (`start()`) — or a test driving `step()` directly —
    owns the device state. The lock guards only the pending queue, slot
    table, and stats; device arrays are touched exclusively by the stepper.
    """

    def __init__(self, params: Params, config: LlamaConfig,
                 n_slots: int = 4, token_budget: int = 0,
                 queue_depth: int = 64, temperature: float = 0.0,
                 top_k: int = 0, top_p: float = 1.0,
                 eos_id: Optional[int] = None, quant_cache: bool = False,
                 seed: int = 0, queue_token_budget: int = 0,
                 weights_generation: int = 0,
                 prefix_sharing: bool = False, kv_page_size: int = 16,
                 kv_pages: int = 0, role: str = "both"):
        if token_budget <= 0:
            token_budget = config.max_seq
        if token_budget > config.max_seq:
            raise ValueError(f"token_budget {token_budget} exceeds "
                             f"config.max_seq {config.max_seq}")
        # queued-WORK bound next to the request-count bound: half-budget
        # average request size by default, so a few near-budget requests
        # shed load as early as many small ones (a pure count bound lets
        # queue_depth maximal requests hide an unbounded latency backlog)
        if queue_token_budget <= 0:
            queue_token_budget = max(token_budget,
                                     queue_depth * token_budget // 2)
        self.queue_token_budget = queue_token_budget
        _warn_moe_below_capacity(config, who="serve")
        self.params = params
        self.config = config
        self.n_slots = n_slots
        self.token_budget = token_budget
        self.queue_depth = queue_depth
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.eos_id = eos_id
        self.quant_cache = quant_cache
        # disaggregated serving role: "prefill" replicas migrate decode
        # work out after admission, "decode" replicas accept /v1/migrate
        # installs, "both" (default) is the classic monolithic replica
        self.role = role if role in ("prefill", "decode", "both") else "both"
        if cache_by_kind(config) and (prefix_sharing or self.role != "both"):
            # a page of K/V rows is not a prefix of such a model: its
            # lightning layers' state after the prefix would have to be
            # kept a page too (a later PR: docs/SERVING.md)
            raise ValueError(
                "prefix_sharing and the prefill/decode roles (K/V "
                "migration) are refused for a model with recurrent state: "
                "a slot's cache is not a function of its K/V rows alone")
        self._sparse_reads = getattr(config, "sparse_read_blocks", None)
        self._cache = self._empty_cache()
        # rows a chunk of the decode step's cache read holds (0: the whole
        # budget is read); None for a cache by layer kind, read by its
        # model's own rule
        self._read_chunk = None if (
            cache_by_kind(config)
            and not getattr(config, "reads_cache_by_attend", False)) else \
            read_chunk_rows(token_budget, self._cache["k"].dtype)
        # whether the decode step moves only the riders' recurrent state
        self._state_by_riding = getattr(config, "moves_state_by_riding",
                                        False)
        # what the model's decode step counts on the device, by name, and
        # the expert layers a step runs
        self._step_counts = getattr(kind_module(config), "STEP_COUNTS", ())
        self._expert_layers = getattr(config, "n_expert_layers", 0) \
            if self._step_counts else 0
        # paged prefix-shared KV pool (serve/kvcache.py); None = sharing
        # OFF, which keeps the admission path byte-identical to the
        # pre-paging engine
        self.kv_pool: Optional[kvc.KVPagePool] = None
        if prefix_sharing:
            self.kv_pool = kvc.KVPagePool(
                config, token_budget=self.token_budget,
                page_size=kv_page_size if kv_page_size > 0 else 16,
                n_pages=kv_pages, n_slots=n_slots,
                quant_cache=quant_cache)
        self.prefix_sharing = self.kv_pool is not None
        # the base key; a draw (a decode step, an admission) folds its
        # number into it inside the jitted program (`_draw_key`)
        self._key = jax.random.PRNGKey(seed)
        self._draws = 0
        # each slot's last sampled token, on the device: a decode step's
        # result is the next one's input as it is, an admission writes its
        # first token into it, and the host reads a step's copy a step late
        self._tokens = jnp.zeros((n_slots,), jnp.int32)
        # the one decode step dispatched and not yet read (`_step`)
        self._in_flight: Optional[_Flight] = None
        self._slots = [_Slot(i) for i in range(n_slots)]
        self._pending: collections.deque[RequestHandle] = collections.deque()
        self._pending_tokens = 0   # queued prompt+max_new total
        self._next_id = itertools.count()
        self._lock = threading.Lock()
        self._work = threading.Event()      # submit() kicks the loop
        self._stop = threading.Event()
        # connection draining (fleet router contract): once set, submit()
        # refuses new work with DrainingError while in-flight requests run
        # to completion. An Event, not a locked bool: the router's load
        # probe reads it lock-free.
        self._draining = threading.Event()
        # weight-rollout epoch this replica serves (0 = unversioned): the
        # rolling-update coordinator admits a new-generation replica and
        # drains the old one; the load snapshot carries it so the router
        # can tell the two apart
        self.weights_generation = int(weights_generation)
        self._thread: Optional[threading.Thread] = None
        # chaos seam (constants.TEST_SERVE_DECODE_DELAY): a fixed
        # per-decode-step sleep, read ONCE here so the hot loop's test
        # hook is a float compare, not an env lookup
        try:
            self._test_decode_delay_s = max(0, int(
                os.environ.get(C.TEST_SERVE_DECODE_DELAY, "0")
                or 0)) / 1000.0
        except ValueError:
            self._test_decode_delay_s = 0.0
        self.stats = EngineStats()
        # step() calls so far (the `step` attribute of the loop's spans);
        # when the last read of a decode step's tokens returned (the
        # anchor of stats.step_host_s; None while nothing decodes) and
        # the seconds of admissions since
        self._steps = 0
        self._read_ended_at: Optional[float] = None
        self._admit_s = 0.0
        # (handle, token or _DONE) recorded and not yet handed over
        self._undelivered: list[tuple[RequestHandle, object]] = []
        # observability hook: called (outside the engine lock) with each
        # RequestHandle as it finishes — serve/frontend turns these into
        # request-trace hops
        self.on_request_finished: Optional[callable] = None

    def _empty_cache(self) -> dict[str, jax.Array]:
        """Zero cache in prefill's exact tree layout (quant included) so
        decode_step's structure-based int8 detection sees the same tree
        the offline path builds."""
        return empty_cache(self.config, self.n_slots, self.token_budget,
                           self.quant_cache)

    # -- intake ---------------------------------------------------------
    def submit(self, prompt: list[int], max_new_tokens: int,
               migrate_out: bool = False) -> RequestHandle:
        """Enqueue a request. Raises BudgetExceededError when it can never
        fit a slot, QueueFullError when the bounded queue (or its token
        budget) is full — the backpressure the frontend turns into 429.

        migrate_out=True (prefill-role frontends): after admission
        computes the prompt K/V and first token, the request finishes
        with reason "migrated" and `handle.migration` carries the
        decode handoff payload instead of decoding locally."""
        if max_new_tokens < 1:
            raise BudgetExceededError("max_new_tokens must be >= 1")
        if not prompt:
            raise BudgetExceededError("empty prompt")
        vocab = self.config.vocab_size
        if any(t < 0 or t >= vocab for t in prompt):
            # jax's gather would silently clamp an out-of-range id into a
            # wrong embedding — a tokenizer bug must be a 400, not garbage
            raise BudgetExceededError(
                f"prompt contains token ids outside [0, {vocab})")
        need = len(prompt) + max_new_tokens
        if need > self.token_budget:
            raise BudgetExceededError(
                f"prompt {len(prompt)} + max_new {max_new_tokens} exceeds "
                f"the per-slot token budget {self.token_budget}")
        if self._draining.is_set():
            # draining precedes stop: in-flight work finishes, new work is
            # refused so the router fails it over to a healthy replica
            raise DrainingError("engine is draining")
        with self._lock:
            if self._stop.is_set():
                raise RuntimeError("engine is stopped")
            if len(self._pending) >= self.queue_depth:
                self.stats.requests_rejected += 1
                raise QueueFullError(
                    f"request queue full ({self.queue_depth} pending)")
            if self._pending_tokens + need > self.queue_token_budget:
                self.stats.requests_rejected += 1
                raise QueueFullError(
                    f"queued token budget exhausted "
                    f"({self._pending_tokens} of "
                    f"{self.queue_token_budget} tokens pending)")
            self.stats.requests_submitted += 1
            handle = RequestHandle(next(self._next_id), list(prompt),
                                   max_new_tokens)
            handle.migrate_out = bool(migrate_out)
            self._pending.append(handle)
            self._pending_tokens += need
        self._work.set()
        return handle

    def submit_migration(self, meta: dict,
                         leaves: dict[str, np.ndarray]) -> RequestHandle:
        """Adopt a migrated request from a prefill replica: validate the
        K/V payload against this engine's cache layout and enqueue it;
        the stepper installs it into a slot with `install_rows` (no
        prefill is ever paid here). Same backpressure contract as
        submit() — 400/429/503 mapping is identical."""
        if cache_by_kind(self.config):
            raise ValueError(
                "migration is refused for a model with recurrent state: "
                "its K/V rows alone do not make a slot")
        prompt = [int(t) for t in meta.get("prompt") or []]
        max_new = int(meta.get("max_new_tokens", 0))
        pos = int(meta.get("pos", -1))
        tok0 = int(meta.get("tok0", -1))
        if not prompt or max_new < 1:
            raise BudgetExceededError("invalid migration metadata")
        if pos != len(prompt):
            raise BudgetExceededError(
                f"migration pos {pos} != prompt length {len(prompt)}")
        need = len(prompt) + max_new
        if need > self.token_budget:
            raise BudgetExceededError(
                f"migrated prompt {len(prompt)} + max_new {max_new} "
                f"exceeds the per-slot token budget {self.token_budget}")
        if set(leaves) != set(self._cache):
            raise BudgetExceededError(
                f"migration cache layout mismatch: payload "
                f"{sorted(leaves)}, serving {sorted(self._cache)}")
        for name, arr in self._cache.items():
            l, _, h, _, d = arr.shape
            leaf = leaves[name]
            if tuple(leaf.shape) != (l, h, pos, d):
                raise BudgetExceededError(
                    f"migration leaf {name} shape {tuple(leaf.shape)} != "
                    f"{(l, h, pos, d)}")
            if leaf.dtype != arr.dtype:
                raise BudgetExceededError(
                    f"migration leaf {name} dtype {leaf.dtype} != "
                    f"{arr.dtype}")
        if self._draining.is_set():
            raise DrainingError("engine is draining")
        with self._lock:
            if self._stop.is_set():
                raise RuntimeError("engine is stopped")
            if len(self._pending) >= self.queue_depth:
                self.stats.requests_rejected += 1
                raise QueueFullError(
                    f"request queue full ({self.queue_depth} pending)")
            if self._pending_tokens + need > self.queue_token_budget:
                self.stats.requests_rejected += 1
                raise QueueFullError(
                    f"queued token budget exhausted "
                    f"({self._pending_tokens} of "
                    f"{self.queue_token_budget} tokens pending)")
            self.stats.requests_submitted += 1
            handle = RequestHandle(next(self._next_id), prompt, max_new)
            handle.install = {"pos": pos, "tok0": tok0,
                              "emitted": int(meta.get("emitted", 1)),
                              "leaves": leaves}
            self._pending.append(handle)
            self._pending_tokens += need
        self._work.set()
        return handle

    def queue_size(self) -> int:
        with self._lock:
            return len(self._pending)

    def active_slots(self) -> int:
        with self._lock:
            return sum(1 for s in self._slots if s.active)

    # -- draining + load probe ------------------------------------------
    def begin_drain(self) -> None:
        """Enter the draining state: in-flight requests (and anything
        already queued) run to completion, new submissions raise
        DrainingError. Idempotent; the load snapshot flips `draining`
        immediately so the router's next probe routes around this
        replica."""
        if not self._draining.is_set():
            LOG.info("engine draining: refusing new work, %d pending / "
                     "%d active to finish", len(self._pending),
                     sum(1 for s in self._slots if s.active))
        self._draining.set()
        self._work.set()

    @property
    def draining(self) -> bool:
        return self._draining.is_set()

    def drained(self) -> bool:
        """True once a draining engine holds no pending or in-flight
        work — the point where a relaunch/preemption may stop it without
        failing any request."""
        with self._lock:
            idle = not self._pending
        return idle and not any(s.active for s in self._slots)

    def wait_drained(self, timeout: float) -> bool:
        """Bounded wait for drained() — the shutdown path's in-flight
        grace. Polling, not a condition: drain is a rare lifecycle edge
        and the stepper must never pay for its bookkeeping."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.drained():
                return True
            time.sleep(0.02)
        return self.drained()

    def load(self) -> dict:
        """The router's load probe: queue depth, free slots, draining
        state, weights generation. Deliberately LOCK-FREE — this is
        served per probe per router while the stepper holds the engine
        busy, and a momentarily stale count only costs one slightly
        uneven routing decision, never correctness (len() and attribute
        reads are atomic under the GIL; the hot path gains nothing to
        contend with)."""
        active = sum(1 for s in self._slots if s.handle is not None)
        load = {
            "queue_depth": len(self._pending),
            "slots_free": max(0, self.n_slots - active),
            "active_slots": active,
            "n_slots": self.n_slots,
            "draining": self._draining.is_set(),
            "weights_generation": self.weights_generation,
            "role": self.role,
            "token_budget": self.token_budget,
        }
        pool = self.kv_pool
        if pool is not None:
            # page-pool headroom + advertised prefix hashes: the router's
            # affinity source AND the load-score fix — a replica with
            # free slots but an exhausted (all-pinned) pool must not look
            # idle (pool fields are plain ints / an atomically-swapped
            # tuple, so this stays lock-free)
            load.update(pool.load_fields())
        return load

    # -- stepping -------------------------------------------------------
    def step(self) -> bool:
        """One engine iteration, with the decode step it dispatched landed
        and everything it produced handed to the callers before it
        returns: a caller that steps the engine itself sees each token on
        the step that made it. The loop thread calls `_step`, which leaves
        that step in flight and its wake-ups to the next iteration."""
        busy = self._step(land=True)
        self._deliver()
        return busy

    def _deliver(self) -> None:
        """Wake the callers of the tokens and endings recorded since the
        last call, in the order they were recorded."""
        undelivered, self._undelivered = self._undelivered, []
        for handle, item in undelivered:
            handle._wake(item)

    def _step(self, land: bool = False) -> bool:
        """One engine iteration: reap cancelled slots, admit as many queued
        requests as there are free slots, dispatch decode step N+1 over
        every slot that rides, wake the callers of what was recorded,
        then wait for step N's tokens and book them. Returns True when any
        work happened (the loop's idle signal).

        Exactly one decode step is in flight between two iterations of
        the loop thread, so the host's work runs beside the device. What
        step N+1 needs is known without N's tokens: its input tokens are
        N's result on the device; a riding slot's position is its last
        plus one; a finish by length is known from `slot.dispatched`;
        a cancellation was read in `reap`. Only an `eos` in N is seen a
        step late: that slot rides in N+1, and its token there is dropped
        when it is read (`_land`: the handle no longer holds the slot).
        `land=True` (a caller's own `step()`) lands N+1 before returning.

        What a dropped slot-step wrote is harmless, by the invariant the
        parked row relies on: a free slot's rows are masked for its next
        occupant until that occupant's own writes cover them. The late
        step wrote one row inside the old stream's budget, as does the
        step after a stream's last by length, in which the slot does not
        ride and stays at its next row; for a model whose cache is by
        layer kind it also wrote the slot's `tail` and `ck` rows (or its
        `conv` state), as every step does for a parked slot, and the late
        step, in which the slot still rode, advanced its lightning
        `state` (a step moves no state of a slot that does not ride:
        models/sala.py). The slot's next admission is dispatched after
        it, so device order puts the admission's writes last:
        `shared=False` replaces the slot's whole row of every leaf, the
        recurrent state included; `shared=True` (K/V leaves only) gathers
        pages into rows [0, start) and prefills [start, prompt), which
        are also all that `_seal_prefix` copies out; rows past the prompt
        stay masked until the new stream's decode reaches them, each
        written before it is read.

        A step's tokens are recorded when they are read and their callers
        woken a little later: before the next admission, else once the
        next decode step is dispatched, else when nothing is active. A
        handler thread takes the GIL to write its chunk; woken before a
        dispatch, each open stream kept the loop from it (a slope of
        0.11 ms a stream in `sala-longdoc`'s gaps: PERF.md, PR 30).

        The iteration is tiled by `tony.engine.*` spans on the profiler's
        clock (observability/spans.py; docs/OBSERVABILITY.md lists them):
        with a profile open, every device-idle instant falls in a named
        phase of the host's work."""
        self._steps += 1
        with Phases("tony.engine.step", step=self._steps) as ph:
            ph.enter("tony.engine.reap")
            reaped = False
            for slot in self._slots:
                if slot.active and slot.handle.cancelled.is_set():
                    self._finish_slot(slot, "cancelled", time.monotonic())
                    reaped = True
            ph.leave()
            before = self.stats.admissions_total
            t_admit = time.monotonic()
            admitted = self._admit_pending() or reaped
            self._admit_s += time.monotonic() - t_admit
            riders = [s for s in self._slots if s.rides]
            ph.note(active=len(riders),
                    admitted=self.stats.admissions_total - before)
            landing = []
            if self._in_flight is not None:
                landing, self._in_flight = [self._in_flight], None
            if not riders and not landing:
                self._read_ended_at = None
                self._deliver()
                return admitted
            if riders:
                ph.enter("tony.engine.decode.prepare")
                # every slot is stepped; one that does not ride stays where
                # it is (a freed one at its parked row, `_finish_slot`) and
                # attends to nothing, so the step reads none of its rows:
                # its token is thrown away. Fresh arrays a step: the call
                # may still be reading the ones before
                pos = np.fromiter((s.pos for s in self._slots), np.int32,
                                  self.n_slots)
                attend = np.fromiter(
                    (s.pos if s.rides else 0 for s in self._slots),
                    np.int32, self.n_slots)
                read = self._rows_read(attend)
                moved = int(np.count_nonzero(attend)) \
                    if self._state_by_riding else None
                # the step's own occupancy, on the span the profile pairs
                # with its device program
                ph.enter("tony.engine.decode.dispatch", riders=len(riders),
                         context_rows=int(attend.sum()))
                self._tokens, self._cache, counts = _decode_sample_step(
                    self.params, self.config, self._cache, self._tokens,
                    pos, self._key, self._next_draw(), self.temperature,
                    self.top_k, self.top_p, attend=attend)
                flight = _Flight(self._tokens, counts,
                                 [(s, s.handle, s.pos) for s in riders],
                                 self._steps)
                for slot in riders:
                    slot.pos += 1
                    slot.dispatched += 1
                with self._lock:
                    self.stats.decode_steps_total += 1
                    self.stats.decode_steps_overlapped_total += bool(landing)
                    if read is not None:
                        self.stats.cache_rows_read_total += read
                        self.stats.cache_rows_budget_total += (
                            self.n_slots * self.token_budget)
                    if moved is not None:
                        self.stats.state_slots_moved_total += moved
                        self.stats.state_slots_total += self.n_slots
                if land:
                    landing.append(flight)
                else:
                    self._in_flight = flight
            if not landing:
                self._deliver()     # the first step after an idle engine
            for flight in landing:
                self._land(flight, ph)
            ph.enter("tony.engine.release")
            # the read step's record dies here, inside a leaf, and not a
            # moment later at the return: freeing a device buffer releases
            # the GIL, and whatever thread wants it takes it before the
            # loop gets it back: a wait that would otherwise lie under no
            # span
            del landing, flight
            return True

    def _rows_read(self, attend: np.ndarray) -> Optional[int]:
        """Cache rows (a layer) a decode step with these attend lengths
        reads: each slot's length rounded up to the kernel's chunk (None
        for a cache by layer kind, which keeps no such count)."""
        chunk = self._read_chunk
        if chunk is None:
            return None
        if chunk == 0:
            return self.n_slots * self.token_budget
        return int(((attend + (chunk - 1)) // chunk).sum()) * chunk

    def _next_draw(self) -> np.int32:
        """The number of the next sampling draw (`_draw_key`)."""
        self._draws = (self._draws + 1) & 0x7FFFFFFF
        return np.int32(self._draws)

    def _land(self, flight: _Flight, ph: Phases) -> None:
        """Wait for a dispatched step's tokens (`decode.wait`, after the
        wake-ups the loop held back) and book them (`emit`). Both spans
        say which step they land (`lands`: the dispatching iteration's
        `step`), and `emit` what the model counted in it on the device
        (its STEP_COUNTS, under their own names)."""
        ph.enter("tony.engine.decode.wait", lands=flight.step)
        self._deliver()
        started = time.monotonic()
        if self._read_ended_at is not None:
            with self._lock:
                self.stats.step_host_s.append(
                    started - self._read_ended_at - self._admit_s)
        nxt_np = np.asarray(jax.device_get(flight.tokens))
        counted = {} if flight.counts is None else dict(zip(
            self._step_counts, map(int, jax.device_get(flight.counts))))
        if self._test_decode_delay_s > 0:
            # chaos seam: TEST_SERVE_DECODE_DELAY slows this replica's
            # decode by a fixed per-step delay — the slow-hop-attribution
            # e2e's guilty replica
            time.sleep(self._test_decode_delay_s)
        now = self._read_ended_at = time.monotonic()
        self._admit_s = 0.0
        ph.enter("tony.engine.emit", lands=flight.step, **counted)
        gaps, attended, context = [], 0, 0
        for slot, handle, pos in flight.riders:
            if slot.handle is not handle:
                continue    # the stream ended while this step was in flight
            token = int(nxt_np[slot.index])
            if self._sparse_reads is not None:
                a, c = self._sparse_reads(pos + 1)
                attended, context = attended + a, context + c
            slot.emitted += 1
            handle._record(token, now)
            self._undelivered.append((handle, token))
            gaps.append(now - slot.last_emit_at)
            slot.last_emit_at = now
            self._maybe_finish(slot, token, now)
        with self._lock:
            self.stats.tokens_emitted += len(gaps)
            self.stats.itl_s.extend(gaps)
            self.stats.decode_slot_steps_total += len(gaps)
            self.stats.decode_slot_steps_discarded_total += (
                len(flight.riders) - len(gaps))
            self.stats.sparse_blocks_attended_total += attended
            self.stats.sparse_context_blocks_total += context
            if counted:
                self.stats.moe_layer_steps_total += self._expert_layers
                for name, n in counted.items():
                    name += "_total"
                    setattr(self.stats, name, getattr(self.stats, name) + n)

    def _admit_pending(self) -> bool:
        admitted = False
        while self._pending:      # a peek: the dequeue below holds the lock
            free = next((s for s in self._slots if not s.active), None)
            if free is None:
                break
            self._deliver()       # not behind the seconds of an admission
            with Phases("tony.engine.admit") as ph:
                ph.enter("tony.engine.admit.prepare")
                with self._lock:
                    if not self._pending:
                        break
                    handle = self._pending.popleft()
                    self._pending_tokens -= (len(handle.prompt)
                                             + handle.max_new_tokens)
                ph.set(request_id=handle.request_id)
                ph.note(prompt_tokens=len(handle.prompt), slot=free.index)
                if handle.cancelled.is_set():
                    # dropped while still queued: no prefill is ever paid
                    handle._finish("cancelled", time.monotonic())
                elif handle.install is not None:
                    self._admit_migrated(free, handle, ph)
                else:
                    self._admit(free, handle, ph)
            admitted = True
        return admitted

    def _admit(self, slot: _Slot, handle: RequestHandle,
               ph: Phases) -> None:
        # phase stamps: the queue-wait phase ends the moment a free slot
        # dequeued this request; everything until the first sampled token
        # lands on the host is the prefill phase. `ph` is the admission's
        # span, its `prepare` leaf open since before the dequeue.
        t_dequeue = time.monotonic()
        handle.queue_wait_s = t_dequeue - handle.submitted_at
        pool = self.kv_pool
        start = 0
        depth = 0
        hashes: list[str] = []
        pinned: Optional[str] = None
        if pool is not None:
            # paged admission: gather the longest indexed prefix into the
            # slot row, prefill only the suffix. The match is capped so at
            # least one suffix token remains to produce the logits.
            hashes = kvc.chain_hashes(handle.prompt, pool.page_size)
            usable = (len(handle.prompt) - 1) // pool.page_size
            page_ids, depth = pool.match(hashes[:usable])
            handle.kv_match_s = time.monotonic() - t_dequeue
            if depth:
                pinned = hashes[depth - 1]
                table = np.full((pool.blocks_per_slot,),
                                kvc.SCRATCH_PAGE, np.int32)
                table[:depth] = page_ids
                self._cache = kvc.gather_pages(
                    self._cache, pool.pool, table, np.int32(slot.index))
                start = depth * pool.page_size
                handle.kv_matched_tokens = start
                handle.kv_match_s = time.monotonic() - t_dequeue
        # host arrays straight into the jitted call: an upload each, and
        # no eager program (a `jnp.int32(...)` is one) ahead of it. Behind
        # the decode step in flight by device order, so it overwrites what
        # that step wrote to this slot (`_step`)
        prompt = np.asarray(handle.prompt[start:], np.int32)
        ph.enter("tony.engine.admit.dispatch")
        tok0_dev, self._tokens, self._cache = _admit_step(
            self.params, self.config, self._cache, self._tokens, prompt,
            np.int32(slot.index), self._key, self._next_draw(),
            self.temperature, self.top_k, self.top_p, self.quant_cache,
            np.int32(start), pool is not None)
        ph.enter("tony.engine.admit.wait")
        tok0 = int(jax.device_get(tok0_dev))
        ph.enter("tony.engine.admit.book")
        if pool is not None:
            # the slot now holds the full prompt K/V: seal the complete
            # blocks the index lacks so the NEXT sharer hits, then
            # release the admission pin and account the reuse
            self._seal_prefix(slot, handle, hashes, depth)
            if pinned is not None:
                pool.unpin(pinned)
            pool.hit_tokens += start
            pool.miss_tokens += len(handle.prompt) - start
            if start:
                pool.req_hits += 1
            else:
                pool.req_misses += 1
        now = time.monotonic()
        handle.prefill_s = now - t_dequeue
        handle.admitted_at = now
        slot.handle = handle
        slot.pos = len(handle.prompt)
        slot.emitted = slot.dispatched = 1
        slot.last_emit_at = now
        handle._push(tok0, now)
        dense = self._sparse_reads is not None and \
            self.config.dense_context(len(handle.prompt))
        with self._lock:
            self.stats.admissions_total += 1
            self.stats.dense_path_admissions_total += int(dense)
            self.stats.tokens_emitted += 1
            self.stats.ttft_s.append(now - handle.submitted_at)
            self.stats.queue_wait_s.append(handle.queue_wait_s)
            self.stats.prefill_s.append(handle.prefill_s)
        LOG.debug("admitted request %d into slot %d (prompt %d, max_new "
                  "%d)", handle.request_id, slot.index, len(handle.prompt),
                  handle.max_new_tokens)
        if handle.migrate_out:
            done = ((self.eos_id is not None and tok0 == self.eos_id)
                    or handle.max_new_tokens <= 1)
            if not done:
                # hand the decode off: extract the slot's K/V rows
                # [0, pos) + sampler state, finish as "migrated", free
                # the slot immediately (the frontend relays the payload
                # to a decode replica)
                handle.migration = self._extract_migration(slot, handle,
                                                           tok0)
                with self._lock:
                    self.stats.migrated_out += 1
                self._finish_slot(slot, "migrated", now)
                return
        self._maybe_finish(slot, tok0, now)

    def _seal_prefix(self, slot: _Slot, handle: RequestHandle,
                     hashes: list[str], depth: int) -> None:
        """Copy the slot's freshly computed complete blocks beyond the
        matched depth out into pool pages and index them. Allocation
        failures (every page pinned/interior) skip sealing — reuse
        degrades, correctness never."""
        pool = self.kv_pool
        n_complete = min(len(handle.prompt) // pool.page_size,
                         pool.blocks_per_slot)
        if n_complete <= depth:
            return
        table = np.full((pool.blocks_per_slot,), kvc.SCRATCH_PAGE,
                        np.int32)
        parent = hashes[depth - 1] if depth else ""
        newly: list[str] = []
        for i in range(depth, n_complete):
            digest = hashes[i]
            if digest in pool._nodes:
                parent = digest
                continue
            pid = pool.allocate()
            if pid is None:
                break
            pool.register(parent, digest, pid, i + 1)
            # pin until the bytes are actually sealed: allocate() for a
            # later block must never evict a just-registered leaf and
            # hand its page out twice
            pool.pin(digest)
            table[i] = pid
            newly.append(digest)
            parent = digest
        if newly:
            pool.pool = kvc.seal_pages(pool.pool, self._cache, table,
                                       np.int32(slot.index))
            for digest in newly:
                pool.unpin(digest)

    def _extract_migration(self, slot: _Slot, handle: RequestHandle,
                           tok0: int) -> dict:
        """Host-side copy of the slot's computed K/V rows [0, pos) plus
        the sampler state a decode replica needs to continue exactly
        where this admission stopped (tok0's own K/V is written by the
        FIRST decode step, there as here). The read follows the admission
        on the device, and so the decode step in flight before it."""
        leaves = {}
        for name, arr in self._cache.items():
            row = np.asarray(jax.device_get(arr[:, slot.index]))
            leaves[name] = np.ascontiguousarray(row[:, :, :slot.pos])
        meta = {"prompt": list(handle.prompt),
                "max_new_tokens": handle.max_new_tokens,
                "pos": int(slot.pos), "tok0": int(tok0), "emitted": 1}
        return {"meta": meta, "leaves": leaves}

    def _admit_migrated(self, slot: _Slot, handle: RequestHandle,
                        ph: Phases) -> None:
        """Install a migrated-in request: pad the payload rows to the
        full budget, one fixed-shape install_rows, resume decode at pos.
        tok0 was already streamed to the client by the prefill replica —
        it is NOT re-pushed here; it seeds the next decode step."""
        t_dequeue = time.monotonic()
        handle.queue_wait_s = t_dequeue - handle.submitted_at
        handle.migrated_in = True
        install, handle.install = handle.install, None
        pos = install["pos"]
        rows = {}
        for name, arr in self._cache.items():
            l, _, h, s, d = arr.shape
            leaf = install["leaves"][name]
            full = np.zeros((l, 1, h, s, d), leaf.dtype)
            full[:, 0, :, :pos, :] = leaf
            rows[name] = jnp.asarray(full)
        slot_dev = np.int32(slot.index)
        ph.enter("tony.engine.admit.dispatch")
        self._cache = kvc.install_rows(self._cache, rows, slot_dev)
        self._tokens = _seed_token(self._tokens, slot_dev,
                                   np.int32(install["tok0"]))
        ph.enter("tony.engine.admit.book")
        now = time.monotonic()
        handle.prefill_s = now - t_dequeue
        handle.admitted_at = now
        slot.handle = handle
        slot.pos = pos
        slot.emitted = slot.dispatched = int(install.get("emitted", 1))
        slot.last_emit_at = now
        with self._lock:
            self.stats.queue_wait_s.append(handle.queue_wait_s)
            self.stats.prefill_s.append(handle.prefill_s)
            self.stats.migrated_in += 1
            self.stats.admissions_total += 1
        LOG.debug("installed migrated request %d into slot %d (pos %d)",
                  handle.request_id, slot.index, pos)
        if slot.emitted >= handle.max_new_tokens:
            self._finish_slot(slot, "length", now)

    def _maybe_finish(self, slot: _Slot, token: int, now: float) -> None:
        """Per-slot eos/length latch + immediate slot recycling."""
        reason = None
        if self.eos_id is not None and token == self.eos_id:
            reason = "eos"
        elif slot.emitted >= slot.handle.max_new_tokens:
            reason = "length"
        if reason is not None:
            self._finish_slot(slot, reason, now)

    def _finish_slot(self, slot: _Slot, reason: str, now: float) -> None:
        """Free a slot (eos/length latch, or a cancelled request) and
        recycle it immediately. A decode step in flight may still hold a
        token for the handle: `_land` drops it (`_step` says why what that
        step wrote does no harm)."""
        handle, slot.handle = slot.handle, None
        # park the freed slot's decode writes at the last budget row:
        # always masked for the next occupant until its own decode
        # overwrites it
        slot.pos = self.token_budget - 1
        handle._record_finish(reason, now)
        self._undelivered.append((handle, _DONE))
        with self._lock:
            self.stats.requests_finished += 1
        sink = self.on_request_finished
        if sink is not None:
            try:
                sink(handle)
            except Exception:  # noqa: BLE001 — observability never wedges
                LOG.debug("request-finished hook failed", exc_info=True)

    # -- lifecycle ------------------------------------------------------
    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(target=self._loop,
                                        name="serve-engine", daemon=True)
        self._thread.start()

    def _loop(self) -> None:
        from tony_tpu.observability.profiler import register_beacon
        # a wedged engine loop means every in-flight request hangs —
        # cadence is one idle backstop tick, so detection is fast
        beacon = register_beacon("serve-engine", 1.0)
        while not self._stop.is_set():
            beacon.beat()
            try:
                busy = self._step()
            except Exception:  # noqa: BLE001 — a poisoned step must not
                LOG.exception("engine step failed")    # wedge the server
                busy = False
            if not busy:
                with span("tony.engine.idle_wait"):
                    self._work.wait(timeout=0.02)
                    self._work.clear()
        beacon.idle()

    def stop(self) -> None:
        """Stop the loop and fail outstanding work (pending AND in-flight)
        with finish_reason='shutdown' so no caller blocks forever."""
        self._stop.set()
        self._work.set()
        joined = True
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            joined = not self._thread.is_alive()
            self._thread = None
        flight, self._in_flight = self._in_flight, None
        if flight is not None and joined:
            # the step the loop left in flight: its tokens were made, so
            # their streams get them before they end
            try:
                with Phases("tony.engine.step", step=self._steps) as ph:
                    self._land(flight, ph)
            except Exception:  # noqa: BLE001 — shutdown must not hang
                LOG.exception("the step in flight was not read")
        self._deliver()
        now = time.monotonic()
        with self._lock:
            pending = list(self._pending)
            self._pending.clear()
            self._pending_tokens = 0
        for handle in pending:
            handle._finish("shutdown", now)
        for slot in self._slots:
            if slot.active:
                handle, slot.handle = slot.handle, None
                handle._finish("shutdown", now)

    # -- observability --------------------------------------------------
    def snapshot(self) -> dict:
        """Serving gauges for /v1/metrics, the metrics-RPC pusher, and the
        bench: TTFT, inter-token latency, queue depth, slot occupancy,
        tokens/sec."""
        with self._lock:
            active = sum(1 for s in self._slots if s.active)
            depth = len(self._pending)
            elapsed = max(time.monotonic() - self.stats.started_at, 1e-9)
            snap = {
                "tokens_emitted": self.stats.tokens_emitted,
                "requests_finished": self.stats.requests_finished,
                "requests_submitted": self.stats.requests_submitted,
                "requests_rejected": self.stats.requests_rejected,
                "tokens_per_sec": self.stats.tokens_emitted / elapsed,
                "queue_depth": depth,
                "active_slots": active,
                "n_slots": self.n_slots,
                "slot_occupancy_pct": 100.0 * active / self.n_slots,
                "ttft_p50_s": _percentile(self.stats.ttft_s, 0.50),
                "ttft_p95_s": _percentile(self.stats.ttft_s, 0.95),
                "itl_p50_ms": None,
                "token_budget": self.token_budget,
                "draining": self._draining.is_set(),
                "weights_generation": self.weights_generation,
                "role": self.role,
                "migrated_out_total": self.stats.migrated_out,
                "migrated_in_total": self.stats.migrated_in,
                "decode_steps_total": self.stats.decode_steps_total,
                "decode_slot_steps_total":
                    self.stats.decode_slot_steps_total,
                "admissions_total": self.stats.admissions_total,
                "decode_steps_overlapped_total":
                    self.stats.decode_steps_overlapped_total,
                "decode_slot_steps_discarded_total":
                    self.stats.decode_slot_steps_discarded_total,
            }
            if self.kv_pool is not None:
                snap.update(self.kv_pool.stats_fields())
            if self._sparse_reads is not None:
                for name in ("sparse_blocks_attended_total",
                             "sparse_context_blocks_total",
                             "dense_path_admissions_total"):
                    snap[name] = getattr(self.stats, name)
            if self._read_chunk is not None:
                for name in ("cache_rows_read_total",
                             "cache_rows_budget_total"):
                    snap[name] = getattr(self.stats, name)
            if self._state_by_riding:
                for name in ("state_slots_moved_total", "state_slots_total"):
                    snap[name] = getattr(self.stats, name)
            if self._step_counts:
                for name in ("moe_layer_steps_total",
                             *(n + "_total" for n in self._step_counts)):
                    snap[name] = getattr(self.stats, name)
            itl = _percentile(self.stats.itl_s, 0.50)
            if itl is not None:
                snap["itl_p50_ms"] = itl * 1000.0
            # per-request phase breakdown: where a request's latency went
            # (queued behind other work / prefill compute / per-token
            # decode) — p50/p95/p99 each, the serving answer to "which
            # phase ate the time"
            _phase_percentiles(snap, "queue_wait_s",
                               self.stats.queue_wait_s)
            _phase_percentiles(snap, "prefill_s", self.stats.prefill_s)
            _phase_percentiles(snap, "decode_ms_per_token",
                               self.stats.itl_s, scale=1000.0)
            # the loop thread's time a decode iteration, outside its wait
            # on the device (/v1/metrics only: the counterpart of the
            # loop's spans)
            _phase_percentiles(snap, "step_host_ms",
                               self.stats.step_host_s, scale=1000.0)
            return snap

    def metrics(self) -> list[dict]:
        """snapshot() as AM metric dicts ({name, value}) — the shape
        train/metrics.py pushes and the MetricsStore ingests."""
        names = {
            "tokens_per_sec": "SERVING_TOKENS_PER_SEC",
            "queue_depth": "SERVING_QUEUE_DEPTH",
            "slot_occupancy_pct": "SERVING_SLOT_OCCUPANCY_PCT",
            "ttft_p50_s": "SERVING_TTFT_P50_S",
            "ttft_p95_s": "SERVING_TTFT_P95_S",
            "itl_p50_ms": "SERVING_ITL_P50_MS",
            "tokens_emitted": "SERVING_TOKENS_TOTAL",
            # admission counters: the reject-rate burn-rate rule's SLI
            "requests_submitted": "SERVING_SUBMITTED_TOTAL",
            "requests_rejected": "SERVING_REJECTED_TOTAL",
            # phase breakdown (p95s are the alerting-grade tails; the
            # full p50/p95/p99 set lives on /v1/metrics)
            "queue_wait_s_p50": "SERVING_QUEUE_WAIT_P50_S",
            "queue_wait_s_p95": "SERVING_QUEUE_WAIT_P95_S",
            "prefill_s_p50": "SERVING_PREFILL_P50_S",
            "prefill_s_p95": "SERVING_PREFILL_P95_S",
            "decode_ms_per_token_p50": "SERVING_DECODE_P50_MS",
            "decode_ms_per_token_p95": "SERVING_DECODE_P95_MS",
            # paged-KV reuse + disaggregation (absent keys — sharing OFF,
            # role "both" — are filtered by the None/missing guard below)
            "kv_hit_total": "SERVING_KV_HIT_TOTAL",
            "kv_miss_total": "SERVING_KV_MISS_TOTAL",
            "kv_evict_total": "SERVING_KV_EVICT_TOTAL",
            "kv_occupancy_pct": "SERVING_KV_OCCUPANCY_PCT",
            "kv_hit_rate_pct": "SERVING_KV_HIT_RATE_PCT",
            "migrated_out_total": "SERVING_MIGRATED_OUT_TOTAL",
            "migrated_in_total": "SERVING_MIGRATED_IN_TOTAL",
        }
        snap = self.snapshot()
        return [{"name": metric, "value": float(snap[key])}
                for key, metric in names.items()
                if snap.get(key) is not None]
