"""Trainer: the user-process entry the orchestrator's JAX runtime launches.

Boot sequence inside a task container:
1. `jax.distributed.initialize` from the env the TaskExecutor rendered
   (JAX_COORDINATOR_ADDRESS / JAX_PROCESS_ID / JAX_NUM_PROCESSES —
   tony_tpu/executor/runtimes.py `_jax_env`), the TPU-native analogue of
   the reference examples reading TF_CONFIG/RANK (SURVEY.md §3.3).
2. Build the mesh from TPU_MESH_SHAPE/TPU_MESH_AXES (mesh_from_env), shard
   params with the model's logical axes, and jit the train step under the
   ambient mesh.
3. Resume from the latest checkpoint if one exists (AM-retry survival:
   ATTEMPT_NUMBER advances, model state comes back from disk), then step,
   log, and checkpoint on the configured cadence.
"""

from __future__ import annotations

import itertools
import logging
import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Iterator, Optional

import jax
import optax

from tony_tpu import constants as C
from tony_tpu.parallel import mesh_from_env, shard_pytree
from tony_tpu.train.checkpoint import latest_step, restore_checkpoint
from tony_tpu.train.data import PrefetchIterator, global_batch_iterator
from tony_tpu.train.step import make_train_step

LOG = logging.getLogger(__name__)


class TrainerPreempted(BaseException):
    """Raised by the Trainer's SIGTERM handler in the main thread:
    checkpoint-then-evict preemption (or a real TPU maintenance/spot
    eviction — the handler is signal-driven, not arbiter-specific).
    BaseException so user-level `except Exception` blocks can't swallow
    the drain; run() converts it into an emergency checkpoint +
    SystemExit(EXIT_PREEMPTED)."""


def maybe_initialize_distributed() -> None:
    """Call jax.distributed.initialize iff the orchestrator rendered a
    multi-process env; single-process runs skip it. Idempotent: user code
    may validate the mesh env before Trainer.setup() calls this again
    (jax raises on a second initialize)."""
    num = int(os.environ.get(C.JAX_NUM_PROCESSES, "1"))
    if num <= 1:
        return
    if jax.distributed.is_initialized():
        return
    coordinator = os.environ[C.JAX_COORDINATOR_ADDRESS]
    process_id = int(os.environ[C.JAX_PROCESS_ID])
    LOG.info("jax.distributed.initialize(%s, num=%d, id=%d)",
             coordinator, num, process_id)
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num, process_id=process_id)


@dataclass
class TrainerConfig:
    num_steps: int = 100
    log_every: int = 10
    checkpoint_every: int = 0            # 0 = only at the end
    checkpoint_dir: str = ""             # "" = no checkpointing
    learning_rate: float = 3e-4
    warmup_steps: int = 10
    weight_decay: float = 0.01
    seed: int = 0
    optimizer: Optional[optax.GradientTransformation] = None
    # microbatch gradient accumulation: batch dim split into this many
    # scan slices, one optimizer update on the mean gradient (train/step.py)
    grad_accum: int = 1
    # f32 master weights for bf16 params (train/precision.py): updates
    # accumulate in f32 so tiny-lr steps don't underflow the bf16 ULP
    master_weights: bool = False
    # held-out evaluation cadence: every N train steps run `eval_batches`
    # batches from eval_data_iter through a jitted loss-only step and log
    # the mean (0 = no eval; requires eval_data_iter on the Trainer)
    eval_every: int = 0
    eval_batches: int = 1
    # overlapped input pipeline (docs/HOTLOOP.md): depth of the
    # background device-prefetch queue. None = TONY_PREFETCH_DEPTH env
    # (default 2); 0 = synchronous global_batch_iterator (debug knob)
    prefetch_depth: Optional[int] = None
    # training FLOPs per token for MFU accounting (model config's
    # flops_per_token(seq); 0 = MFU not reported). Throughput
    # (tokens/sec/chip) is derived from batch shapes regardless.
    flops_per_token: float = 0.0
    # checkpoint retention: committed step dirs kept after each commit
    # (never the step this run restored from). None = the
    # TONY_CHECKPOINT_KEEP env the executor renders from
    # tony.checkpoint.keep (default 3); 0 = keep everything.
    checkpoint_keep: Optional[int] = None
    extra: dict = field(default_factory=dict)


class Trainer:
    def __init__(self, loss_fn: Callable[[Any, Any], jax.Array],
                 init_fn: Callable[[jax.Array], Any],
                 data_iter: Iterator[Any],
                 config: TrainerConfig,
                 param_axes: Optional[Any] = None,
                 eval_data_iter: Optional[Iterator[Any]] = None,
                 loss_takes_mesh: bool = False):
        # loss_takes_mesh: the loss needs the runtime mesh (pipelined
        # losses take mesh=...) — it's only known at setup() once
        # jax.distributed is up, so Trainer binds it there
        self.loss_takes_mesh = loss_takes_mesh
        self.loss_fn = loss_fn
        self.init_fn = init_fn
        self.data_iter = data_iter
        self.eval_data_iter = eval_data_iter
        self.last_eval_loss: Optional[float] = None
        self.config = config
        self.param_axes = param_axes
        self.mesh = None
        self.step = 0
        self.params = None
        self.opt_state = None
        self.last_loss: Optional[float] = None
        self.metrics_history: list[dict] = []
        self._checkpointer = None
        # the step this run restored from — pinned against retention GC
        # (still the only rollback target until newer commits exist)
        self._restore_pinned: Optional[int] = None
        # set by the SIGTERM-driven emergency path (read by callers that
        # want to distinguish a preempted exit from a completed run)
        self.preempted = False

    # ------------------------------------------------------------------
    def setup(self) -> None:
        # the drain contract arms as early as possible: a SIGTERM during
        # setup/restore still routes through the emergency path instead
        # of the default kill (run() re-installs for setup()-skipping
        # callers)
        self._install_sigterm_handler()
        # lifecycle tracing: parented under the executor's user_process
        # span via the env it rendered; spans ship through the reporter's
        # non-blocking queue — the hot loop never gains an RPC
        from tony_tpu.observability.trace import SpanRecorder
        self._tracer = SpanRecorder.from_env(
            os.environ,
            task_id=(f"{os.environ.get(C.JOB_NAME, '')}:"
                     f"{os.environ.get(C.TASK_INDEX, '0')}"
                     if os.environ.get(C.JOB_NAME) else ""),
            attempt=int(os.environ.get(C.TASK_ATTEMPT, "0") or 0))
        # goodput ledger (observability/perf.py): every wall-clock second
        # of this process lands in exactly one phase. One ledger per
        # process — a re-setup() (session retry) keeps accounting on the
        # same clock, it just transitions back to "init".
        from tony_tpu.observability.perf import GoodputLedger
        if getattr(self, "ledger", None) is None:
            # seeded with the executor-accounted localization/barrier
            # phases, so this one ledger covers the whole task attempt
            self.ledger = GoodputLedger.from_env(os.environ)
        else:
            self.ledger.transition("init")
        setup_span = self._tracer.start("trainer_setup")
        try:
            self._setup_inner()
        except BaseException:
            self._tracer.end(setup_span, "ERROR")
            raise
        self._tracer.end(setup_span, attrs={"resumed_step": self.step})
        self._flush_spans()

    def _flush_spans(self) -> None:
        tracer = getattr(self, "_tracer", None)
        reporter = getattr(self, "_metrics_reporter", None)
        if tracer is not None and reporter is not None and tracer.enabled:
            reporter.report_spans(tracer.drain())

    def _setup_inner(self) -> None:
        maybe_initialize_distributed()
        # persistent XLA compile cache (utils/compilecache.py): applied
        # before any jit below, so the Nth identical trainer skips the
        # cold compile — the warm-bring-up third of the cold-start work
        from tony_tpu.utils.compilecache import enable_compile_cache
        enable_compile_cache(jax)
        # device evidence AFTER distributed init — jax.devices() here
        # would otherwise initialize the local backend first and make a
        # later jax.distributed.initialize() raise on multi-worker runs
        from tony_tpu.train.metrics import log_devices
        log_devices(LOG)
        # MFU against a peak the table knows, or none: the CPU reports no
        # MFU, and an accelerator the table does not know fails HERE, at
        # setup, not at the first log boundary
        device = jax.local_devices()[0]
        self._peak_flops = 0.0
        if device.platform != "cpu" and self.config.flops_per_token > 0:
            from tony_tpu.observability.perf import peak_flops
            self._peak_flops = peak_flops(device)
        self._maybe_start_profiler()
        from tony_tpu.train.metrics import TpuMetricsReporter
        self._metrics_reporter = TpuMetricsReporter()
        # on-demand profiler capture (observability/perf.py): the request
        # file is polled at log boundaries; the finished artifact rides
        # the metrics RPC back to the AM. Rebuilt on re-setup so publish
        # binds the fresh reporter (the AM dedups request ids anyway).
        from tony_tpu.observability.perf import ProfileCapture
        self._profile = ProfileCapture(
            cwd=os.getcwd(),
            publish=self._metrics_reporter.report_profile_done)
        self._tokens_per_batch = getattr(self, "_tokens_per_batch", 0)
        self._last_stall_s = 0.0
        # chaos seam (TEST_TRAINER_STEP_DELAY, rendered per-task by the
        # executor): a fixed per-step host sleep that turns this task
        # into a steady-state straggler for the AM's skew analyzer
        self._test_step_delay_s = float(
            os.environ.get(C.TRAINER_STEP_DELAY_MS, "0") or 0) / 1000.0
        self.mesh = mesh_from_env()
        LOG.info("mesh: %s over %d devices", dict(self.mesh.shape),
                 self.mesh.devices.size)
        # bind into a local, never back onto self.loss_fn: a second
        # setup() (session retry) would otherwise stack a duplicate
        # mesh= kwarg onto the already-bound partial
        loss_fn = self.loss_fn
        if self.loss_takes_mesh:
            from functools import partial as _partial
            loss_fn = _partial(loss_fn, mesh=self.mesh)
        self._bound_loss_fn = loss_fn
        cfg = self.config
        if cfg.optimizer is not None:
            self.optimizer = cfg.optimizer
        else:
            schedule = optax.warmup_cosine_decay_schedule(
                0.0, cfg.learning_rate, max(1, cfg.warmup_steps),
                max(cfg.num_steps, cfg.warmup_steps + 1))
            self.optimizer = optax.adamw(schedule,
                                         weight_decay=cfg.weight_decay)
        if cfg.master_weights:
            from tony_tpu.train.precision import with_f32_master
            self.optimizer = with_f32_master(self.optimizer)
        self.train_step = make_train_step(
            self._bound_loss_fn, self.optimizer, grad_accum=cfg.grad_accum,
            # the master consumes f32 grads: don't quantize the
            # f32-accumulated mean back to bf16 at the interface
            emit_accum_dtype=cfg.master_weights,
            # XProf step annotations: traces attribute host stalls to the
            # exact step they delayed (docs/HOTLOOP.md)
            annotate=True)

        resume = (latest_step(cfg.checkpoint_dir)
                  if cfg.checkpoint_dir else None)
        params = self.init_fn(jax.random.PRNGKey(cfg.seed))
        if self.param_axes is not None:
            params = shard_pytree(params, self.param_axes, self.mesh)
        else:
            # no sharding rules -> replicate over the whole mesh (a bare
            # device_put would pin single-device, clashing with the
            # ambient-mesh jit and with template-based restore)
            from jax.sharding import NamedSharding, PartitionSpec
            params = jax.device_put(
                params, NamedSharding(self.mesh, PartitionSpec()))
        self.params = params
        # where the largest parameter lives: "everything on the first
        # chip" must be readable from the log of a multi-chip run
        name, big = max(jax.tree_util.tree_leaves_with_path(params),
                        key=lambda kv: kv[1].size)
        LOG.info("param %s %s: shards %s, sharded over %d of %d mesh "
                 "devices", jax.tree_util.keystr(name), big.shape,
                 big.sharding.shard_shape(big.shape),
                 len({str(s.index) for s in big.addressable_shards}),
                 self.mesh.devices.size)
        # explicit out_shardings on the optimizer init: propagation alone
        # may leave the masters/Adam moments replicated (observed on the
        # v5p AOT compile) — at 8B that's the difference between fitting
        # and OOM
        from jax.sharding import NamedSharding as NS
        from tony_tpu.parallel.sharding import (
            make_partition_spec, opt_state_specs,
        )
        if self.param_axes is not None:
            pspecs = make_partition_spec(self.param_axes, mesh=self.mesh)
        else:
            from jax.sharding import PartitionSpec
            pspecs = jax.tree.map(lambda _: PartitionSpec(), self.params)
        ospecs = opt_state_specs(
            jax.eval_shape(self.optimizer.init, self.params), pspecs)
        with jax.set_mesh(self.mesh):
            opt_state = jax.jit(
                self.optimizer.init,
                out_shardings=jax.tree.map(
                    lambda s: NS(self.mesh, s), ospecs))(self.params)
        self.opt_state = opt_state
        if resume is not None:
            # template restore: each target shard reads only the saved
            # regions it overlaps (mmap) — no host ever holds a full leaf,
            # and the checkpoint reshards onto this run's mesh for free
            LOG.info("resuming from checkpoint step %d", resume)
            self._restore_pinned = resume
            self.ledger.transition("checkpoint_restore")
            with self._tracer.span("checkpoint_restore",
                                   attrs={"step": resume}):
                state = restore_checkpoint(
                    cfg.checkpoint_dir, resume,
                    template={"params": self.params,
                              "opt_state": self.opt_state, "step": 0})
            self.ledger.transition("init")
            self.params = state["params"]
            self.opt_state = state["opt_state"]
            self.step = int(state["step"])
        # re-seat the XProf annotation counter so trace step numbers
        # line up with training steps across AM retries — including a
        # checkpoint-less re-setup() where self.step was retained but
        # make_train_step rebuilt the wrapper at 0 (no-op when fresh)
        self.train_step.step_num = self.step
        # Overlapped input pipeline: background host generation + H2D
        # transfer, N batches deep on device (docs/HOTLOOP.md). Bind into
        # a separate attribute — a second setup() (session retry) must
        # not wrap the wrapper (the outer one would feed already-global
        # arrays into make_array_from_process_local_data); close the old
        # prefetcher first so its thread is released, and carry its
        # undelivered batches into the successor — they were already
        # pulled from the shared self.data_iter, so dropping them would
        # silently skip up to depth+1 batches across a retry.
        old = getattr(self, "_global_data_iter", None)
        # sync-path leftovers live on self._carry (consumed in place),
        # prefetch-path leftovers on the closed iterator — exactly one
        # of the two is non-empty, and either survives ANOTHER re-setup
        carry: list = list(getattr(self, "_carry", ()))
        if isinstance(old, PrefetchIterator):
            old.close()
            carry = old.leftover + carry
        depth = cfg.prefetch_depth
        if depth is None:
            depth = int(os.environ.get("TONY_PREFETCH_DEPTH", "2"))
        if depth > 0:
            self._carry = []
            self._global_data_iter = PrefetchIterator(
                self.data_iter, self.mesh, depth=depth, initial=carry)
        else:
            self._carry = carry

            def _sync_with_carry():
                while self._carry:
                    yield self._carry.pop(0)
                yield from global_batch_iterator(self.data_iter,
                                                 self.mesh)

            self._global_data_iter = _sync_with_carry()
        if cfg.eval_every and self.eval_data_iter is not None:
            from tony_tpu.train.step import make_eval_step
            self.eval_step = make_eval_step(self._bound_loss_fn)
            # materialize a FIXED eval set once: successive eval_loss
            # values are then comparable across steps (and across
            # AM-retry resumes — a streaming iterator would restart and
            # score different batches after a resume). "Once" includes
            # across a re-setup(): rebuilding would draw the NEXT
            # batches from the partially-consumed iterator and silently
            # swap the held-out set. Materialization rides the same
            # prefetcher so generation overlaps the H2D copies, then the
            # temporary thread is closed.
            if getattr(self, "_eval_set", None) is None:
                n = max(1, cfg.eval_batches)
                # islice caps the pull at exactly n: the producer would
                # otherwise run ahead and silently advance a shared
                # eval_data_iter past the batches actually kept
                with PrefetchIterator(
                        itertools.islice(self.eval_data_iter, n),
                        self.mesh, depth=n) as stream:
                    self._eval_set = [next(stream) for _ in range(n)]

    def _perf_metrics(self) -> list[dict]:
        """Log-boundary perf accounting (never per-step): carve the
        prefetch stall counter's fresh seconds out of the open train_step
        phase, derive interval step-time / throughput / MFU, and return
        the goodput-ledger gauges for the metrics push. The only device
        interaction is reading array shapes — no sync."""
        from tony_tpu.observability.perf import mfu_pct
        now = time.monotonic()
        snap = getattr(self._global_data_iter, "stall_snapshot", None)
        if snap is not None:
            stall_s, _ = snap()
            if stall_s > self._last_stall_s:
                # stall always comes out of train_step, never the open
                # phase — the end-of-run flush already sits in idle
                self.ledger.carve("input_stall",
                                  stall_s - self._last_stall_s,
                                  source="train_step")
            self._last_stall_s = stall_s
        phases = self.ledger.snapshot()["phases"]
        out = self.ledger.metrics()
        prev_t = getattr(self, "_perf_t0", None)
        prev_step = getattr(self, "_perf_step0", self.step)
        if prev_t is not None and self.step > prev_step and now > prev_t:
            dt = now - prev_t
            d_steps = self.step - prev_step
            # step time excludes eval/checkpoint time spent inside the
            # interval (ledger phase deltas) — the SLO watchdog must not
            # read a periodic eval boundary as a step-time regression.
            # Throughput below stays on wall dt: achieved tokens/sec is
            # the honest number, stalls included.
            prev_phases = getattr(self, "_perf_phases0", {})
            overhead = sum(
                phases.get(p, 0.0) - prev_phases.get(p, 0.0)
                for p in ("eval", "checkpoint_save", "checkpoint_restore"))
            step_dt = max(dt - max(0.0, overhead), 1e-9)
            out.append({"name": "TRAIN_STEP_TIME_MS",
                        "value": round(1000.0 * step_dt / d_steps, 3)})
            if self._tokens_per_batch:
                # batch shapes are GLOBAL under the prefetch path, so the
                # per-chip rate divides by the global device count
                tok_s = (self._tokens_per_batch * d_steps / dt
                         / max(1, jax.device_count()))
                out.append({"name": "TRAIN_TOKENS_PER_SEC_PER_CHIP",
                            "value": round(tok_s, 2)})
                if getattr(self, "_peak_flops", 0.0) > 0:
                    out.append({"name": "TRAIN_MFU_PCT",
                                "value": round(mfu_pct(
                                    tok_s, self.config.flops_per_token,
                                    peak=self._peak_flops), 3)})
        self._perf_t0, self._perf_step0 = now, self.step
        self._perf_phases0 = phases
        return out

    def _evaluate(self) -> float:
        """Mean loss over the fixed held-out eval set (params only — no
        gradients, no optimizer state touched). Losses accumulate ON
        DEVICE; the single host read happens once at the end, so an
        N-batch eval costs one sync, not N."""
        total = None
        for batch in self._eval_set:
            loss = self.eval_step(self.params, batch)
            total = loss if total is None else total + loss
        return float(total) / len(self._eval_set)

    # ------------------------------------------------------------------
    def run(self) -> float:
        """Train to num_steps; returns the final loss.

        The hot loop is sync-free (docs/HOTLOOP.md): the loss stays a
        device array between optimizer updates — no `float()` forces a
        host<->device barrier on the current step. Logging is one
        interval LATENT: at each log boundary the PREVIOUS boundary's
        retained loss is fetched (the device is log_every steps past it,
        so the read returns immediately) and the current one is queued.
        The final boundary and the final loss flush after the loop."""
        if self.params is None:
            self.setup()
        self._install_sigterm_handler()
        if getattr(self, "ledger", None) is None:
            # params injected by hand (setup() skipped): account from here
            from tony_tpu.observability.perf import GoodputLedger
            self.ledger = GoodputLedger(phase="init")
            self._tokens_per_batch = 0
            self._last_stall_s = 0.0
        it = self._global_data_iter
        if (isinstance(it, PrefetchIterator) and it.closed
                and self.step < self.config.num_steps):
            # a previous run() completed and released its prefetch
            # thread; a num_steps-bump re-run restarts one, resuming
            # the shared source stream from the retained leftovers
            # (the step guard keeps an exact-resume no-op run() from
            # spinning up a pipeline it would immediately tear down)
            self._global_data_iter = PrefetchIterator(
                self.data_iter, self.mesh, depth=it.depth,
                initial=it.leftover)
        cfg = self.config
        loss = None
        pending = None   # (step, device loss, elapsed_s) awaiting fetch

        def _flush(p) -> None:
            step, dev_loss, dt = p
            loss_f = float(dev_loss)
            self.last_loss = loss_f
            self.metrics_history.append(
                {"step": step, "loss": loss_f, "elapsed_s": dt})
            LOG.info("step %d loss %.4f (%.1fs)", step, loss_f, dt)

        # first-step span: dispatch of step 1 includes the jit compile —
        # the single largest cold-start cost the waterfall must show.
        # Ends after the first dispatch returns (no device sync added).
        tracer = getattr(self, "_tracer", None)
        first_span = (tracer.start("first_step")
                      if tracer is not None and self.step < cfg.num_steps
                      else None)
        # goodput: dispatch of step 1 is the compile phase; a tracer-less
        # run (params injected by hand) goes straight to train_step
        profile = getattr(self, "_profile", None)
        if self.step < cfg.num_steps:
            self.ledger.transition("compile" if first_span is not None
                                   else "train_step")
        try:
            with jax.set_mesh(self.mesh):
                t0 = time.monotonic()
                while self.step < cfg.num_steps:
                    batch = next(self._global_data_iter)
                    if first_span is not None:
                        self._log_step_kernels(batch)
                    self.params, self.opt_state, loss = self.train_step(
                        self.params, self.opt_state, batch)
                    self.step += 1
                    if getattr(self, "_test_step_delay_s", 0.0):
                        # compiled-in fault injection, like the executor's
                        # TEST_* hooks — zero cost when unset
                        time.sleep(self._test_step_delay_s)
                    if profile is not None and profile.active:
                        profile.on_step()
                    if not self._tokens_per_batch:
                        from tony_tpu.observability.perf import \
                            tokens_in_batch
                        self._tokens_per_batch = tokens_in_batch(batch)
                    if first_span is not None:
                        # a jit dispatch returns once the program is
                        # compiled and enqueued: trace + compile seconds
                        # (or the persistent cache's load), not the step
                        LOG.info("first step dispatched in %.1fs "
                                 "(trace + compile)",
                                 time.monotonic() - t0)
                        tracer.end(first_span,
                                   attrs={"step": self.step})
                        first_span = None
                        self._flush_spans()
                        self.ledger.transition("train_step")
                    if cfg.log_every and self.step % cfg.log_every == 0:
                        if pending is not None:
                            _flush(pending)
                        pending = (self.step, loss,
                                   time.monotonic() - t0)
                        self._metrics_reporter.report(
                            extra=self._perf_metrics())
                        if profile is not None:
                            profile.poll()
                    if (cfg.eval_every
                            and self.eval_data_iter is not None
                            and self.step % cfg.eval_every == 0):
                        self.ledger.transition("eval")
                        self.last_eval_loss = self._evaluate()
                        self.ledger.transition("train_step")
                        self.metrics_history.append(
                            {"step": self.step,
                             "eval_loss": self.last_eval_loss})
                        LOG.info("step %d eval_loss %.4f", self.step,
                                 self.last_eval_loss)
                    if (cfg.checkpoint_dir and cfg.checkpoint_every
                            and self.step % cfg.checkpoint_every == 0):
                        self._checkpoint()
                if pending is not None:
                    _flush(pending)
                    pending = None
                if loss is not None:   # loop may no-op on exact resume
                    self.last_loss = float(loss)
                    from tony_tpu.train.metrics import peak_hbm_bytes
                    peak = peak_hbm_bytes()
                    if peak is not None:
                        LOG.info("peak HBM in use %.2f GiB of %.2f GiB "
                                 "(memory_stats peak_bytes_in_use, "
                                 "bytes_limit)", peak[0] / 2 ** 30,
                                 peak[1] / 2 ** 30)
                if cfg.checkpoint_dir and loss is not None:
                    self._checkpoint(final=True)
                elif self._checkpointer is not None:
                    self._checkpointer.close()
                    self._checkpointer = None
        except BaseException as e:
            # emergency save: the SIGTERM-driven drain (TrainerPreempted
            # — checkpoint-then-evict preemption, TPU maintenance, spot
            # eviction) AND any unhandled mid-run exception land here,
            # so a run that dies mid-epoch keeps its progress instead of
            # only its cadence checkpoints. Best-effort by construction:
            # the save must never mask the real error.
            preempting = isinstance(e, TrainerPreempted)
            self._emergency_checkpoint(
                reason="preemption" if preempting else type(e).__name__)
            if preempting:
                self.preempted = True
                LOG.warning("preempted at step %d — emergency checkpoint "
                            "committed; exiting %d", self.step,
                            C.EXIT_PREEMPTED)
                raise SystemExit(C.EXIT_PREEMPTED) from e
            raise
        finally:
            # an error mid-loop must not lose the already-queued log
            # boundary the synchronous loop would have recorded (the
            # read may itself fail if the device is wedged — best-effort)
            if pending is not None:
                try:
                    _flush(pending)
                except Exception:  # noqa: BLE001
                    LOG.debug("could not flush pending log boundary",
                              exc_info=True)
            # on completion AND on error: release the prefetch thread
            # and the metrics push worker (both idempotent). Undelivered
            # batches stay on the closed iterator's .leftover, so a
            # num_steps-bump re-run() — or a retry after the error —
            # revives the pipeline above with no gap in the stream.
            if isinstance(self._global_data_iter, PrefetchIterator):
                self._global_data_iter.close()
            if first_span is not None:   # error before the first step
                tracer.end(first_span, "ERROR")
            self._flush_spans()
            # close the goodput books: the run is over, remaining wall
            # time is idle, and the final ledger ships with the last push
            # (best-effort — accounting must never mask the real error)
            try:
                self.ledger.transition("idle")
                self._metrics_reporter.report(extra=self._perf_metrics())
            except Exception:  # noqa: BLE001
                LOG.debug("final goodput report failed", exc_info=True)
            self._metrics_reporter.close()
        return self.last_loss

    def _log_step_kernels(self, batch) -> None:
        """Say which attention / RMSNorm branch the step about to run was
        lowered with: the Pallas TPU kernels by name (ops/attention.py
        kernel_counts), all zero where the jnp paths were taken. The jit
        keeps the trace, so the first dispatch does not pay it again."""
        from tony_tpu.ops.attention import kernel_counts
        step = getattr(self.train_step, "_fn", self.train_step)
        # a CPU lowering holds no TPU kernel by construction
        # (lax.platform_dependent): nothing to show, and every test
        # trainer would pay a second lowering for a line of zeros
        if jax.default_backend() == "cpu" or not hasattr(step, "lower"):
            return
        text = step.lower(self.params, self.opt_state, batch).as_text()
        LOG.info("train step lowered for %s with Pallas kernels: %s",
                 jax.default_backend(),
                 " ".join(f"{k}={v}" for k, v in
                          kernel_counts(text).items()))

    def _maybe_start_profiler(self) -> None:
        """Serve the JAX profiler on the TB port the executor reserved and
        registered with the AM (reference TensorBoard plumbing,
        TaskExecutor.java:87-95,311-319 → here it carries XProf traces:
        `tensorboard --logdir ...` or xprof can attach to this port)."""
        port = os.environ.get(C.TB_PORT)
        if not port or os.environ.get(C.IS_CHIEF, "true") != "true":
            return
        try:
            jax.profiler.start_server(int(port))
            LOG.info("jax profiler server on port %s", port)
        except Exception:  # noqa: BLE001 — profiling must never kill training
            LOG.exception("could not start profiler server")

    def _checkpoint_keep(self) -> int:
        """Retention count: config wins, else the executor-rendered
        TONY_CHECKPOINT_KEEP (tony.checkpoint.keep), else 3."""
        keep = self.config.checkpoint_keep
        if keep is None:
            try:
                keep = int(os.environ.get(C.CHECKPOINT_KEEP, "") or 3)
            except ValueError:
                keep = 3
        return max(0, keep)

    def _install_sigterm_handler(self) -> None:
        """Arm the checkpoint-then-evict drain: SIGTERM (forwarded by
        the executor on a preemption drain, or delivered directly by a
        TPU maintenance/spot eviction) raises TrainerPreempted in the
        main thread, and run()'s emergency path commits one synchronous
        checkpoint before exiting EXIT_PREEMPTED. Signal handlers only
        install from the main thread; anywhere else (unit tests driving
        run() from a worker thread) the drain falls back to whatever
        the process-level default does."""
        import signal
        import threading as _threading
        if _threading.current_thread() is not _threading.main_thread():
            return
        try:
            signal.signal(signal.SIGTERM, self._on_sigterm)
        except (ValueError, OSError):  # non-main interpreter contexts
            LOG.debug("could not install SIGTERM handler", exc_info=True)

    def _on_sigterm(self, signum, frame) -> None:
        LOG.warning("SIGTERM — draining for emergency checkpoint at "
                    "step %d", self.step)
        raise TrainerPreempted()

    def _emergency_checkpoint(self, reason: str = "") -> None:
        """One synchronous save of the current state: wait out any
        in-flight async write (its commit is newer evidence than a
        crash), then commit this step unless it is already on disk.
        Every failure is swallowed — this runs on the way out of a
        dying process and must never mask the original error."""
        cfg = self.config
        if not cfg.checkpoint_dir or self.params is None or self.step <= 0:
            return
        try:
            from tony_tpu.train.checkpoint import save_checkpoint
            if self._checkpointer is not None:
                try:
                    self._checkpointer.wait()
                except Exception:  # noqa: BLE001 — prior async failure
                    LOG.exception("in-flight async checkpoint failed "
                                  "during emergency drain")
            if latest_step(cfg.checkpoint_dir) == self.step:
                LOG.info("emergency checkpoint: step %d already "
                         "committed", self.step)
                return
            ledger = getattr(self, "ledger", None)
            if ledger is not None:
                ledger.transition("checkpoint_save")
            save_checkpoint(
                cfg.checkpoint_dir, self.step,
                {"params": self.params, "opt_state": self.opt_state,
                 "step": self.step},
                keep=self._checkpoint_keep(), pinned=self._restore_pinned)
            if ledger is not None:
                ledger.transition("idle")
            LOG.warning("emergency checkpoint committed at step %d (%s)",
                        self.step, reason or "unhandled error")
        except BaseException:  # noqa: BLE001 — never mask the real error
            LOG.exception("emergency checkpoint failed")

    def _checkpoint(self, final: bool = False) -> None:
        """Mid-training saves are async (file IO overlaps the next steps;
        the device->host snapshot inside save() is synchronous because the
        train step donates buffers); the final save blocks to commit."""
        if self._checkpointer is None:
            from tony_tpu.train.checkpoint import AsyncCheckpointer
            self._checkpointer = AsyncCheckpointer(
                self.config.checkpoint_dir,
                keep=self._checkpoint_keep(),
                pinned=self._restore_pinned)
        tracer = getattr(self, "_tracer", None)
        span = (tracer.start("checkpoint_save",
                             attrs={"step": self.step, "final": final})
                if tracer is not None else None)
        ledger = getattr(self, "ledger", None)
        prev_phase = ledger.phase if ledger is not None else ""
        if ledger is not None:
            ledger.transition("checkpoint_save")
        self._checkpointer.save(
            self.step, {"params": self.params, "opt_state": self.opt_state,
                        "step": self.step})
        if final:
            self._checkpointer.close()
            self._checkpointer = None
        if ledger is not None:
            # the async file IO continues past this by design — only the
            # synchronous snapshot (+ final commit) is checkpoint time
            ledger.transition(prev_phase or "train_step")
        if span is not None:
            # covers the synchronous snapshot (+ commit when final); the
            # async file IO continues past it by design
            tracer.end(span)
            self._flush_spans()
        LOG.info("checkpointed step %d%s", self.step,
                 " (final)" if final else " (async)")
