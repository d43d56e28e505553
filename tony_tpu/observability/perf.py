"""Performance truth: goodput ledger, MFU accounting, SLO watchdog,
on-demand profiler capture.

PR 4 gave the orchestrator lifecycle spans and gauge trajectories — this
module turns those raw signals into *performance* answers:

- **Goodput ledger** (`GoodputLedger`): a per-task time-accounting state
  machine that attributes every wall-clock second to exactly one
  exclusive phase (init, localization, rendezvous_wait, compile,
  train_step, input_stall, checkpoint_save/restore, eval,
  relaunch_downtime, idle). Transitions happen only at existing span /
  stall boundaries — the hot loop gains no host sync. By construction
  the phase durations sum to wall clock exactly; the e2e test pins the
  flushed `goodput.json` to within 1%.
- **MFU** (`peak_flops` / `mfu_pct`): the program's peak-FLOPs table
  and MFU formula, behind the trainer's goodput metrics. The table
  agrees with `benchmark/lib/peaks.py` wherever both name a device
  (tests/test_perf.py); the trainer cannot import `benchmark/`.
- **Goodput aggregation** (`aggregate_goodput`): the AM folds per-task
  ledgers (arriving as GOODPUT_* gauges over the metrics RPC) plus the
  fault-tolerance layer's relaunch downtime into a job-level
  `goodput_pct` = productive train-step seconds / total wall seconds.
- **SLO watchdog** (`SloWatchdog`): step-time-regression and
  goodput-floor thresholds -> latched violations the AM turns into
  WARNING history events + alert gauges.
- **Profiler capture** (`ProfileCapture`): the trainer-side half of the
  `request_profile` operator workflow — polls for the executor-written
  request file (heartbeat-piggybacked from the AM), runs
  `jax.profiler` for N steps, and publishes the artifact back through
  the metrics RPC so the AM can link it into history.

No jax import at module level: control-plane processes (the AM's
goodput aggregation, the SLO watchdog) import this module and never
claim a chip.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
import uuid
from typing import Callable, Optional

LOG = logging.getLogger(__name__)

# ---------------------------------------------------------------------------
# peak FLOPs + MFU
# ---------------------------------------------------------------------------

# bf16 peak FLOP/s of one chip, keyed by `device_kind` exactly as jax
# reports it (Google Cloud TPU documentation, per-generation system
# architecture pages). A v5e chip reports "TPU v5 lite"; "TPU v5e" is
# kept beside it for configurations that name the product. A device
# that is not here is an error, never a default.
PEAK_FLOPS = {
    "TPU v2": 45e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,     # Trillium
    "TPU v6e": 918e12,
}


def peak_flops(device) -> float:
    """Peak bf16 FLOP/s of one chip. Raises for a device the table does
    not know — the CPU included: a utilization against a made-up peak
    would flow from the trainer's gauge to the AM, the portal and the
    alerts as if it were a measurement."""
    kind = getattr(device, "device_kind", "")
    try:
        return PEAK_FLOPS[kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s known for device_kind {kind!r} (platform "
            f"{getattr(device, 'platform', '?')!r}); add it to "
            f"observability/perf.py PEAK_FLOPS with its source") from None


def mfu_pct(tokens_per_sec_per_chip: float, flops_per_token: float,
            device=None, peak: float = 0.0) -> float:
    """Model FLOPs utilization in percent: achieved training FLOPs/s per
    chip over the chip's peak. Pass either a jax device (`device`) or an
    explicit `peak` FLOPs/s."""
    denom = peak or (peak_flops(device) if device is not None else 0.0)
    if denom <= 0 or flops_per_token <= 0:
        return 0.0
    return 100.0 * tokens_per_sec_per_chip * flops_per_token / denom


def tokens_in_batch(batch) -> int:
    """Token count of one training batch (0 when the shape is not
    token-like). Shape inspection only — reading `.shape` of a jax array
    never syncs the device."""
    if not isinstance(batch, dict):
        return 0
    for key in ("inputs", "tokens"):
        arr = batch.get(key)
        shape = getattr(arr, "shape", None)
        if shape and len(shape) >= 2:
            return int(shape[0]) * int(shape[1])
    return 0


# ---------------------------------------------------------------------------
# goodput ledger
# ---------------------------------------------------------------------------

# Exclusive phases every wall-clock second is attributed to. `input_stall`
# and `relaunch_downtime` are carved out of their enclosing phase
# (train_step / the AM-side gap between attempts) rather than entered by a
# timeline transition.
PHASES = (
    "init", "localization", "rendezvous_wait", "compile", "train_step",
    "input_stall", "checkpoint_save", "checkpoint_restore", "eval",
    "relaunch_downtime", "resize", "idle",
)

GOODPUT_METRIC_PREFIX = "GOODPUT_"
GOODPUT_WALL_METRIC = "GOODPUT_WALL_SECONDS"
# the phases that count as productive training in goodput_pct
PRODUCTIVE_PHASES = ("train_step",)


def goodput_metric_name(phase: str) -> str:
    return f"{GOODPUT_METRIC_PREFIX}{phase.upper()}_SECONDS"


class GoodputLedger:
    """Exclusive-phase wall-clock accounting for one task process.

    Exactly one phase is open at any time; `transition` closes it and
    opens the next, `carve` re-attributes seconds of the open phase to a
    sibling (the prefetch stall counter's seconds move from `train_step`
    to `input_stall` at log boundaries). Invariant, by construction:
    sum(phase seconds) == wall seconds since construction — the snapshot
    includes the open phase's elapsed-so-far, so the books always
    balance mid-phase too.

    Thread-safe (the metrics pusher snapshots from its worker thread);
    mutation cost is a monotonic read + a dict add, fine for phase
    boundaries (never per-step)."""

    def __init__(self, phase: str = "init",
                 clock: Callable[[], float] = time.monotonic,
                 seed: Optional[dict] = None):
        self._clock = clock
        self._t0 = clock()
        self._phase = phase
        self._phase_start = self._t0
        self._acc: dict[str, float] = {p: 0.0 for p in PHASES}
        self._acc.setdefault(phase, 0.0)
        # phases another process of the same task slot already accounted
        # (the executor's localization / rendezvous_wait, handed over in
        # TONY_GOODPUT_SEED): closed durations that extend this ledger's
        # wall clock, keeping sum(phases) == wall_s across the handoff
        self._seed_total = 0.0
        for p, v in (seed or {}).items():
            v = max(0.0, float(v))
            self._acc[str(p)] = self._acc.get(str(p), 0.0) + v
            self._seed_total += v
        self._lock = threading.Lock()

    @classmethod
    def from_env(cls, env, phase: str = "init") -> "GoodputLedger":
        """Ledger seeded with the executor-accounted phases rendered into
        the user-process env (no seed -> a bare ledger, so direct script
        runs keep working)."""
        from tony_tpu import constants as C
        seed = None
        raw = env.get(C.TONY_GOODPUT_SEED, "")
        if raw:
            try:
                parsed = json.loads(raw)
                if isinstance(parsed, dict):
                    seed = {str(k): float(v) for k, v in parsed.items()
                            if isinstance(v, (int, float))}
            except (ValueError, TypeError):
                seed = None
        return cls(phase=phase, seed=seed)

    @property
    def phase(self) -> str:
        return self._phase

    def transition(self, phase: str) -> None:
        """Close the open phase, attributing its elapsed time, and open
        `phase`. Transitioning to the already-open phase is a no-op that
        still folds the elapsed segment in (safe to call defensively)."""
        now = self._clock()
        with self._lock:
            self._acc[self._phase] = self._acc.get(self._phase, 0.0) + (
                now - self._phase_start)
            self._phase = phase
            self._phase_start = now
            self._acc.setdefault(phase, 0.0)

    def carve(self, phase: str, seconds: float,
              source: Optional[str] = None) -> None:
        """Move `seconds` from `source` (default: the OPEN phase) to
        `phase` without touching the timeline — wall-clock sum is
        preserved. Used for quantities measured by counters inside a
        phase (input stall seconds inside train_step); pass `source`
        explicitly when the carve may run after the source phase closed
        (the end-of-run flush happens from idle)."""
        if seconds <= 0:
            return
        with self._lock:
            src = source if source is not None else self._phase
            self._acc[phase] = self._acc.get(phase, 0.0) + seconds
            self._acc[src] = self._acc.get(src, 0.0) - seconds

    def snapshot(self) -> dict:
        """{"phases": {phase: seconds}, "wall_s": seconds} — open phase
        included at its elapsed-so-far, so sum(phases) == wall_s."""
        now = self._clock()
        with self._lock:
            phases = dict(self._acc)
            phases[self._phase] = phases.get(self._phase, 0.0) + (
                now - self._phase_start)
            wall = (now - self._t0) + self._seed_total
        return {"phases": phases, "wall_s": wall}

    def metrics(self) -> list[dict]:
        """The ledger as AM metric dicts ({name, value}) for the existing
        metrics RPC — GOODPUT_<PHASE>_SECONDS + GOODPUT_WALL_SECONDS."""
        snap = self.snapshot()
        out = [{"name": goodput_metric_name(p), "value": round(v, 4)}
               for p, v in sorted(snap["phases"].items())]
        out.append({"name": GOODPUT_WALL_METRIC,
                    "value": round(snap["wall_s"], 4)})
        return out


def parse_goodput_gauges(gauges: dict[str, float]) -> Optional[dict]:
    """Invert `GoodputLedger.metrics()` from a task's latest-gauge map:
    -> {"phases": {...}, "wall_s": ...}, or None when the task never
    pushed a ledger."""
    phases: dict[str, float] = {}
    wall = None
    for name, value in gauges.items():
        if name == GOODPUT_WALL_METRIC:
            wall = float(value)
        elif (name.startswith(GOODPUT_METRIC_PREFIX)
              and name.endswith("_SECONDS")):
            phase = name[len(GOODPUT_METRIC_PREFIX):-len("_SECONDS")].lower()
            phases[phase] = float(value)
    if wall is None and not phases:
        return None
    return {"phases": phases,
            "wall_s": wall if wall is not None else sum(phases.values())}


def aggregate_goodput(per_task_gauges: dict[str, dict[str, float]],
                      relaunch_downtime_s: float = 0.0,
                      preemption_downtime_s: float = 0.0,
                      resize_downtime_s: float = 0.0,
                      am_downtime_s: float = 0.0) -> dict:
    """Fold per-task ledgers + AM-side relaunch downtime into the job
    view flushed as `goodput.json`:

    {"tasks": {task_id: {"phases", "wall_s", "mfu_pct"?,
                         "tokens_per_sec_per_chip"?}},
     "job": {"goodput_pct", "productive_s", "wall_s",
             "relaunch_downtime_s", "preemption_downtime_s",
             "resize_downtime_s", "am_downtime_s"}}

    goodput_pct = productive train-step seconds / (summed task wall +
    relaunch downtime + preemption downtime + resize downtime + AM
    downtime) — downtime the fault-tolerance layer spent between
    attempts, the eviction→resume gap a checkpoint-then-evict
    preemption cost this job's lineage, the quiesce→re-rendezvous gap
    of every elastic resize (the `resize` phase), and the control-plane
    blackout of an AM crash→adoption-barrier recovery (the
    `am_downtime` phase), all count AGAINST goodput even though no
    task process existed (or no AM was listening) to observe them."""
    tasks: dict[str, dict] = {}
    productive = 0.0
    wall_total = 0.0
    for task_id, gauges in sorted(per_task_gauges.items()):
        ledger = parse_goodput_gauges(gauges)
        if ledger is None:
            continue
        entry = dict(ledger)
        for gauge, key in (("TRAIN_MFU_PCT", "mfu_pct"),
                           ("TRAIN_TOKENS_PER_SEC_PER_CHIP",
                            "tokens_per_sec_per_chip")):
            if gauge in gauges:
                entry[key] = float(gauges[gauge])
        tasks[task_id] = entry
        wall_total += entry["wall_s"]
        productive += sum(entry["phases"].get(p, 0.0)
                          for p in PRODUCTIVE_PHASES)
    denom = wall_total + max(0.0, relaunch_downtime_s) \
        + max(0.0, preemption_downtime_s) + max(0.0, resize_downtime_s) \
        + max(0.0, am_downtime_s)
    return {
        "tasks": tasks,
        "job": {
            "goodput_pct": round(100.0 * productive / denom, 3)
            if denom > 0 else 0.0,
            "productive_s": round(productive, 4),
            "wall_s": round(denom, 4),
            "relaunch_downtime_s": round(max(0.0, relaunch_downtime_s), 4),
            "preemption_downtime_s": round(
                max(0.0, preemption_downtime_s), 4),
            "resize_downtime_s": round(max(0.0, resize_downtime_s), 4),
            "am_downtime_s": round(max(0.0, am_downtime_s), 4),
        },
    }


# ---------------------------------------------------------------------------
# SLO watchdog (AM-side)
# ---------------------------------------------------------------------------

class SloWatchdog:
    """Latched SLO checks over the AM's metric trajectories.

    - step-time regression: a task's latest TRAIN_STEP_TIME_MS exceeds
      its own baseline (median of the first samples **of its current
      attempt**) by more than `step_regression_pct` percent. The
      baseline is attempt-aware: a task relaunch (attempt bump) resets
      the baseline window to the new attempt's own samples, so a
      replacement's recompile steps become the new baseline instead of
      tripping the latch against the dead attempt's steady state;
    - goodput floor: job goodput_pct below `goodput_floor_pct`.

    `check()` returns only NEWLY-entered violations (the AM emits one
    WARNING history event per entry); the latch re-arms when the
    condition recovers. `current_step_regressions()` exposes the raw
    currently-violating set without the latch — the alert engine's
    step-regression rule reads that and runs its own lifecycle.
    Thresholds <= 0 disable the respective check."""

    BASELINE_POINTS = 5
    MIN_POINTS = 3

    def __init__(self, step_regression_pct: float = 0.0,
                 goodput_floor_pct: float = 0.0):
        self.step_regression_pct = step_regression_pct
        self.goodput_floor_pct = goodput_floor_pct
        self._latched: set[str] = set()
        # task_id -> (attempt the baseline belongs to, boundary
        # timestamp: samples at or before it belong to dead attempts).
        # A TIMESTAMP, not an index — the TimeSeries behind the series
        # decimates in place when full, so an absolute index would
        # drift (or point past the end forever) after a halving; the
        # boundary survives decimation because surviving points keep
        # their timestamps.
        self._baseline_marks: dict[str, tuple[int, int]] = {}

    @staticmethod
    def _median(values: list[float]) -> float:
        ordered = sorted(values)
        return ordered[len(ordered) // 2]

    def _baseline_boundary(self, task_id: str, attempt: int,
                           points: list) -> int:
        """Timestamp before which samples are excluded from the current
        attempt's baseline window. First sighting of a slot keeps the
        whole series; an attempt bump cuts at the series tail (the
        trajectories survive a relaunch, so the dead attempt's points
        must stay out of the new baseline) while keeping the newest
        point — the push that announced the new attempt; monitor
        cadence is at least as fast as the push cadence, so at most one
        new-attempt point predates the bump being observed."""
        mark = self._baseline_marks.get(task_id)
        if mark is not None and mark[0] == attempt:
            return mark[1]
        boundary = -1
        if mark is not None and len(points) >= 2:
            boundary = int(points[-2][0])
        self._baseline_marks[task_id] = (attempt, boundary)
        # the old attempt's latched violation (if any) describes a task
        # that no longer exists — re-arm
        self._latched.discard(f"step_time:{task_id}")
        return boundary

    def current_step_regressions(
            self, step_series: dict[str, list],
            attempts: Optional[dict[str, int]] = None) -> list[dict]:
        """The CURRENTLY-violating tasks (no latch): {"kind",
        "task_id", "value", "threshold", "message"} dicts. `attempts`
        maps task_id -> its latest attempt number (the MetricsStore's
        per-slot attempt tracking); absent entries read as attempt 0."""
        if self.step_regression_pct <= 0:
            return []
        attempts = attempts or {}
        out: list[dict] = []
        for task_id, points in sorted(step_series.items()):
            points = [p for p in points
                      if isinstance(p, (list, tuple)) and len(p) == 2]
            attempt = int(attempts.get(task_id, 0) or 0)
            boundary = self._baseline_boundary(task_id, attempt, points)
            values = [float(v) for ts, v in points if ts > boundary]
            if len(values) < max(self.MIN_POINTS,
                                 self.BASELINE_POINTS // 2 + 1):
                continue
            baseline = self._median(values[:self.BASELINE_POINTS])
            latest = values[-1]
            threshold = baseline * (1.0 + self.step_regression_pct
                                    / 100.0)
            if baseline > 0 and latest > threshold:
                out.append({
                    "kind": "step_time_regression",
                    "task_id": task_id,
                    "value": round(latest, 3),
                    "threshold": round(threshold, 3),
                    "message": (
                        f"step time {latest:.1f} ms exceeds baseline "
                        f"{baseline:.1f} ms (attempt {attempt}) by more "
                        f"than {self.step_regression_pct:.0f}%"),
                })
        return out

    def check(self, step_series: dict[str, list],
              goodput_pct: Optional[float] = None,
              attempts: Optional[dict[str, int]] = None) -> list[dict]:
        """`step_series`: {task_id: [[ts_ms, step_ms], ...]} (the
        MetricsStore's TRAIN_STEP_TIME_MS trajectories). Returns newly
        entered violations as {"kind", "task_id"?, "value",
        "threshold", "message"} dicts."""
        fresh: list[dict] = []
        seen: set[str] = set()
        for violation in self.current_step_regressions(step_series,
                                                       attempts=attempts):
            key = f"step_time:{violation['task_id']}"
            seen.add(key)
            if key not in self._latched:
                self._latched.add(key)
                fresh.append(violation)
        if self.goodput_floor_pct > 0 and goodput_pct is not None:
            key = "goodput_floor"
            if goodput_pct < self.goodput_floor_pct:
                seen.add(key)
                if key not in self._latched:
                    self._latched.add(key)
                    fresh.append({
                        "kind": "goodput_floor",
                        "value": round(goodput_pct, 3),
                        "threshold": self.goodput_floor_pct,
                        "message": (
                            f"job goodput {goodput_pct:.1f}% below the "
                            f"{self.goodput_floor_pct:.0f}% floor"),
                    })
        # re-arm every latch whose condition recovered this check
        self._latched &= seen
        return fresh

    def active(self) -> list[str]:
        """Currently-latched violation keys (alert gauge source)."""
        return sorted(self._latched)


# ---------------------------------------------------------------------------
# on-demand profiler capture (trainer-side)
# ---------------------------------------------------------------------------

def new_profile_request_id() -> str:
    return uuid.uuid4().hex[:12]


class ProfileCapture:
    """Trainer-side half of the `request_profile` workflow.

    The AM piggybacks a pending request on the executor's heartbeat; the
    executor writes it to `profile_request.json` in the container cwd
    (the trainer's cwd). The trainer calls `poll()` at log boundaries (a
    stat syscall, never a device sync) and `on_step()` after each step
    (a host bool check while idle): a new request starts
    `jax.profiler.start_trace` into `profiles/<request_id>/`, N steps
    later `stop_trace` runs and `publish` ships
    {request_id, path, num_steps, duration_ms} back over the metrics
    RPC for the AM to link into history.

    Idempotent: request ids already seen (including the one currently
    capturing) never restart a trace. `start_fn`/`stop_fn` default to
    jax.profiler and exist for tests/fixtures that must not drag jax in.
    """

    def __init__(self, cwd: str = ".",
                 publish: Optional[Callable[[dict], None]] = None,
                 start_fn: Optional[Callable[[str], None]] = None,
                 stop_fn: Optional[Callable[[], None]] = None):
        from tony_tpu import constants as C
        self._cwd = cwd
        self._request_path = os.path.join(cwd, C.PROFILE_REQUEST_FILE)
        self._profiles_dir = os.path.join(cwd, C.PROFILES_DIR_NAME)
        self._publish = publish
        self._start_fn = start_fn
        self._stop_fn = stop_fn
        self._seen: set[str] = set()
        self._active: Optional[dict] = None

    @property
    def active(self) -> bool:
        return self._active is not None

    def poll(self) -> None:
        """Check for a new request file; start a capture if one names an
        unseen request id. Called at log boundaries only."""
        if self._active is not None:
            return
        try:
            with open(self._request_path, "r", encoding="utf-8") as f:
                req = json.load(f)
        except (OSError, json.JSONDecodeError, ValueError):
            return
        rid = str(req.get("request_id", "") or "")
        if not rid:
            return
        if rid in self._seen:
            # completed (or failed) earlier in THIS process but the file
            # outlived it — clear it so a successor process after an
            # in-place relaunch doesn't re-burn a full capture
            self._remove_request_file()
            return
        self._seen.add(rid)
        steps = max(1, int(req.get("num_steps", 1) or 1))
        out_dir = os.path.join(self._profiles_dir, rid)
        try:
            os.makedirs(out_dir, exist_ok=True)
            self._trace_start(out_dir)
        except Exception:  # noqa: BLE001 — profiling must never kill training
            LOG.exception("could not start profiler trace for request %s",
                          rid)
            return
        LOG.info("profiler capture %s started (%d steps) -> %s", rid,
                 steps, out_dir)
        self._active = {"request_id": rid, "remaining": steps,
                        "num_steps": steps, "dir": out_dir,
                        "t0": time.monotonic()}

    def on_step(self) -> None:
        """Count one completed train step against the active capture;
        stop + publish when the budget is spent."""
        active = self._active
        if active is None:
            return
        active["remaining"] -= 1
        if active["remaining"] > 0:
            return
        self._active = None
        # the request is spent either way: remove the relay file so a
        # relaunched trainer (fresh _seen set, same cwd) never replays it
        self._remove_request_file()
        try:
            self._trace_stop()
        except Exception:  # noqa: BLE001
            LOG.exception("profiler stop_trace failed for request %s",
                          active["request_id"])
            return
        duration_ms = int(1000 * (time.monotonic() - active["t0"]))
        LOG.info("profiler capture %s finished after %d steps (%d ms)",
                 active["request_id"], active["num_steps"], duration_ms)
        if self._publish is not None:
            try:
                self._publish({
                    "request_id": active["request_id"],
                    "path": os.path.abspath(active["dir"]),
                    "num_steps": active["num_steps"],
                    "duration_ms": duration_ms,
                })
            except Exception:  # noqa: BLE001
                LOG.exception("profile publish failed")

    def _remove_request_file(self) -> None:
        try:
            os.remove(self._request_path)
        except OSError:
            pass

    def _trace_start(self, out_dir: str) -> None:
        if self._start_fn is not None:
            self._start_fn(out_dir)
            return
        import jax
        jax.profiler.start_trace(out_dir)

    def _trace_stop(self) -> None:
        if self._stop_fn is not None:
            self._stop_fn()
            return
        import jax
        jax.profiler.stop_trace()
