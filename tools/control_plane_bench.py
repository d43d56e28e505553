"""Host control-plane harness: how the orchestrator (session, liveliness
monitor, spec-diff protocol, warm pool, AM recovery) behaves at gang
widths no test reaches.

`python tools/control_plane_bench.py` runs every leg (see
`control_plane_main`) and prints ONE JSON line; `--cp-pool` is the child
mode its real-executor legs spawn. Everything here is a host measurement:
it runs on the CPU, its numbers carry CPU names, and nothing in it
touches a chip. Speed on the chip is the benchmark's business
(`python3 benchmark/run.py`, `BENCHMARK.json`, `PERF.md`).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# run as a script (also by the pools it spawns from another cwd): the
# package is found from the checkout, not from an installation
sys.path.insert(0, _REPO_ROOT)

_T0 = time.monotonic()


def _mark(msg: str) -> None:
    """Progress marker on stderr."""
    print(f"[bench +{time.monotonic() - _T0:.1f}s] {msg}", file=sys.stderr,
          flush=True)


def _rss_mb() -> float:
    """Current resident set of THIS process (MB), via /proc (the harness
    hosts the AM-side stores in-process, so this is 'AM RSS')."""
    try:
        with open("/proc/self/statm", "r", encoding="utf-8") as f:
            pages = int(f.read().split()[1])
        return round(pages * os.sysconf("SC_PAGE_SIZE") / (1 << 20), 1)
    except (OSError, ValueError, IndexError):
        return 0.0


def _make_cp_handler(session, monitor, on_result=None):
    """The AM's control-plane surface over a real TonySession + sharded
    LivelinessMonitor, mirroring ApplicationMaster's handlers (attempt
    fence, liveliness plant/ping, generation-keyed spec-diff piggyback) —
    shared by the stub storm and the real-executor gang legs."""
    from tony_tpu.rpc.service import ClusterServiceHandler

    class _Handler(ClusterServiceHandler):
        def get_task_infos(self, req):
            return []

        def get_cluster_spec(self, req):
            spec = session.cluster_spec_json()
            if spec is not None:
                session.note_full_serve(spec)
            return {"spec": spec, "generation": session.spec_generation}

        def register_worker_spec(self, req):
            attempt = int(req.get("task_attempt", -1))
            spec, generation, accepted = \
                session.register_worker_spec_with_generation(
                    req["task_id"], req["spec"], expected_attempt=attempt)
            if accepted and monitor is not None:
                monitor.register(req["task_id"], max(0, attempt))
            return {"spec": spec, "generation": generation}

        def register_tensorboard_url(self, req):
            return {}

        def register_serving_endpoint(self, req):
            return {}

        def register_execution_result(self, req):
            if monitor is not None:
                monitor.unregister(
                    f"{req['job_name']}:{req['job_index']}")
            if on_result is not None:
                on_result(req)
            return {}

        def finish_application(self, req):
            return {}

        def task_executor_heartbeat(self, req):
            generation = session.spec_generation
            attempt = int(req.get("task_attempt", -1))
            if attempt >= 0:
                task = session.get_task_by_id(req["task_id"])
                if task is not None and attempt != task.attempt:
                    return {"spec_generation": generation}
            if monitor is not None:
                monitor.ping(req["task_id"])
            resp = {"spec_generation": generation}
            exec_gen = int(req.get("spec_generation", -1) or -1)
            # the ONE shared piggyback implementation — the bench measures
            # the protocol production runs, never a hand-copied drift
            resp.update(session.heartbeat_spec_fields(exec_gen))
            return resp

        def request_profile(self, req):
            return {"error": "control-plane harness"}

        def read_task_logs(self, req):
            return {"error": "control-plane harness"}

        def get_skew(self, req):
            return {"error": "control-plane harness"}

        def get_alerts(self, req):
            return {"error": "control-plane harness"}

        def request_preemption(self, req):
            return {"error": "control-plane harness"}

        def request_rolling_update(self, req):
            return {"error": "control-plane harness"}

        def request_resize(self, req):
            return {"error": "control-plane harness"}

    return _Handler()


def _control_plane_width(width: int, history_points: int = 64,
                         max_spans: int = 2048,
                         relaunch_rounds: int = 12) -> dict:
    """Synthetic-width control-plane storm (ROADMAP item 3's measuring
    stick): `width` STUB tasks — real retrying gRPC clients, no
    containers/user processes — against the REAL AM-side control plane
    (TonySession gang barrier + sharded LivelinessMonitor + MetricsStore
    + SpanStore behind the genuine JSON-gRPC server). Records
    submit->all-registered latency, heartbeat round-trip p50/p95 at
    width, AM-process RSS, and SpanStore/MetricsStore sizes; then drives
    3x history_points metric samples per task through
    MetricsStore.update_metrics and asserts the PR-4 stride-doubling
    decimation actually bounds memory at this width (plus the skew
    sketch/analyzer drive, as before).

    New (coalesced control plane): after rendezvous every stub fetches
    the full spec once (the real launch-time fan-out), then
    `relaunch_rounds` relaunch generations propagate to every survivor
    via heartbeat-piggybacked spec DIFFS alone. spec_bytes_sent counts
    actual wire bytes; spec_bytes_full_equiv is what the pre-diff
    protocol would have fanned out ((1+rounds) x width x full-spec) —
    the O(width^2)->O(width) acceptance ratio."""
    import statistics
    import threading as th

    from tony_tpu.am.application_master import MetricsStore
    from tony_tpu.am.liveliness import (
        LivelinessMonitor, auto_liveliness_shards,
    )
    from tony_tpu.conf import keys as K
    from tony_tpu.conf.configuration import TonyConfiguration
    from tony_tpu.executor.task_executor import apply_spec_diff
    from tony_tpu.observability.skew import SkewTracker, StragglerAnalyzer
    from tony_tpu.observability.trace import SpanStore
    from tony_tpu.rpc.client import ClusterServiceClient, MetricsServiceClient
    from tony_tpu.rpc.service import auto_rpc_workers, serve
    from tony_tpu.session.session import TonySession

    conf = TonyConfiguration()
    conf.set(K.instances_key("worker"), width, "bench")
    session = TonySession(conf)
    session.num_expected_tasks = width
    store = MetricsStore(history_points=history_points)
    spans = SpanStore(max_spans)
    store.span_sink = spans.add
    monitor = LivelinessMonitor(1000, 25, lambda tid, att: None,
                                shards=auto_liveliness_shards(width))
    monitor.start()
    # cross-task skew path (observability/skew.py), wired exactly like
    # the AM wires it: every numeric gauge the decimation drive below
    # pushes through update_metrics also folds into the tracker's
    # windowed sketches — so the skew bench measures the REAL ingest path
    skew_buckets = 96
    tracker = SkewTracker(buckets=skew_buckets, heatmap_windows=8)
    analyzer = StragglerAnalyzer(threshold_pct=50, windows=2,
                                 min_tasks=3)
    store.skew_sink = tracker.observe_metric

    server, port = serve(cluster_handler=_make_cp_handler(session, monitor),
                         metrics_handler=store,
                         max_workers=auto_rpc_workers(width))
    n_clients = min(width, 32)
    cluster = [ClusterServiceClient("127.0.0.1", port)
               for _ in range(n_clients)]
    metrics = [MetricsServiceClient("127.0.0.1", port)
               for _ in range(n_clients)]
    errors: list[str] = []
    hb_times: list[float] = []
    hb_lock = th.Lock()

    def _stub(task_index: int) -> None:
        c = cluster[task_index % n_clients]
        m = metrics[task_index % n_clients]
        tid = f"worker:{task_index}"
        try:
            c.call("register_worker_spec",
                   {"task_id": tid, "spec": f"stub{task_index}:1"})
            t0 = time.monotonic()
            c.call("task_executor_heartbeat",
                   {"task_id": tid, "task_attempt": 0},
                   retries=1, timeout_sec=10.0)
            with hb_lock:
                hb_times.append(time.monotonic() - t0)
            m.update_metrics(
                "worker", task_index,
                [{"name": "TPU_UTILIZATION", "value": 50.0},
                 {"name": "TRAIN_STEP_TIME_MS", "value": 100.0}],
                spans=[{"name": "user_process", "span_id": f"s{task_index}",
                        "trace_id": "bench", "task_id": tid,
                        "start_ms": 0, "end_ms": 1, "status": "OK"},
                       {"name": "rendezvous_wait",
                        "span_id": f"r{task_index}", "trace_id": "bench",
                        "task_id": tid, "start_ms": 0, "end_ms": 1,
                        "status": "OK"}],
                attempt=0)
        except Exception as e:  # noqa: BLE001 — recorded, not raised
            with hb_lock:
                errors.append(f"{tid}: {type(e).__name__}: {e}")

    t0 = time.monotonic()
    threads = []
    # bounded launcher: at most 64 stub threads in flight
    sem = th.Semaphore(64)

    def _run(i: int) -> None:
        try:
            _stub(i)
        finally:
            sem.release()

    for i in range(width):
        sem.acquire()
        t = th.Thread(target=_run, args=(i,), daemon=True)
        t.start()
        threads.append(t)
    for t in threads:
        t.join(timeout=120)
    all_registered_s = time.monotonic() - t0
    registered = session.all_tasks_registered()

    # ---- launch-time spec fan-out + relaunch/diff storm ----------------
    # Every task fetches the full spec exactly once (what a real executor
    # needs to render its user-process env) ...
    def _parallel(fn, items, pool=64):
        ts, sem2 = [], th.Semaphore(pool)

        def _go(item):
            try:
                fn(item)
            except Exception as e:  # noqa: BLE001
                with hb_lock:
                    errors.append(f"{item}: {type(e).__name__}: {e}")
            finally:
                sem2.release()

        for item in items:
            sem2.acquire()
            t2 = th.Thread(target=_go, args=(item,), daemon=True)
            t2.start()
            ts.append(t2)
        for t2 in ts:
            t2.join(timeout=120)

    _parallel(lambda i: cluster[i % n_clients].call(
        "get_cluster_spec", {"task_id": f"worker:{i}"}), range(width))
    full_spec_json = session.cluster_spec_json() or "{}"
    # ... then `relaunch_rounds` generations: each relaunch reaches every
    # survivor as a heartbeat-piggybacked DIFF (O(changed) bytes), never
    # a full-spec re-fetch. A sample of survivors applies its diffs
    # locally; bit-identical convergence is asserted at the end.
    held_gen = {i: 1 for i in range(width)}
    sample = {i: json.loads(full_spec_json) for i in range(min(8, width))}
    diff_misses = [0]

    def _survive(i):
        t1 = time.monotonic()
        # a real survivor reports its OWN attempt (the storm victim sits
        # at attempt N after N relaunch rounds; a hardcoded 0 would be
        # zombie-fenced out of the diff protocol, correctly)
        task = session.get_task_by_id(f"worker:{i}")
        resp = cluster[i % n_clients].call(
            "task_executor_heartbeat",
            {"task_id": f"worker:{i}",
             "task_attempt": task.attempt if task is not None else 0,
             "spec_generation": held_gen[i]},
            retries=1, timeout_sec=10.0)
        with hb_lock:
            hb_times.append(time.monotonic() - t1)
        diff = (resp or {}).get("spec_diff")
        if not diff:
            with hb_lock:
                diff_misses[0] += 1
            return
        held_gen[i] = diff["generation"]
        if i in sample:
            sample[i] = apply_spec_diff(sample[i], diff["changed"],
                                        diff.get("removed"))

    victim = 0
    for r in range(1, relaunch_rounds + 1):
        task = session.relaunch_task("worker", victim)
        monitor.unregister(f"worker:{victim}")
        cluster[0].call("register_worker_spec",
                        {"task_id": f"worker:{victim}",
                         "spec": f"repl{r}:1",
                         "task_attempt": task.attempt})
        held_gen[victim] = session.spec_generation
        if victim in sample:
            sample[victim][
                "worker"][victim] = f"repl{r}:1"
        _parallel(_survive, [i for i in range(width) if i != victim])
    final_spec = session.cluster_spec_json() or "{}"
    diff_converged = (diff_misses[0] == 0
                      and all(held_gen[i] == session.spec_generation
                              for i in range(width))
                      and all(json.dumps(s) == final_spec
                              for s in sample.values()))
    stats = dict(session.spec_stats)
    spec_bytes_sent = stats["full_bytes"] + stats["diff_bytes"]
    # the pre-diff protocol's fan-out: every task re-fetches the full
    # spec at rendezvous AND after every relaunch generation
    spec_bytes_full_equiv = (1 + relaunch_rounds) * width \
        * len(full_spec_json)

    # ---- elastic resize roundtrip (cluster/elastic.py's control-plane
    # cost): grow width -> width+K (newcomers register, every survivor
    # converges via one membership diff), then shrink back (trailing
    # slots removed, survivors converge via a removal diff) — the
    # control-plane half of the resize round trip, with the quiesce/
    # checkpoint time excluded by construction (stub tasks own no user
    # process). Target: seconds — gated via bench_history as
    # control_plane_resize_roundtrip.
    k_resize = max(4, width // 16)
    resize_t0 = time.monotonic()
    added = []
    for _ in range(k_resize):
        t = session.add_task_instance("worker")
        session.num_expected_tasks += 1   # the scheduler's role, inlined
        added.append(t)
    session.resize_bump_generation({t.task_id for t in added}, {})
    _parallel(lambda i: cluster[i % n_clients].call(
        "register_worker_spec",
        {"task_id": f"worker:{i}", "spec": f"grown{i}:1",
         "task_attempt": 0}), range(width, width + k_resize))
    grow_registered = session.all_tasks_registered()
    _parallel(_survive, range(width))
    grow_s = time.monotonic() - resize_t0
    shrink_t0 = time.monotonic()
    removed = session.remove_task_slots("worker", k_resize)
    session.resize_bump_generation(
        set(), {"worker": {t.index for t in removed}})
    for t in removed:
        monitor.unregister(t.task_id)
    _parallel(_survive, range(width))
    shrink_s = time.monotonic() - shrink_t0
    resize_roundtrip_s = time.monotonic() - resize_t0
    resized_spec = session.cluster_spec_json() or "{}"
    resize_checks = {
        "grow_registered": grow_registered,
        "shrunk_registered": session.all_tasks_registered(),
        "slots_removed": len(removed) == k_resize,
        "survivor_generations": all(
            held_gen[i] == session.spec_generation
            for i in range(width)),
        "sample_specs": all(json.dumps(s) == resized_spec
                            for s in sample.values()),
    }
    resize_converged = all(resize_checks.values())

    # decimation-boundedness drive: 3x the ring capacity of samples per
    # task through the REAL store path (in-process — the wire above
    # already measured RPC cost); the stride-doubling TimeSeries must
    # hold every series at <= history_points regardless
    batch = 8   # samples per in-process push (cuts call overhead 8x)
    for i in range(width):
        for k in range(3 * history_points // batch):
            store.update_metrics(
                {"task_type": "worker", "index": i,
                 # a live duty sample rides along so the wedge detector
                 # doesn't (correctly, but noisily) flag the synthetic
                 # pushes as a stalled task
                 "metrics": [{"name": "TPU_UTILIZATION", "value": 50.0}]
                 + [{"name": "TRAIN_STEP_TIME_MS",
                     "value": float(k * batch + j)}
                    for j in range(batch)]})
    series = store.timeseries_dict()
    max_points = max((len(pts) for per in series.values()
                      for pts in per.values()), default=0)
    total_points = sum(len(pts) for per in series.values()
                       for pts in per.values())

    # skew-analyzer drive: the decimation loop above already folded
    # 3 x history_points step-time samples per task into the tracker's
    # open window; roll + analyze across 3 windows (feeding one fresh
    # sample per task per window, with the last task injected 3x slower
    # so the analyzer has something to latch) and time the pass. The
    # assertions are ROADMAP item 3's: sketch state is O(buckets) —
    # identical at width 48 and 1024 — and per-task retained state is a
    # few scalars per window, never a sample list.
    pass_ms: list[float] = []
    detected = 0
    sketch_cells = 0
    for _ in range(3):
        for i in range(width):
            value = 300.0 if i == width - 1 else 100.0
            tracker.observe(f"worker:{i}", "step_time_ms", value)
        # MEASURED open-window sketch footprint, sampled while the
        # window is populated (a roll clears it) — this is the number
        # that must stay identical across widths
        sketch_cells = max(sketch_cells, tracker.sketch_cells())
        t0 = time.monotonic()
        closed = tracker.maybe_roll(window_ms=0.0, force=True)
        actions, _rem = analyzer.analyze(closed or {},
                                         tracker.startup_values())
        pass_ms.append(1000.0 * (time.monotonic() - t0))
        detected += sum(1 for a in actions if a["action"] == "detected")
    per_task_cells = tracker.per_task_cells()
    # per task: <= 1 heatmap mean per retained window per signal, plus
    # O(1) open-window scalars — 64 cells/task is a generous ceiling
    skew_bounded = (0 < sketch_cells <= tracker.max_sketch_cells()
                    and per_task_cells <= 64 * width
                    and detected >= 1)

    bounded = (max_points <= history_points
               and len(spans) <= max_spans
               and skew_bounded
               and diff_converged
               and resize_converged)
    hb_sorted = sorted(hb_times)
    out = {
        "width": width,
        "registered": registered,
        "submit_to_all_registered_s": round(all_registered_s, 3),
        "heartbeat_p50_ms": (round(
            1000 * statistics.median(hb_times), 2) if hb_times else None),
        "heartbeat_p95_ms": (round(
            1000 * hb_sorted[int(0.95 * (len(hb_sorted) - 1))], 2)
            if hb_sorted else None),
        "spec": {
            "relaunch_rounds": relaunch_rounds,
            "renders": stats["renders"],
            "full_serves": stats["full_serves"],
            "diff_serves": stats["diff_serves"],
            "bytes_sent": spec_bytes_sent,
            "bytes_full_equiv": spec_bytes_full_equiv,
            "fanout_reduction_x": round(
                spec_bytes_full_equiv / max(1, spec_bytes_sent), 1),
            "diff_converged": diff_converged,
        },
        "resize": {
            "delta_tasks": k_resize,
            "grow_s": round(grow_s, 3),
            "shrink_s": round(shrink_s, 3),
            "roundtrip_s": round(resize_roundtrip_s, 3),
            "converged": resize_converged,
            "checks": resize_checks,
        },
        "rss_mb": _rss_mb(),
        "span_store": {"held": len(spans), "dropped": spans.dropped,
                       "cap": max_spans},
        "metrics_store": {"series_points_total": total_points,
                          "series_points_max": max_points,
                          "history_points_cap": history_points},
        "skew": {"analyzer_pass_ms": round(max(pass_ms), 3),
                 "analyzer_pass_ms_p50": round(
                     statistics.median(pass_ms), 3),
                 "sketch_cells": sketch_cells,
                 "sketch_cells_cap": tracker.max_sketch_cells(),
                 "per_task_cells": per_task_cells,
                 "stragglers_detected": detected,
                 "bounded": skew_bounded},
        "bounded": bounded,
        "errors": len(errors),
    }
    if errors:
        out["first_error"] = errors[0]
    monitor.stop()
    server.stop(grace=0)
    for c in cluster + metrics:
        c.close()
    return out


def _cp_pool_count(width: int) -> int:
    """Executor-pool subprocesses hosting a width-k gang (threads share
    interpreters: 1024 full python processes would measure the OS)."""
    return max(1, min(8, width // 64)) if width >= 64 else 1


def _control_plane_real(width: int, sleep_sec: float = 6.0,
                        deadline_sec: float = 0.0, warm_pool=None,
                        cache_dir: str = "") -> dict:
    """Real-executor gang at `width`: pool subprocesses host REAL
    `TaskExecutor` instances (jittered Heartbeater, backoff barrier
    poll, TaskMonitor metric pushes, result registration — everything
    except the per-executor log-service gRPC server, stubbed because
    width x servers is not what this leg measures) whose user processes
    are `sleep`s; the bench process hosts ONLY the AM side (session +
    sharded liveliness + MetricsStore behind the width-sized gRPC pool),
    so its RSS is genuinely "AM RSS under sustained width-k load".
    Records submit->all-registered and ->all-running latency, heartbeat
    RTT p50/p95 measured executor-side, sustained AM RSS, spec fan-out
    bytes, and how many executors completed cleanly.

    Cold-start phases are measured per leg: spawn (t0 -> CP-POOL-BOOT,
    i.e. interpreter + import cost) and localization (executor-side
    seconds + cache hit/miss counts for the synthetic resource every
    executor localizes). `warm_pool` (a pre-warmed
    cluster.warmpool.WarmExecutorPool) leases the pool subprocesses
    instead of cold-spawning them, and `cache_dir` enables the
    content-addressed localization cache (pre-seeded by the caller =
    the Nth-job case) — together they are the WARM leg; both unset is
    the cold baseline, exactly today's bring-up."""
    import subprocess as sp
    import tempfile
    import threading as th

    from tony_tpu.am.application_master import MetricsStore
    from tony_tpu.am.liveliness import (
        LivelinessMonitor, auto_liveliness_shards,
    )
    from tony_tpu.conf import keys as K
    from tony_tpu.conf.configuration import TonyConfiguration
    from tony_tpu.rpc.service import auto_rpc_workers, serve
    from tony_tpu.session.session import TonySession
    from tony_tpu.utils.common import current_host

    # the harness box may be far smaller than a production AM host (the
    # CI container has 2 cores): bound the run generously per width and
    # give the barrier the prod-default patience — the LATENCY numbers
    # say how fast it actually was
    if deadline_sec <= 0:
        deadline_sec = max(240.0, 0.75 * width)
    # width-1k sizing guidance (docs/OBSERVABILITY.md): past ~256 tasks
    # the heartbeat cadence lengthens — a pure-python AM on a small box
    # cannot serve 1024 JSON-RPCs/s, and a 1k gang gains nothing from
    # 1 s liveliness when its expiry window is 25 intervals anyway. The
    # row reports the cadence it measured under.
    hb_ms = 1000 if width <= 256 else 3000
    workdir = tempfile.mkdtemp(prefix="tony_cp_real_")
    # synthetic resource every executor localizes: the localize phase of
    # bring-up, measurable in both legs (cold = per-container copy,
    # warm = content-addressed cache hit + hardlink)
    res_path = os.path.join(workdir, "cp_resource.bin")
    with open(res_path, "wb") as f:
        f.write(os.urandom(4 << 20))
    conf = TonyConfiguration()
    conf.set(K.instances_key("worker"), width, "bench")
    conf.set(K.TASK_HEARTBEAT_INTERVAL_MS, hb_ms, "bench")
    conf.set(K.TASK_METRICS_INTERVAL_MS, max(5000, 4 * hb_ms), "bench")
    conf.set(K.TASK_REGISTRATION_TIMEOUT_SEC, 300, "bench")
    conf.set(K.CONTAINERS_RESOURCES, res_path, "bench")
    if cache_dir:
        from tony_tpu.utils.localization import LocalizationCache
        conf.set(K.LOCALIZATION_CACHE_ENABLED, True, "bench")
        conf.set(K.LOCALIZATION_CACHE_DIR, cache_dir, "bench")
        # seed = the (N-1)th job already fetched these bytes machine-wide
        LocalizationCache(cache_dir).get_or_add_file(res_path)
    session = TonySession(conf)
    session.num_expected_tasks = width
    store = MetricsStore(history_points=64)
    monitor = LivelinessMonitor(hb_ms, 25, lambda tid, att: None,
                                shards=auto_liveliness_shards(width))
    monitor.start()
    completed: set[str] = set()
    clean: list[int] = []
    done = th.Event()

    def _on_result(req):
        completed.add(f"{req['job_name']}:{req['job_index']}")
        if int(req.get("exit_code", 1)) == 0:
            clean.append(1)
        if len(completed) >= width:
            done.set()

    server, port = serve(
        cluster_handler=_make_cp_handler(session, monitor, _on_result),
        metrics_handler=store, max_workers=auto_rpc_workers(width))
    conf_path = os.path.join(workdir, "tony-final.json")
    conf.write(conf_path)

    pools = _cp_pool_count(width)
    per_pool = [width // pools + (1 if i < width % pools else 0)
                for i in range(pools)]
    host = current_host()
    procs, results, running_at, boot_at = [], [], [], []
    warm_leases, warm_misses = 0, 0
    lock = th.Lock()

    def _reader(proc):
        for raw in proc.stdout:
            line = raw.strip()
            if line.startswith("CP-POOL-BOOT"):
                with lock:
                    boot_at.append(time.monotonic())
            elif line.startswith("CP-POOL-RUNNING"):
                with lock:
                    running_at.append(time.monotonic())
            elif line.startswith("CP-POOL-RESULT "):
                try:
                    with lock:
                        results.append(json.loads(line.split(" ", 1)[1]))
                except ValueError:
                    pass

    t0 = time.monotonic()
    start = 0
    for count in per_pool:
        argv = [os.path.basename(os.path.abspath(__file__)), "--cp-pool",
                host, str(port), str(start), str(count), str(width),
                conf_path, str(sleep_sec)]
        proc = None
        if warm_pool is not None:
            # lease a pre-imported warm process: the bind spec re-enters
            # this file at cp_pool_main with the same argv a cold spawn
            # would parse; stdout stays on the inherited pipe so the
            # reader sees the CP-POOL-* protocol unchanged
            proc = warm_pool.lease_and_bind(
                env={}, cwd=workdir, entry="script",
                script_path=os.path.abspath(__file__),
                script_func="cp_pool_main", argv=argv)
            if proc is not None:
                warm_leases += 1
            else:
                warm_misses += 1
        if proc is None:
            proc = sp.Popen(
                [sys.executable, os.path.abspath(__file__), "--cp-pool",
                 host, str(port), str(start), str(count), str(width),
                 conf_path, str(sleep_sec)],
                stdout=sp.PIPE, stderr=sys.stderr, text=True, cwd=workdir)
        th.Thread(target=_reader, args=(proc,), daemon=True).start()
        procs.append(proc)
        start += count
    all_registered_s = all_running_s = None
    rss_peak = 0.0
    deadline = t0 + deadline_sec
    while time.monotonic() < deadline:
        if all_registered_s is None and session.all_tasks_registered():
            all_registered_s = time.monotonic() - t0
        with lock:
            pools_running = len(running_at)
        if all_running_s is None and pools_running >= pools:
            all_running_s = max(running_at) - t0
            _mark(f"real width {width}: all-running "
                  f"{all_running_s:.2f}s")
        rss_peak = max(rss_peak, _rss_mb())
        if done.is_set() and all(p.poll() is not None for p in procs):
            break
        time.sleep(0.25)
    for p in procs:
        if p.poll() is None:
            p.kill()
    hb_p50s = [r["hb_p50_ms"] for r in results if r.get("hb_p50_ms")]
    hb_p95s = [r["hb_p95_ms"] for r in results if r.get("hb_p95_ms")]
    errors = sum(r.get("errors", 0) for r in results)
    stats = dict(session.spec_stats)
    with lock:
        spawn_s = (round(max(boot_at) - t0, 3) if len(boot_at) >= pools
                   else None)
    out = {
        "width": width,
        "pools": pools,
        "hb_interval_ms": hb_ms,
        # cold-start disclosure (docs/OBSERVABILITY.md cold-start
        # section): which bring-up mode measured this row and what the
        # cacheable phases cost — history entries stay comparable
        # across machines and warm/cold modes
        "warm": warm_pool is not None,
        "loc_cache_enabled": bool(cache_dir),
        "warm_leases": warm_leases,
        "warm_misses": warm_misses,
        "spawn_s": spawn_s,
        "localize_s_sum": round(sum(
            r.get("localize_s_sum", 0.0) for r in results), 3),
        "localize_s_max": round(max(
            [r.get("localize_s_max", 0.0) for r in results] or [0.0]), 4),
        "loc_cache_hits": sum(r.get("loc_cache_hits", 0) for r in results),
        "loc_cache_misses": sum(r.get("loc_cache_misses", 0)
                                for r in results),
        "all_registered_s": (round(all_registered_s, 3)
                             if all_registered_s is not None else None),
        "submit_to_all_running_s": (round(all_running_s, 3)
                                    if all_running_s is not None else None),
        "hb_p50_ms": round(max(hb_p50s), 2) if hb_p50s else None,
        "hb_p95_ms": round(max(hb_p95s), 2) if hb_p95s else None,
        "rss_mb_sustained": rss_peak,
        "spec": {"renders": stats["renders"],
                 "full_serves": stats["full_serves"],
                 "diff_serves": stats["diff_serves"],
                 "bytes_sent": stats["full_bytes"] + stats["diff_bytes"]},
        "completed": len(completed),
        "completed_clean": len(clean),
        "errors": errors,
        "ok": (all_running_s is not None and len(completed) >= width),
    }
    monitor.stop()
    server.stop(grace=0)
    return out


def cp_pool_main() -> None:
    """`--cp-pool host port start count width conf sleep_sec`:
    one executor-pool subprocess of the real-gang control-plane bench —
    hosts `count` REAL TaskExecutor instances on threads (sharing this
    process's interpreter: 1024 full python processes would measure the
    OS, not the control plane). Emits CP-POOL-RUNNING when every
    executor's user process has launched and CP-POOL-RESULT {json} with
    executor-side heartbeat RTT quantiles at exit."""
    import tempfile
    import threading as th

    (host, port, start, count, width, conf_path, sleep_sec) = (
        sys.argv[2], int(sys.argv[3]), int(sys.argv[4]), int(sys.argv[5]),
        int(sys.argv[6]), sys.argv[7], float(sys.argv[8]))
    os.chdir(tempfile.mkdtemp(prefix="cp_pool_"))

    from tony_tpu import constants as TC
    from tony_tpu.executor.task_executor import TaskExecutor
    from tony_tpu.observability.metrics import REGISTRY
    from tony_tpu.rpc.client import (
        ClusterServiceClient, MetricsServiceClient,
    )

    # spawn-phase marker: interpreter + executor-stack imports are done
    # (near-zero for a warm-pool lease, the whole point of the pool)
    print("CP-POOL-BOOT", flush=True)

    # shared channels: a python process cannot drive 2 x count
    # independent gRPC channels (each costs pollers + memory); the RPC
    # traffic itself — every register/heartbeat/metrics call — is still
    # one per executor, multiplexed as HTTP/2 streams like any wide
    # client fleet behind a connection pool
    n_chan = max(2, min(8, count // 16))
    shared_cluster = [ClusterServiceClient(host, port)
                      for _ in range(n_chan)]
    shared_metrics = [MetricsServiceClient(host, port)
                      for _ in range(n_chan)]

    launched = th.Semaphore(0)

    class _PoolExecutor(TaskExecutor):
        # the one withheld piece: a per-executor log-service gRPC server
        # (width x servers measures grpc, not the control plane)
        _cp_launched = False
        # many executors share this process: one executor's 5-strike
        # heartbeat self-destruct (os._exit) would take the whole pool
        # down on a load-induced latency spike — widen the budget; the
        # parent's per-width deadline still bounds a truly dead AM
        HB_FAILURE_BUDGET = 60

        def _start_log_service(self):
            self._log_server, self._log_port = None, 0

        def _execute(self, env, timeout_sec):
            if not self._cp_launched:   # respec may re-enter
                self._cp_launched = True
                launched.release()
            return super()._execute(env, timeout_sec)

    errors: list[str] = []
    rcs: list[int] = []
    loc_secs: list[float] = []
    lock = th.Lock()

    def _run_one(i: int) -> None:
        env = {TC.JOB_NAME: "worker", TC.TASK_INDEX: str(i),
               TC.TASK_NUM: str(width), TC.IS_CHIEF: "false",
               TC.SESSION_ID: "0", TC.TASK_ATTEMPT: "0",
               TC.AM_HOST: host, TC.AM_PORT: str(port),
               TC.TASK_COMMAND: f"exec sleep {sleep_sec}",
               TC.TONY_APP_DIR: os.getcwd(),
               TC.TONY_CONF_PATH: conf_path}
        ex = None
        try:
            ex = _PoolExecutor(env=env,
                               client=shared_cluster[i % n_chan],
                               metrics_client=shared_metrics[i % n_chan])
            rc = ex.run()
            with lock:
                rcs.append(rc)
                loc_secs.append(
                    getattr(ex, "_goodput_seed", {}).get(
                        "localization", 0.0))
        except Exception as e:  # noqa: BLE001
            with lock:
                errors.append(f"worker:{i}: {type(e).__name__}: {e}")
        finally:
            # never wedge the RUNNING latch: an executor that died (or
            # timed out at the barrier) before launching still releases
            if ex is None or not ex._cp_launched:
                launched.release()

    threads = [th.Thread(target=_run_one, args=(start + k,), daemon=True)
               for k in range(count)]
    for t in threads:
        t.start()
    for _ in range(count):
        launched.acquire()
    print("CP-POOL-RUNNING", flush=True)
    for t in threads:
        t.join(timeout=600)
    for c in shared_cluster + shared_metrics:
        try:
            c.close()
        except Exception:  # noqa: BLE001
            pass
    hb = REGISTRY.summary("tony_rpc_client_latency_seconds",
                          method="task_executor_heartbeat")
    out = {"count": count, "errors": len(errors),
           "clean_exits": sum(1 for rc in rcs if rc == 0),
           "localize_s_sum": round(sum(loc_secs), 3),
           "localize_s_max": round(max(loc_secs or [0.0]), 4),
           "loc_cache_hits": int(REGISTRY.counter(
               "tony_localization_cache_hits_total").value),
           "loc_cache_misses": int(REGISTRY.counter(
               "tony_localization_cache_misses_total").value),
           "hb_p50_ms": (round(1000 * hb.quantile(0.5), 2)
                         if hb.count else None),
           "hb_p95_ms": (round(1000 * hb.quantile(0.95), 2)
                         if hb.count else None)}
    if errors:
        out["first_error"] = errors[0][:200]
    print("CP-POOL-RESULT " + json.dumps(out, separators=(",", ":")),
          flush=True)


def _cp_warm_leg(width: int, cache_dir: str, sleep_sec: float = 6.0) -> dict:
    """Run one real-executor leg through a pre-warmed executor pool +
    pre-seeded localization cache, tearing the pool down afterwards.
    The pool is warmed to exactly the leg's subprocess count BEFORE t0
    — the warm-job case: the pool amortized the interpreter/import cost
    while the previous job was still running."""
    from tony_tpu.cluster.warmpool import WarmExecutorPool

    pools = _cp_pool_count(width)
    pool = WarmExecutorPool(size=pools)
    pool.start()
    if not pool.wait_ready(pools, timeout=60.0):
        _mark(f"warm pool never reached {pools} ready — leg runs on "
              f"cold-spawn fallbacks")
    try:
        return _control_plane_real(width, sleep_sec=sleep_sec,
                                   warm_pool=pool, cache_dir=cache_dir)
    finally:
        pool.stop()


def _cp_disclosure(row: dict, cold_baseline_s=None) -> dict:
    """Cold-start disclosure stamped onto every control-plane history
    entry (the tpu_unavailable_reason discipline): a warm number must
    say it is warm, what the cache did, and what cold cost — so a
    reader can never mistake a warm headline for a cold-path speedup
    or vice versa."""
    d = {"warm_pool": bool(row.get("warm")),
         "warm_leases": row.get("warm_leases", 0),
         "warm_misses": row.get("warm_misses", 0),
         "spawn_s": row.get("spawn_s"),
         "loc_cache_hits": row.get("loc_cache_hits", 0),
         "loc_cache_misses": row.get("loc_cache_misses", 0)}
    if cold_baseline_s is not None:
        d["cold_baseline_s"] = cold_baseline_s
    return d


def _am_recovery_disclosure(row: dict) -> dict:
    """Recovery-leg disclosure stamped onto the control_plane_am_recovery
    history entry: a recovery-time headline means nothing without how
    much of the gang it actually recovered — an AM that 'recovered' fast
    by relaunching everyone would otherwise look like a win."""
    return {"adopted": row.get("adopted", 0),
            "lost": row.get("lost", 0),
            "replayed_records": row.get("replayed_records", 0),
            "relaunches": row.get("relaunches", 0),
            "kill_after_ms": row.get("kill_after_ms", 0)}


def _control_plane_am_recovery(width: int, kill_after_ms: int = 4000,
                               run_sec: float = 25.0) -> dict:
    """AM-kill leg of `control_plane_main`: run a REAL width-k gang
    through the full client -> supervised AM -> executor chain, SIGKILL
    the AM mid-run (the TEST_AM_KILL hook, same one the chaos suite
    drives), and let am/supervisor.py relaunch it: the new attempt
    replays the journal and every orphaned executor re-registers through
    the adoption barrier. The measured number is the AM_RECOVERY_COMPLETED
    event's downtime_ms — wall clock from the kill until the last live
    executor was adopted, i.e. how long the control plane was actually
    gone — lower is better. `ok` demands the job SUCCEEDED with the
    whole gang adopted and ZERO relaunches: a "recovery" that relaunched
    user processes is the failure mode this subsystem exists to avoid,
    and must never become a baseline."""
    import shutil
    import tempfile

    from tony_tpu import constants as TC
    from tony_tpu.client.tony_client import TonyClient
    from tony_tpu.conf import keys as K
    from tony_tpu.conf.configuration import TonyConfiguration
    from tony_tpu.events.handler import parse_events
    from tony_tpu.events.schema import EventType

    workdir = tempfile.mkdtemp(prefix="tony_cp_amkill_")
    conf = TonyConfiguration()
    conf.set(K.CLUSTER_WORKDIR, workdir, "bench")
    conf.set(K.instances_key("worker"), width, "bench")
    # test-scale cadences (the chaos suite's fast_conf shape): 200 ms
    # heartbeats, orphan after 2 strikes, AM-side expiry window 5 s —
    # liveliness clocks restart fresh per adopted member, so the window
    # only has to cover steady-state jitter, not the outage itself
    conf.set(K.AM_MONITOR_INTERVAL_MS, 100, "bench")
    conf.set(K.TASK_HEARTBEAT_INTERVAL_MS, 200, "bench")
    conf.set(K.TASK_MAX_MISSED_HEARTBEATS, 25, "bench")
    conf.set(K.TASK_HB_FAILURE_BUDGET, 2, "bench")
    conf.set(K.AM_ORPHAN_GRACE_MS, 120_000, "bench")
    conf.set(K.TASK_REGISTRATION_TIMEOUT_SEC, 120, "bench")
    conf.set(K.CONTAINER_ALLOCATION_TIMEOUT, 120_000, "bench")
    conf.set(K.AM_STOP_POLL_TIMEOUT_MS, 3000, "bench")
    # the survivability knobs under test: supervised restart + journal
    conf.set(K.AM_MAX_ATTEMPTS, 3, "bench")
    conf.set(K.AM_RETRY_BACKOFF_BASE_MS, 250, "bench")
    conf.set(K.AM_RETRY_BACKOFF_MAX_MS, 500, "bench")
    # user processes are plain sleeps long enough to span the outage:
    # adoption only counts executors whose user process never died
    conf.set(K.TASK_COMMAND, f"exec sleep {run_sec}", "bench")

    hook = f"{kill_after_ms}#0"      # kill AM process-attempt 0 only
    saved = os.environ.get(TC.TEST_AM_KILL)
    os.environ[TC.TEST_AM_KILL] = hook
    row = {"width": width, "kill_after_ms": kill_after_ms, "ok": False}
    client = TonyClient(conf)
    try:
        client.init([])
        client.run()
    finally:
        if saved is None:
            os.environ.pop(TC.TEST_AM_KILL, None)
        else:
            os.environ[TC.TEST_AM_KILL] = saved
    row["final_status"] = client.final_status
    hist_base = os.path.join(client.app_dir, TC.HISTORY_DIR_NAME)
    finals = [os.path.join(d, f) for d, _, files in os.walk(hist_base)
              for f in files if f.endswith(TC.HISTORY_SUFFIX)]
    if client.final_status == "SUCCEEDED" and len(finals) == 1:
        events = parse_events(finals[0])
        completed = [e.payload for e in events
                     if e.type == EventType.AM_RECOVERY_COMPLETED]
        row["relaunches"] = sum(
            1 for e in events if e.type == EventType.TASK_RELAUNCHED)
        if completed:
            rec = completed[-1]
            row.update({
                "recovery_s": round(rec.downtime_ms / 1000.0, 3),
                "downtime_ms": rec.downtime_ms,
                "adoption_ms": rec.duration_ms,
                "adopted": rec.adopted,
                "lost": rec.lost,
                "replayed_records": rec.replayed_records,
                "am_attempt": rec.am_attempt,
            })
            row["ok"] = (rec.adopted >= width and rec.lost == 0
                         and row["relaunches"] == 0)
    shutil.rmtree(workdir, ignore_errors=True)
    return row


def control_plane_main() -> None:
    """`python tools/control_plane_bench.py`: the control-plane harness —
    the synthetic-width stub storm at gang widths {48, 256, 1024}
    (TONY_CP_WIDTHS overrides) PLUS real-executor gangs at
    TONY_CP_REAL_WIDTHS (default the same; "" skips the real leg),
    each real width measured twice: a COLD leg (today's bring-up:
    fork+import per pool process, per-container resource copies) and a
    WARM leg (pre-warmed cluster/warmpool.py executor pool + pre-seeded
    content-addressed localization cache), plus a resize-grow leg
    (+widest/8 executors, warm vs cold) modeling the elastic grow path,
    plus an AM-KILL leg (TONY_CP_RECOVERY_WIDTH, default 8; "" skips)
    that SIGKILLs a live gang's AM and times the supervised-restart ->
    journal-replay -> adoption recovery.
    Emits ONE JSON line with a `control_plane` block and the widest
    width's spec_bytes_sent / hb_p95_ms at top level; appends gated
    entries (control_plane_spec_bytes [bytes], control_plane_hb_p95
    [ms], control_plane_all_registered [s],
    control_plane_resize_roundtrip [s],
    control_plane_real_all_running [s] — the WARM number, appended only
    when it beat the same run's cold leg — resize_grow_latency [s],
    same rule — and control_plane_am_recovery [s], appended only when
    the WHOLE gang was adopted with zero relaunches — all
    lower-is-better) to tools/bench_history.jsonl for
    tools/bench_compare.py. Exits non-zero if AM-side state is
    unbounded, the diff protocol failed to converge, any real gang
    (either leg) never reached all-running, or the AM-kill leg failed
    to recover the full gang."""
    import shutil
    import tempfile

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # the always-on profiler samples this harness process through every
    # storm below; the run FAILS if its measured cost breaches the <1%
    # budget on any real leg, and the reading is stamped on every
    # emitted line so no headline can quietly include (or exclude) the
    # profiler tax
    from tony_tpu.observability.profiler import (OVERHEAD_BUDGET_PCT,
                                                 SamplingProfiler)
    prof = SamplingProfiler("bench-cp")
    prof.start()
    widths = [int(w) for w in os.environ.get(
        "TONY_CP_WIDTHS", "48,256,1024").split(",") if w.strip()]
    rows = []
    for width in widths:
        _mark(f"control-plane width {width}")
        rows.append(_control_plane_width(width))
        _mark(f"width {width}: all-registered "
              f"{rows[-1]['submit_to_all_registered_s']}s rss "
              f"{rows[-1]['rss_mb']}MB bounded={rows[-1]['bounded']} "
              f"spec-fanout-x{rows[-1]['spec']['fanout_reduction_x']} "
              f"resize-roundtrip {rows[-1]['resize']['roundtrip_s']}s")
    real_rows = []
    real_widths = [int(w) for w in os.environ.get(
        "TONY_CP_REAL_WIDTHS", "48,256,1024").split(",") if w.strip()]
    # one machine-wide content-addressed cache dir shared by every warm
    # leg — exactly how the real knob deploys (tony.localization.cache-dir
    # is a host path, not a per-job path)
    cache_root = tempfile.mkdtemp(prefix="tony_cp_loccache_") \
        if real_widths else ""
    grow = None
    for width in real_widths:
        _mark(f"control-plane REAL executors width {width} — COLD leg")
        cold = _control_plane_real(width)
        _mark(f"real width {width} cold: all-running "
              f"{cold['submit_to_all_running_s']}s spawn "
              f"{cold['spawn_s']}s localize-max {cold['localize_s_max']}s "
              f"hb-p95 {cold['hb_p95_ms']}ms rss "
              f"{cold['rss_mb_sustained']}MB ok={cold['ok']}")
        _mark(f"control-plane REAL executors width {width} — WARM leg "
              f"(pre-warmed pool + seeded cache)")
        warm = _cp_warm_leg(width, cache_root)
        _mark(f"real width {width} warm: all-running "
              f"{warm['submit_to_all_running_s']}s spawn "
              f"{warm['spawn_s']}s localize-max {warm['localize_s_max']}s "
              f"leases {warm['warm_leases']}/{warm['warm_leases'] + warm['warm_misses']} "
              f"cache-hits {warm['loc_cache_hits']} ok={warm['ok']}")
        real_rows.append({"width": width, "cold": cold, "warm": warm,
                          # cumulative self-overhead at the point this
                          # leg finished — the width-256 leg's reading
                          # is the budget assertion below
                          "profiler_overhead_pct":
                              round(prof.overhead_pct(), 4)})
    if real_widths:
        # resize-grow leg: the elastic grow path (arbiter grants +n, AM
        # launches +n NEW containers into a running app) is bounded by
        # exactly the phases the warm pool + cache remove — measure the
        # +n bring-up alone, cold vs warm
        grow_n = max(8, max(real_widths) // 8)
        _mark(f"control-plane resize-grow leg: +{grow_n} executors COLD")
        grow_cold = _control_plane_real(grow_n, sleep_sec=2.0)
        _mark(f"grow +{grow_n} cold: all-running "
              f"{grow_cold['submit_to_all_running_s']}s ok={grow_cold['ok']}")
        _mark(f"control-plane resize-grow leg: +{grow_n} executors WARM")
        grow_warm = _cp_warm_leg(grow_n, cache_root, sleep_sec=2.0)
        _mark(f"grow +{grow_n} warm: all-running "
              f"{grow_warm['submit_to_all_running_s']}s ok={grow_warm['ok']}")
        grow = {"grow_n": grow_n, "cold": grow_cold, "warm": grow_warm}
    if cache_root:
        shutil.rmtree(cache_root, ignore_errors=True)
    # AM-kill recovery leg: kill the control plane of a live gang and
    # time the supervised-restart -> journal-replay -> adoption path
    # (TONY_CP_RECOVERY_WIDTH overrides the width; "" skips the leg)
    recovery = None
    rec_width = os.environ.get("TONY_CP_RECOVERY_WIDTH", "8").strip()
    if rec_width:
        _mark(f"control-plane AM-kill recovery leg: width {rec_width}")
        recovery = _control_plane_am_recovery(int(rec_width))
        _mark(f"am-kill width {recovery['width']}: recovery "
              f"{recovery.get('recovery_s')}s adopted "
              f"{recovery.get('adopted')}/{recovery['width']} lost "
              f"{recovery.get('lost')} replayed "
              f"{recovery.get('replayed_records')} relaunches "
              f"{recovery.get('relaunches')} ok={recovery['ok']}")
    prof.stop()
    profiler_overhead_pct = round(prof.overhead_pct(), 4)
    widest = rows[-1] if rows else {}
    result = {"metric": "control_plane", "backend": "cpu",
              # not a fallback: this metric never touches the chip
              "tpu_unavailable_reason": "not-applicable: orchestrator "
                                        "metric (cpu by contract)",
              "spec_bytes_sent": widest.get("spec", {}).get("bytes_sent"),
              "hb_p95_ms": widest.get("heartbeat_p95_ms"),
              "profiler_overhead_pct": profiler_overhead_pct,
              "control_plane": {"widths": rows, "real": real_rows,
                                "grow": grow, "recovery": recovery}}
    unbounded = [r["width"] for r in rows if not r["bounded"]]
    real_failed = [r["width"] for r in real_rows
                   if not (r["cold"]["ok"] and r["warm"]["ok"])]
    if grow and not (grow["cold"]["ok"] and grow["warm"]["ok"]):
        real_failed.append(f"grow+{grow['grow_n']}")
    if recovery is not None and not recovery["ok"]:
        real_failed.append(f"am-kill@{recovery['width']}")
    # hard self-overhead budget: the always-on profiler must stay <1%
    # even under the real control-plane storm, or it cannot be
    # always-on — a breach fails the run like any other regression
    over_budget = [r["width"] for r in real_rows
                   if r.get("profiler_overhead_pct", 0.0)
                   >= OVERHEAD_BUDGET_PCT]
    if over_budget:
        real_failed.append(f"profiler-overhead@{over_budget}")
    # gated history entries: a future chatty regression (spec fan-out,
    # heartbeat tail, rendezvous latency) fails bench_compare loudly.
    # Only a PASSING run may append — a diverged/failed run's numbers
    # must never become the baseline the next run is judged against.
    if not unbounded and not real_failed:
        base = {"backend": "cpu",
                "tpu_unavailable_reason": "not-applicable: orchestrator "
                                          "metric (cpu by contract)",
                # every history line discloses what the always-on
                # profiler cost this run (budget: <1%)
                "profiler_overhead_pct": profiler_overhead_pct}
        for metric, value, unit in (
                ("control_plane_spec_bytes",
                 widest.get("spec", {}).get("bytes_sent"), "bytes"),
                ("control_plane_hb_p95",
                 widest.get("heartbeat_p95_ms"), "ms"),
                ("control_plane_all_registered",
                 widest.get("submit_to_all_registered_s"), "s"),
                ("control_plane_resize_roundtrip",
                 widest.get("resize", {}).get("roundtrip_s"), "s"),
        ):
            if value:
                _append_history({**base, "metric": metric, "value": value,
                                 "unit": unit, "width": widest.get("width"),
                                 "warm_pool": False})
        if real_rows:
            # the bring-up headline is the WARM number — but it only
            # lands when the same run's cold leg proves warm actually
            # won; a warm regression past cold never becomes a
            # "better" baseline
            cold, warm = real_rows[-1]["cold"], real_rows[-1]["warm"]
            cv = cold.get("submit_to_all_running_s")
            wv = warm.get("submit_to_all_running_s")
            if cv and wv and wv < cv:
                _append_history({**base,
                                 "metric": "control_plane_real_all_running",
                                 "value": wv, "unit": "s",
                                 "width": real_rows[-1]["width"],
                                 **_cp_disclosure(warm,
                                                  cold_baseline_s=cv)})
            else:
                _mark(f"warm leg did not beat cold "
                      f"({wv}s vs {cv}s) — real_all_running headline "
                      f"withheld")
        if grow:
            cv = grow["cold"].get("submit_to_all_running_s")
            wv = grow["warm"].get("submit_to_all_running_s")
            if cv and wv and wv < cv:
                _append_history({**base, "metric": "resize_grow_latency",
                                 "value": wv, "unit": "s",
                                 "width": grow["grow_n"],
                                 **_cp_disclosure(grow["warm"],
                                                  cold_baseline_s=cv)})
            else:
                _mark(f"grow warm leg did not beat cold ({wv}s vs {cv}s)"
                      f" — resize_grow_latency headline withheld")
        if recovery is not None and recovery["ok"] \
                and recovery.get("recovery_s"):
            # the gate above already proved adopted == width, lost == 0,
            # zero relaunches — only a FULL recovery's time is a baseline
            _append_history({**base,
                             "metric": "control_plane_am_recovery",
                             "value": recovery["recovery_s"], "unit": "s",
                             "width": recovery["width"],
                             **_am_recovery_disclosure(recovery)})
    if unbounded:
        result["error"] = (f"span/metrics/skew/spec-diff state unbounded "
                           f"or diverged at width(s) {unbounded} — "
                           f"decimation, the skew sketches, or the diff "
                           f"protocol regressed")
    if real_failed:
        result["real_error"] = (f"real-executor leg(s) {real_failed} "
                                f"failed: gang never reached all-running, "
                                f"the AM-kill leg did not recover the "
                                f"full gang relaunch-free, or the "
                                f"profiler breached its <1% self-overhead "
                                f"budget")
    line = json.dumps(result)
    if len(line) > 4000:
        # keep the driver-facing line bounded; full rows went to stderr
        result["control_plane"] = {"widths": rows[-1:],
                                   "real": real_rows[-1:], "grow": grow}
        line = json.dumps(result)
    print(line, flush=True)
    if unbounded or real_failed:
        sys.exit(1)


def _commit_stamp() -> str:
    """Short HEAD hash, or "unknown": the chip machine runs a copy of
    the tree that is not a git repository (git prints to stderr and
    exits non-zero there), and a missing git binary must not fail the
    run either."""
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, timeout=10, cwd=_REPO_ROOT
        ).stdout.strip() or "unknown"
    except Exception:  # noqa: BLE001
        return "unknown"


# env-overridable so harnesses (and the contract tests) can redirect
# the append away from the checked-in trajectory file
_HISTORY_PATH = os.environ.get(
    "TONY_BENCH_HISTORY_PATH",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "bench_history.jsonl"))


def _append_history(result: dict) -> None:
    """Self-defending bench (ROADMAP item 5 slice): every emitted
    headline is appended to tools/bench_history.jsonl — commit- and
    time-stamped — so tools/bench_compare.py can flag a regression
    against the best same-backend baseline. Heavy diagnostic fields
    are dropped."""
    entry = dict(result)
    entry.setdefault("measured_at",
                     time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()))
    entry.setdefault("commit", _commit_stamp())
    for key in ("error", "scraped_metrics"):
        entry.pop(key, None)
    try:
        with open(_HISTORY_PATH, "a", encoding="utf-8") as f:
            f.write(json.dumps(entry, separators=(",", ":")) + "\n")
    except Exception:  # noqa: BLE001 — history is metadata, never fatal
        pass



if __name__ == "__main__":
    if len(sys.argv) >= 9 and sys.argv[1] == "--cp-pool":
        cp_pool_main()
    else:
        control_plane_main()
