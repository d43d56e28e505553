"""Serving task entry: ``python -m tony_tpu.serve``.

The default command of the ``serving`` jobtype (AM fills it in when no
per-jobtype command is configured). Inside an orchestrated container it:

- reads the frozen conf (``TONY_CONF_PATH``) for the ``tony.serving.*``
  knobs (slots, token budget, queue depth, port) — CLI flags override;
- binds the executor-registered rendezvous port (``SERVING_PORT``), so the
  endpoint in the AM's cluster spec IS the live HTTP endpoint;
- registers the endpoint URL with the AM (``register_serving_endpoint``),
  which records it as a history event and surfaces it in task infos and on
  the portal job page;
- pushes serving metrics (TTFT, inter-token latency, queue depth, slot
  occupancy, tokens/sec) through the same metrics RPC the trainer uses;
- shuts down cleanly on SIGTERM (the executor's graceful container stop):
  frontend first, then the engine — no orphan process, no held port.

Standalone (no orchestrator env) it is a plain local server: all the same
flags, no registration, metrics exposed on ``/v1/metrics`` only.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import signal
import sys
import threading
import time
from contextlib import contextmanager

LOG = logging.getLogger(__name__)


def build_arg_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="tony_tpu.serve")
    p.add_argument("--config", default="tiny",
                   help="model preset (models/llama.py PRESETS / MoE / a "
                        "model of several layer kinds: models/__init__.py "
                        "BY_KIND)")
    p.add_argument("--checkpoint-dir", default="",
                   help="restore params from the latest checkpoint here "
                        "(the examples/llama-pretrain format)")
    p.add_argument("--quant", default="", choices=("", "int8"),
                   help="int8 weight-only decode (models/quant.py)")
    p.add_argument("--quant-cache", action="store_true",
                   help="per-row int8 KV cache for the shared slot cache")
    p.add_argument("--slots", type=int, default=0,
                   help="decode slots (0 = tony.serving.slots)")
    p.add_argument("--token-budget", type=int, default=0,
                   help="per-slot prompt+generation budget "
                        "(0 = tony.serving.token-budget, capped at "
                        "config.max_seq)")
    p.add_argument("--queue-depth", type=int, default=0,
                   help="bounded pending-request queue "
                        "(0 = tony.serving.queue-depth)")
    p.add_argument("--port", type=int, default=-1,
                   help="HTTP port (-1 = tony.serving.port, else the "
                        "executor-assigned $SERVING_PORT, else ephemeral)")
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--top-k", type=int, default=0)
    p.add_argument("--top-p", type=float, default=1.0)
    p.add_argument("--eos-id", type=int, default=-1,
                   help="eos token id latching a row (-1 = none)")
    p.add_argument("--weights-generation", type=int, default=0,
                   help="weights rollout epoch this replica serves "
                        "(0 = $TONY_SERVING_WEIGHTS_GENERATION, else "
                        "the AM stamps its current epoch)")
    p.add_argument("--role", default="",
                   choices=("", "both", "prefill", "decode"),
                   help="disaggregated serving role "
                        "('' = $TONY_SERVING_ROLE, else tony.serving.role)")
    p.add_argument("--migrate-to", default="",
                   help="comma-separated decode-replica base URLs a "
                        "prefill replica hands decode work to "
                        "('' = tony.serving.migrate-to)")
    p.add_argument("--prefix-sharing", default="",
                   choices=("", "on", "off"),
                   help="paged prefix-shared KV admission "
                        "('' = tony.serving.kv.prefix-sharing)")
    p.add_argument("--kv-page-size", type=int, default=0,
                   help="tokens per KV page "
                        "(0 = tony.serving.kv.page-size)")
    p.add_argument("--kv-pages", type=int, default=0,
                   help="device page-pool size incl. scratch "
                        "(0 = tony.serving.kv.pages, 0 = auto)")
    return p


def _init_runtime() -> None:
    """First contact with the backend, forced here so that it is timed on
    its own (`runtime_init` of the SERVE_STARTUP line) and not inside
    whichever of the model's first device calls would have paid for it."""
    import jax

    # persistent XLA compile cache (utils/compilecache.py): applied
    # before any device work so replica N skips replica 0's cold
    # prefill/decode compile
    from tony_tpu.utils.compilecache import enable_compile_cache
    enable_compile_cache(jax)
    # the trainer's device line: what tells a TPU replica from a CPU one
    # (asking for the devices is what starts the runtime)
    from tony_tpu.train.metrics import log_devices
    log_devices(LOG)


def _process_age_s():
    """Seconds since the operating system started this process (Linux
    /proc), None where there is no such record: what interpreter start
    and imports cost before main() ran."""
    try:
        with open("/proc/self/stat", encoding="ascii") as f:
            # the fields after the parenthesised command name; the 22nd
            # of the line is the start time in clock ticks since boot
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime", encoding="ascii") as f:
            uptime_s = float(f.read().split()[0])
        return uptime_s - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


class _Startup:
    """The replica's start-up, phase by phase: a `serve_startup` lifecycle
    span with one child a phase on the job's waterfall, and the one
    SERVE_STARTUP line a reader of the container log finds."""

    def __init__(self, recorder, t0: float):
        self._t0 = t0                       # main()'s entry
        self._recorder = recorder
        self._root = recorder.start("serve_startup")
        self._parts: dict = {}

    @contextmanager
    def phase(self, name: str):
        t = time.monotonic()
        with self._recorder.span(name, parent=self._root):
            yield
        self._parts[f"{name}_s"] = time.monotonic() - t

    def finish(self) -> None:
        line = dict(self._parts, total_s=time.monotonic() - self._t0)
        age = _process_age_s()
        if age is not None:
            line["process_age_s"] = age
        self._recorder.end(self._root, attrs={
            k: round(v, 3) for k, v in line.items()})
        # log-ok: raw stdout like SERVING_UP below, for the same readers
        print("SERVE_STARTUP " + json.dumps(line), flush=True)


def _load_model(args):
    import jax
    import jax.numpy as jnp

    from tony_tpu.models import by_kind_preset
    from tony_tpu.models.moe import is_moe_preset

    kind = by_kind_preset(args.config)
    if kind is not None:
        # layers of several kinds: the preset's own module makes the
        # config and the weights, and the engine asks it for its cache
        config = kind.get_config(args.config)
        params = kind.init(config, jax.random.PRNGKey(0))
    elif is_moe_preset(args.config):
        from tony_tpu.models.moe import get_moe_config, moe_init
        base = get_moe_config(args.config)
        # no-drop capacity: serve-side decode equals the training forward
        # (models/generate._mlp docstring)
        config = get_moe_config(args.config, capacity_factor=max(
            base.capacity_factor, base.n_experts / base.top_k))
        params = moe_init(config, jax.random.PRNGKey(0))
    else:
        from tony_tpu.models.llama import get_config, llama_init
        config = get_config(args.config)
        params = llama_init(config, jax.random.PRNGKey(0))
    if args.checkpoint_dir:
        from tony_tpu.train.checkpoint import latest_step, restore_checkpoint
        step = latest_step(args.checkpoint_dir)
        if step is None:
            raise SystemExit(f"no checkpoint in {args.checkpoint_dir}")
        state = restore_checkpoint(args.checkpoint_dir, step)
        params = jax.tree.map(jnp.asarray, state["params"])
        LOG.info("restored checkpoint step %d from %s", step,
                 args.checkpoint_dir)
    if args.quant == "int8":
        from tony_tpu.models.quant import quantize_params
        params = quantize_params(params)
        LOG.info("int8 weight-only params")
    return params, config


def _register_endpoint(url: str, env, weights_generation: int = 0,
                       draining: bool = False, role: str = "") -> None:
    """Tell the AM where this server listens — or, with draining=True,
    that it is connection-draining ahead of shutdown, so the fleet
    router stops new sends (no-op outside the orchestrator). Same
    lazily-available env contract as the trainer's metrics reporter."""
    from tony_tpu import constants as C
    host, port = env.get(C.AM_HOST), env.get(C.AM_PORT)
    if not host or not port:
        return
    from tony_tpu.rpc.client import ClusterServiceClient
    from tony_tpu.security.tokens import TOKEN_ENV
    task_id = f"{env.get(C.JOB_NAME, 'serving')}:{env.get(C.TASK_INDEX, '0')}"
    token = env.get(TOKEN_ENV) or None
    client = ClusterServiceClient(host, int(port), auth_token=token,
                                  task_auth_id=task_id if token else None,
                                  # the drain announcement runs inside
                                  # the TERM grace window: one fast try,
                                  # never a retry ladder
                                  retries=1 if draining else 10)
    try:
        client.register_serving_endpoint(
            task_id, url, weights_generation=weights_generation,
            draining=draining, role=role)
        LOG.info("registered serving endpoint %s with the AM%s", url,
                 " (draining)" if draining else "")
    except Exception:  # noqa: BLE001 — registration is observability
        LOG.exception("failed to register serving endpoint")
    finally:
        client.close()


def _migrated_reporter(env):
    """Hook(target_url) for the frontend: report each prefill→decode
    handoff to the AM (SERVING_MIGRATED event on the job page) without
    ever blocking the relay path. None outside the orchestrator."""
    from tony_tpu import constants as C
    host, port = env.get(C.AM_HOST), env.get(C.AM_PORT)
    if not host or not port:
        return None
    from tony_tpu.rpc.client import ClusterServiceClient
    from tony_tpu.security.tokens import TOKEN_ENV
    task_id = f"{env.get(C.JOB_NAME, 'serving')}:{env.get(C.TASK_INDEX, '0')}"
    token = env.get(TOKEN_ENV) or None

    def report(target_url: str) -> None:
        def _send() -> None:
            client = ClusterServiceClient(
                host, int(port), auth_token=token,
                task_auth_id=task_id if token else None, retries=1)
            try:
                client.report_serving_migrated(task_id, target_url)
            except Exception:  # noqa: BLE001 — observability only
                LOG.debug("report_serving_migrated failed", exc_info=True)
            finally:
                client.close()
        threading.Thread(target=_send, name="migrate-report",
                         daemon=True).start()

    return report


def main(argv=None) -> int:
    t_main = time.monotonic()
    # structured JSON-lines logging (stamped with the serving task's
    # identity from the container env; TONY_LOG_PLAIN=1 opts out)
    from tony_tpu.observability.logs import configure_structured_logging
    configure_structured_logging()
    args = build_arg_parser().parse_args(argv)
    env = os.environ

    from tony_tpu import constants as C
    # lifecycle spans of this task on the job's waterfall (only when a
    # trace context was rendered into this container's env — standalone
    # runs record locally and push nothing): the start-up phases. Per-
    # request traces are reqtrace's, pulled from /v1/traces.
    from tony_tpu.observability.trace import SpanRecorder
    recorder = SpanRecorder.from_env(
        env,
        task_id=(f"{env.get(C.JOB_NAME, '')}:{env.get(C.TASK_INDEX, '0')}"
                 if env.get(C.JOB_NAME) else ""),
        attempt=int(env.get(C.TASK_ATTEMPT, "0") or 0))
    startup = _Startup(recorder, t_main)
    from tony_tpu.conf import TonyConfiguration, keys as K
    conf_path = env.get(C.TONY_CONF_PATH, "")
    conf = (TonyConfiguration.read(conf_path)
            if conf_path and os.path.exists(conf_path)
            else TonyConfiguration())

    # continuous profiler + stall watchdog + faulthandler (SIGUSR2 →
    # all-thread dump): a serving replica is a long-running process and
    # a wedged decode loop should name its blocking frame locally
    from tony_tpu.observability.profiler import install_process_profiler
    install_process_profiler(
        f"serve:{env.get(C.JOB_NAME, 'serving')}"
        f":{env.get(C.TASK_INDEX, str(os.getpid()))}", conf=conf)

    slots = args.slots or conf.get_int(K.SERVING_SLOTS, 4)
    queue_depth = args.queue_depth or conf.get_int(K.SERVING_QUEUE_DEPTH, 64)
    port = args.port
    if port < 0:
        port = conf.get_int(K.SERVING_PORT, 0) \
            or int(env.get(C.SERVING_PORT, "0") or 0)

    with startup.phase("runtime_init"):
        _init_runtime()
    with startup.phase("load_model"):
        # each phase waits for its own device work, so that the next one
        # is not billed for it
        import jax
        params, config = _load_model(args)
        jax.block_until_ready(params)
    # capped at the model's max_seq on BOTH paths (flag and conf) — the
    # documented contract; an oversized ask serves at max_seq instead of
    # crashing the container
    token_budget = min(
        args.token_budget or conf.get_int(K.SERVING_TOKEN_BUDGET, 2048),
        config.max_seq)

    weights_generation = args.weights_generation \
        or int(env.get(C.SERVING_WEIGHTS_GENERATION, "0") or 0)
    # disaggregation role: flag > $TONY_SERVING_ROLE > tony.serving.role —
    # the per-replica env override is how the AM's role-split autoscaler
    # steers a scaled-up instance into the thinner pool
    role = args.role or env.get(C.SERVING_ROLE, "") \
        or conf.get(K.SERVING_ROLE, "both") or "both"
    if args.prefix_sharing:
        prefix_sharing = args.prefix_sharing == "on"
    else:
        prefix_sharing = conf.get_bool(K.SERVING_KV_PREFIX_SHARING, False)
    kv_page_size = args.kv_page_size \
        or conf.get_int(K.SERVING_KV_PAGE_SIZE, 16)
    kv_pages = args.kv_pages or conf.get_int(K.SERVING_KV_PAGES, 0)
    migrate_to = args.migrate_to or conf.get(K.SERVING_MIGRATE_TO, "") or ""
    migrate_targets = [u.strip() for u in migrate_to.split(",")
                       if u.strip()]
    with startup.phase("engine_init"):
        # the shared cache's allocation
        from tony_tpu.serve.engine import ContinuousBatchingEngine
        from tony_tpu.serve.frontend import ServeFrontend
        engine = ContinuousBatchingEngine(
            params, config, n_slots=slots, token_budget=token_budget,
            queue_depth=queue_depth, temperature=args.temperature,
            top_k=args.top_k, top_p=args.top_p,
            eos_id=args.eos_id if args.eos_id >= 0 else None,
            quant_cache=args.quant_cache,
            weights_generation=weights_generation,
            prefix_sharing=prefix_sharing, kv_page_size=kv_page_size,
            kv_pages=kv_pages, role=role)
        jax.block_until_ready(engine._cache)

    # request-scoped distributed tracing (observability/reqtrace.py):
    # tail-sampled per-request hop traces, pull-exported on /v1/traces
    # and piggybacked on the metrics RPC into serving_traces.json
    from tony_tpu.observability.reqtrace import (
        ReqTraceCollector, TailSampler,
    )
    from tony_tpu.serve.frontend import install_engine_tracing
    collector = ReqTraceCollector(
        process=(f"{env.get(C.JOB_NAME, role or 'serving')}"
                 f":{env.get(C.TASK_INDEX, str(os.getpid()))}"),
        sampler=TailSampler(
            slow_threshold_ms=conf.get_time_ms(
                K.SERVING_TRACE_SLOW_THRESHOLD_MS, 1000),
            slowest_k=conf.get_int(K.SERVING_TRACE_SLOWEST_K, 8),
            window_ms=conf.get_time_ms(K.SERVING_TRACE_WINDOW_MS,
                                       60_000)),
        max_traces=conf.get_int(K.SERVING_TRACE_MAX_TRACES, 256),
        enabled=conf.get_bool(K.SERVING_TRACE_ENABLED, True))
    install_engine_tracing(engine, collector)

    with startup.phase("frontend_start"):
        engine.start()
        frontend = ServeFrontend(engine, port=port, host=args.host,
                                 migrate_targets=migrate_targets,
                                 on_migrated=_migrated_reporter(env),
                                 collector=collector)
        frontend.start()

    from tony_tpu.utils.common import current_host
    url = f"http://{current_host()}:{frontend.port}"
    startup.finish()
    # log-ok: greppable bring-up marker on RAW stdout (e2e tests + bench
    # drivers grep for it; it must not be wrapped in a JSON log line)
    print(f"SERVING_UP {url}", flush=True)
    _register_endpoint(url, env, weights_generation=weights_generation,
                       role=role)

    def _sample_metrics() -> list:
        # engine gauges + the TTFT-attribution rollup (SERVING_TTFT_
        # ATTR_<component>_MS_P50/P95) on the same metrics push
        out = list(engine.metrics())
        for key, value in collector.attribution.gauges().items():
            out.append({"name": f"SERVING_{key.upper()}",
                        "value": float(value)})
        return out

    from tony_tpu.train.metrics import ServingMetricsReporter
    reporter = ServingMetricsReporter(
        _sample_metrics,
        interval_sec=conf.get_time_ms(K.TASK_METRICS_INTERVAL_MS,
                                      5000) / 1000.0,
        span_source=recorder.drain if recorder.enabled else None,
        trace_source=collector.drain if collector.enabled else None)
    reporter.start()

    stop = threading.Event()

    def _on_signal(signum, frame):
        LOG.info("signal %d — shutting down serving", signum)
        stop.set()

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)
    try:
        stop.wait()
    finally:
        # connection draining (the fleet contract): refuse new work,
        # announce the drain to the AM (router stops new sends), finish
        # in-flight streams inside a bound that fits the executor's
        # TERM→KILL grace, THEN tear down — a relaunch/preemption/
        # scale-down never cuts a client mid-token
        engine.begin_drain()
        _register_endpoint(url, env,
                           weights_generation=weights_generation,
                           draining=True, role=role)
        drain_s = conf.get_time_ms(K.SERVING_FLEET_DRAIN_TIMEOUT_MS,
                                   10_000) / 1000.0
        if not engine.wait_drained(drain_s):
            LOG.warning("drain window (%.1fs) expired with work still "
                        "in flight", drain_s)
        else:
            # the engine finished into the handles; give the handler
            # threads a beat to flush the final chunks down their
            # (daemonic) sockets before the server closes
            import time as _time
            _time.sleep(0.2)
        reporter.close()
        frontend.stop()
        engine.stop()
        LOG.info("serving stopped cleanly")
    return 0


if __name__ == "__main__":
    sys.exit(main())
