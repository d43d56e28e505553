"""Online-serving subsystem tests (serve/engine.py + serve/frontend.py +
the `serving` jobtype e2e).

The load-bearing contract: continuous-batching greedy decode is
BIT-IDENTICAL to the offline `generate()` oracle for the same prompts,
under staggered arrival order and slot recycling, with zero decode-step
recompiles after warmup. Everything else (backpressure, streaming,
endpoint registration, shutdown hygiene) is the serving lifecycle around
that core. All CPU-backend, tier-1 fast.
"""

from __future__ import annotations

import json
import socket
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tony_tpu.models.generate import generate
from tony_tpu.models.llama import get_config, llama_init
from tony_tpu.serve.engine import (
    BudgetExceededError, ContinuousBatchingEngine, QueueFullError,
    admit_step_cache_size, decode_step_cache_size,
)
from tony_tpu.serve.frontend import ServeFrontend

pytestmark = pytest.mark.serving


@pytest.fixture(scope="module")
def model():
    cfg = get_config("tiny")
    return llama_init(cfg, jax.random.PRNGKey(0)), cfg


def _prompts(cfg, lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [[int(t) for t in rng.randint(0, cfg.vocab_size, size=n)]
            for n in lengths]


def _oracle(params, cfg, prompt, n, **kw):
    """Offline single-request greedy generate — the parity oracle."""
    out = generate(params, cfg, jnp.asarray([prompt], jnp.int32), n, **kw)
    return [int(t) for t in np.asarray(out)[0]]


def _drain(engine, handles, max_steps=200):
    for _ in range(max_steps):
        if all(h.done.is_set() for h in handles):
            return
        engine.step()
    raise AssertionError("engine did not finish the workload")


# ---------------------------------------------------------------------------
# the core contract
# ---------------------------------------------------------------------------

def test_staggered_arrivals_bit_identical_to_offline_oracle(model):
    """Requests arriving mid-flight, recycled slots, mixed prompt lengths:
    every request's greedy tokens equal offline generate() on that prompt
    alone — and the persistent decode step never recompiles."""
    params, cfg = model
    prompts = _prompts(cfg, (8, 5, 8, 11, 5, 3))
    engine = ContinuousBatchingEngine(params, cfg, n_slots=2,
                                      token_budget=32, queue_depth=16)
    # warmup: one request through, so compile counts are steady-state
    warm = engine.submit(prompts[0], 2)
    _drain(engine, [warm])
    decode_compiles = decode_step_cache_size()

    handles = [engine.submit(prompts[0], 6), engine.submit(prompts[1], 6)]
    engine.step()
    engine.step()
    # staggered: these arrive while slots are mid-decode
    handles.append(engine.submit(prompts[2], 4))
    handles.append(engine.submit(prompts[3], 6))
    engine.step()
    handles.append(engine.submit(prompts[4], 3))
    handles.append(engine.submit(prompts[5], 5))
    _drain(engine, handles)

    for h, p in zip(handles, prompts):
        want = _oracle(params, cfg, p, h.max_new_tokens)
        assert h.tokens == want, f"request {h.request_id} diverged"
        assert h.finish_reason == "length"
    # zero recompiles after warmup: ONE persistent decode step regardless
    # of arrival pattern; admissions compile once per distinct prompt len
    assert decode_step_cache_size() == decode_compiles


def test_admission_compiles_once_per_prompt_length(model):
    params, cfg = model
    engine = ContinuousBatchingEngine(params, cfg, n_slots=2,
                                      token_budget=32, queue_depth=16)
    h = engine.submit(_prompts(cfg, (7,))[0], 2)
    _drain(engine, [h])
    admit_compiles = admit_step_cache_size()
    # same length again (twice) -> no new admission compile
    hs = [engine.submit(p, 2) for p in _prompts(cfg, (7, 7), seed=3)]
    _drain(engine, hs)
    assert admit_step_cache_size() == admit_compiles


def test_slot_recycling_under_eos_latch(model):
    """A row finishing on eos frees its slot immediately; the next queued
    request runs in the recycled slot and still matches its oracle."""
    params, cfg = model
    prompts = _prompts(cfg, (6, 9, 4), seed=1)
    # pick an eos that fires mid-stream for prompt 0 (from the oracle)
    full = _oracle(params, cfg, prompts[0], 8)
    eos = full[2]
    engine = ContinuousBatchingEngine(params, cfg, n_slots=1,
                                      token_budget=32, queue_depth=8,
                                      eos_id=eos)
    handles = [engine.submit(prompts[0], 8), engine.submit(prompts[1], 4),
               engine.submit(prompts[2], 4)]
    _drain(engine, handles)

    first = handles[0]
    assert first.finish_reason == "eos"
    assert first.tokens[-1] == eos
    assert first.tokens == full[:len(first.tokens)]
    # the recycled slot served the queued requests; oracle with the SAME
    # eos latch (offline pads with eos after the latch — engine stops)
    for h, p in zip(handles[1:], prompts[1:]):
        want = _oracle(params, cfg, p, h.max_new_tokens, eos_id=eos)
        assert h.tokens == want[:len(h.tokens)]
        if h.finish_reason == "eos":
            assert h.tokens[-1] == eos
        else:
            assert len(h.tokens) == h.max_new_tokens
    assert engine.active_slots() == 0


def test_quant_cache_composes_with_engine(model):
    """int8 KV slots: engine greedy == offline generate(quant_cache=True)
    — both paths quantize identical rows via the shared write path."""
    params, cfg = model
    prompts = _prompts(cfg, (8, 6), seed=2)
    engine = ContinuousBatchingEngine(params, cfg, n_slots=2,
                                      token_budget=32, queue_depth=8,
                                      quant_cache=True)
    handles = [engine.submit(p, 5) for p in prompts]
    _drain(engine, handles)
    for h, p in zip(handles, prompts):
        assert h.tokens == _oracle(params, cfg, p, 5, quant_cache=True)


def test_submit_validation(model):
    params, cfg = model
    engine = ContinuousBatchingEngine(params, cfg, n_slots=1,
                                      token_budget=16, queue_depth=2)
    with pytest.raises(BudgetExceededError):
        engine.submit(list(range(10)), 10)      # 20 > budget 16
    with pytest.raises(BudgetExceededError):
        engine.submit([], 4)
    engine.submit([1, 2, 3], 4)
    engine.submit([1, 2, 3], 4)
    with pytest.raises(QueueFullError):
        engine.submit([1, 2, 3], 4)             # queue_depth=2


def test_per_request_latency_breakdown(model):
    """Every finished request carries queue_wait/prefill/decode stamps;
    the snapshot exposes p50/p95/p99 per phase and metrics() ships the
    tails over the AM channel (PR5 pillar 3). A second wave submitted
    while slots are busy must observe a strictly positive queue wait."""
    params, cfg = model
    engine = ContinuousBatchingEngine(params, cfg, n_slots=1,
                                      token_budget=32, queue_depth=8)
    prompts = _prompts(cfg, (4, 4), seed=7)
    finished = []
    engine.on_request_finished = finished.append
    h1 = engine.submit(prompts[0], 4)
    h2 = engine.submit(prompts[1], 4)   # queued behind h1's only slot
    _drain(engine, [h1, h2])
    for h in (h1, h2):
        assert h.queue_wait_s is not None and h.queue_wait_s >= 0
        assert h.prefill_s is not None and h.prefill_s > 0
        assert h.decode_s is not None and h.decode_s >= 0
    # h2 waited for h1's slot: its queue phase is real time, not epsilon
    assert h2.queue_wait_s > h1.queue_wait_s
    assert [h.request_id for h in finished] == [h1.request_id,
                                                h2.request_id]
    snap = engine.snapshot()
    for phase in ("queue_wait_s", "prefill_s", "decode_ms_per_token"):
        for tag in ("p50", "p95", "p99"):
            assert snap[f"{phase}_{tag}"] is not None, (phase, tag)
    assert snap["queue_wait_s_p99"] >= snap["queue_wait_s_p50"]
    names = {m["name"] for m in engine.metrics()}
    assert {"SERVING_QUEUE_WAIT_P95_S", "SERVING_PREFILL_P95_S",
            "SERVING_DECODE_P95_MS"} <= names
    engine.stop()


def test_queued_token_budget_sheds_before_request_count(model):
    """The queued-WORK bound: a few near-budget requests shed load even
    while the request-count bound still has room."""
    params, cfg = model
    engine = ContinuousBatchingEngine(params, cfg, n_slots=1,
                                      token_budget=16, queue_depth=4)
    assert engine.queue_token_budget == 32       # queue_depth * budget / 2
    engine.submit(list(range(12)), 4)            # 16 tokens
    engine.submit(list(range(12)), 4)            # 32 tokens pending
    with pytest.raises(QueueFullError, match="token budget"):
        engine.submit(list(range(12)), 4)        # count 2 < 4, tokens full


# ---------------------------------------------------------------------------
# frontend
# ---------------------------------------------------------------------------

def _post(port, body, timeout=60):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/generate",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=timeout)


def test_frontend_blocking_streaming_and_metrics(model):
    params, cfg = model
    prompts = _prompts(cfg, (6,), seed=4)
    engine = ContinuousBatchingEngine(params, cfg, n_slots=2,
                                      token_budget=32, queue_depth=8)
    engine.start()
    frontend = ServeFrontend(engine, port=0, host="127.0.0.1")
    frontend.start()
    try:
        want = _oracle(params, cfg, prompts[0], 5)
        # blocking
        resp = json.loads(_post(frontend.port,
                                {"prompt": prompts[0],
                                 "max_new_tokens": 5}).read())
        assert resp["tokens"] == want
        assert resp["finish_reason"] == "length"
        # streaming: chunked JSON lines ending in a done record
        with _post(frontend.port, {"prompt": prompts[0],
                                   "max_new_tokens": 5,
                                   "stream": True}) as r:
            lines = [json.loads(ln) for ln in r.read().splitlines()]
        assert [rec["token"] for rec in lines[:-1]] == want
        assert lines[-1]["done"] and lines[-1]["n_tokens"] == 5
        # metrics snapshot reflects the traffic
        snap = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{frontend.port}/v1/metrics",
            timeout=10).read())
        assert snap["tokens_emitted"] >= 10
        assert snap["ttft_p50_s"] is not None
        # healthz
        ok = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{frontend.port}/healthz",
            timeout=10).read())
        assert ok == {"ok": True}
    finally:
        frontend.stop()
        engine.stop()


def test_frontend_backpressure_fills_429_then_drains_and_accepts(model):
    """Bounded queue fills -> 429 with Retry-After; drains -> accepts."""
    params, cfg = model
    prompt = _prompts(cfg, (4,), seed=5)[0]
    engine = ContinuousBatchingEngine(params, cfg, n_slots=1,
                                      token_budget=16, queue_depth=2)
    # engine NOT stepping: fill the queue deterministically
    held = [engine.submit(prompt, 3), engine.submit(prompt, 3)]
    frontend = ServeFrontend(engine, port=0, host="127.0.0.1")
    frontend.start()
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(frontend.port, {"prompt": prompt, "max_new_tokens": 3})
        assert e.value.code == 429
        assert e.value.headers.get("Retry-After")
        # the shed request is a first-class SLI now: the admission
        # counters feed the reject-rate burn-rate alert rule, and the
        # scrape carries them
        snap = engine.snapshot()
        assert snap["requests_rejected"] == 1
        assert snap["requests_submitted"] == 2     # the two held ones
        names = {m["name"]: m["value"] for m in engine.metrics()}
        assert names["SERVING_REJECTED_TOTAL"] == 1.0
        assert names["SERVING_SUBMITTED_TOTAL"] == 2.0
        with urllib.request.urlopen(
                f"http://127.0.0.1:{frontend.port}/v1/metrics"
                f"?format=prometheus", timeout=10) as resp:
            exposition = resp.read().decode()
        assert "tony_serving_requests_rejected" in exposition
        # a never-fits request is a 400, not a retryable 429 — and not
        # a reject-rate SLI event either (retrying can never help)
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(frontend.port, {"prompt": prompt, "max_new_tokens": 99})
        assert e.value.code == 400
        assert engine.snapshot()["requests_rejected"] == 1
        # drain, then the same request is accepted and served
        engine.start()
        _drain_started(held)
        resp = json.loads(_post(frontend.port,
                                {"prompt": prompt,
                                 "max_new_tokens": 3}).read())
        assert resp["tokens"] == _oracle(params, cfg, prompt, 3)
    finally:
        frontend.stop()
        engine.stop()


def _drain_started(handles, timeout=60.0):
    deadline = time.monotonic() + timeout
    for h in handles:
        if not h.done.wait(timeout=max(0.0, deadline - time.monotonic())):
            raise AssertionError("started engine did not drain the queue")


def test_cancel_frees_slot_and_drops_pending(model):
    """A cancelled in-flight request frees its slot at the next step; a
    cancelled pending request is dropped without ever paying a prefill —
    the remaining request still matches its oracle."""
    params, cfg = model
    prompts = _prompts(cfg, (6, 5, 7), seed=7)
    engine = ContinuousBatchingEngine(params, cfg, n_slots=1,
                                      token_budget=32, queue_depth=8)
    inflight = engine.submit(prompts[0], 20)
    queued_cancel = engine.submit(prompts[1], 4)
    survivor = engine.submit(prompts[2], 4)
    engine.step()                      # admits inflight, decodes once
    assert engine.active_slots() == 1
    inflight.cancel()
    queued_cancel.cancel()
    _drain(engine, [inflight, queued_cancel, survivor])
    assert inflight.finish_reason == "cancelled"
    assert len(inflight.tokens) < 20   # stopped well short of max_new
    assert queued_cancel.finish_reason == "cancelled"
    assert queued_cancel.tokens == []  # never admitted
    assert survivor.tokens == _oracle(params, cfg, prompts[2], 4)


def test_engine_stop_fails_outstanding_requests(model):
    params, cfg = model
    prompt = _prompts(cfg, (4,), seed=6)[0]
    engine = ContinuousBatchingEngine(params, cfg, n_slots=1,
                                      token_budget=16, queue_depth=4)
    pending = [engine.submit(prompt, 3) for _ in range(3)]
    engine.stop()
    for h in pending:
        assert h.done.is_set() and h.finish_reason == "shutdown"
    with pytest.raises(RuntimeError):
        engine.submit(prompt, 3)


def test_runtimes_render_serving_port():
    """A serving task's env carries the port IT registered at the barrier
    — the cluster-spec entry and the bound HTTP port must be one and the
    same endpoint."""
    from tony_tpu.conf import TonyConfiguration
    from tony_tpu.executor.runtimes import render_framework_env

    spec = {"serving": ["h1:5001", "h2:5002"], "worker": ["h3:6001"]}
    env = render_framework_env("jax", spec, "serving", 1,
                               TonyConfiguration())
    assert env["SERVING_PORT"] == "5002"
    # non-serving tasks never get the var
    env = render_framework_env("jax", spec, "worker", 0,
                               TonyConfiguration())
    assert "SERVING_PORT" not in env


# ---------------------------------------------------------------------------
# the serving jobtype, end to end on the local backend
# ---------------------------------------------------------------------------

def _port_closed(host, port, attempts=50):
    for _ in range(attempts):
        try:
            with socket.create_connection((host, port), timeout=0.5):
                time.sleep(0.1)
        except OSError:
            return True
    return False


def test_serving_jobtype_e2e_endpoint_proxy_and_clean_shutdown(tmp_path):
    """`cli submit`-equivalent path with the serving jobtype: the AM
    launches `python -m tony_tpu.serve`, the endpoint lands in task infos
    + history, /v1/generate answers THROUGH tony_tpu.proxy, and shutdown
    leaves no orphan process or held port."""
    from tony_tpu import constants as C
    from tony_tpu.client.tony_client import TonyClient
    from tony_tpu.conf import TonyConfiguration, keys as K
    from tony_tpu.events.handler import parse_events
    from tony_tpu.events.schema import EventType
    from tony_tpu.proxy import ProxyServer
    from tony_tpu.rpc.client import ClusterServiceClient

    conf = TonyConfiguration()
    conf.set(K.CLUSTER_WORKDIR, str(tmp_path), "test")
    conf.set(K.AM_MONITOR_INTERVAL_MS, 100, "test")
    conf.set(K.TASK_HEARTBEAT_INTERVAL_MS, 200, "test")
    conf.set(K.AM_STOP_POLL_TIMEOUT_MS, 3000, "test")
    conf.set(K.TASK_METRICS_INTERVAL_MS, 300, "test")
    conf.set(K.SERVING_SLOTS, 2, "test")
    conf.set(K.SERVING_TOKEN_BUDGET, 64, "test")
    conf.set(K.SERVING_QUEUE_DEPTH, 8, "test")
    client = TonyClient(conf)
    client.init(["--conf", "tony.serving.instances=1"])
    client.submit()
    monitor = threading.Thread(target=client.monitor, daemon=True)
    monitor.start()
    endpoint = None
    try:
        # wait for the AM RPC, then for the registered endpoint
        import os
        hostport_path = os.path.join(client.app_dir, C.AM_HOSTPORT_FILE)
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and not os.path.exists(
                hostport_path):
            time.sleep(0.1)
        assert os.path.exists(hostport_path), "AM never came up"
        with open(hostport_path) as f:
            host, _, port = f.read().strip().rpartition(":")
        rpc = ClusterServiceClient(host, int(port), retries=2,
                                   retry_sleep_sec=0.2, timeout_sec=5.0)
        while time.monotonic() < deadline and endpoint is None:
            try:
                infos = rpc.get_task_infos()
            except Exception:  # noqa: BLE001 — AM mid-boot
                infos = []
            for info in infos:
                if info.get("name") == "serving-endpoint":
                    endpoint = info["url"]
            if endpoint is None:
                time.sleep(0.2)
        assert endpoint, "serving endpoint never registered"
        srv_host = endpoint.split("//", 1)[1].rsplit(":", 1)[0]
        srv_port = int(endpoint.rsplit(":", 1)[1])

        # front the endpoint with the authenticated-capable TCP proxy
        proxy = ProxyServer(srv_host, srv_port, local_port=0)
        proxy.start()
        try:
            body = json.dumps({"prompt": [1, 2, 3, 4],
                               "max_new_tokens": 4}).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{proxy.local_port}/v1/generate",
                data=body,
                headers={"Content-Type": "application/json"})
            resp = json.loads(urllib.request.urlopen(req,
                                                     timeout=120).read())
            assert len(resp["tokens"]) == 4
            health = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{proxy.local_port}/healthz",
                timeout=30).read())
            assert health == {"ok": True}
        finally:
            proxy.stop()

        # give the serving metrics reporter (300 ms cadence) a couple of
        # pushes so the history carries SERVING_* gauges
        time.sleep(1.0)

        # shutdown: the client tells the AM to finish; the serving
        # container gets TERM->KILL and the executor reaps the server
        rpc.finish_application()
        rpc.close()
    finally:
        monitor.join(timeout=120)
        client.cleanup()
    assert not monitor.is_alive(), "client monitor never returned"
    # serving runs until told to stop: a client-initiated stop is KILLED
    assert client.final_status == "KILLED"
    # no orphan: the endpoint's port must be released
    assert _port_closed(srv_host, srv_port), \
        "serving port still open after shutdown — orphan server"
    # the endpoint registration is a history event (new schema entry)
    hist_base = os.path.join(client.app_dir, C.HISTORY_DIR_NAME)
    finals = [os.path.join(d, f) for d, _, files in os.walk(hist_base)
              for f in files if f.endswith(".jhist")]
    assert len(finals) == 1, finals
    events = parse_events(finals[0])
    served = [e for e in events
              if e.type == EventType.SERVING_ENDPOINT_REGISTERED]
    assert served and served[0].payload.url == endpoint
    assert served[0].payload.task_type == "serving"
    # serving metrics flowed through the trainer's metrics RPC path into
    # the AM store and out into history (what the portal job page shows)
    metric_names = {m.get("name")
                    for e in events if hasattr(e.payload, "metrics")
                    for m in e.payload.metrics}
    assert "SERVING_TOKENS_PER_SEC" in metric_names, metric_names
