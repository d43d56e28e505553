"""Observability subsystem tests (ISSUE 4).

Covers: Prometheus exposition round-trip (label escaping, NaN/±Inf),
the TimeSeries ring buffer's decimation, the MetricsStore timeseries +
copy-semantics regression, span recorder/store bounding, the
TpuMetricsReporter drop counter + bounded close, liveliness
detection-latency numbers, the AM /metrics scrape server, the serving
frontend's content-negotiated exposition — and one full-stack e2e run
proving trace-context propagation client → AM → executor → trainer on
the local backend, with the portal serving the waterfall and
/jobs/:id/metrics.json out of the flushed history.
"""

from __future__ import annotations

import json
import math
import os
import threading
import time
import urllib.request

import pytest

from tony_tpu import constants as C
from tony_tpu.observability import prometheus as prom
from tony_tpu.observability.metrics import (
    REGISTRY, MetricsRegistry, TimeSeries,
)
from tony_tpu.observability.trace import Span, SpanRecorder, SpanStore

SCRIPTS = os.path.join(os.path.dirname(__file__), "scripts")


def script(name: str) -> str:
    return os.path.join(SCRIPTS, name)


# ---------------------------------------------------------------------------
# prometheus exposition
# ---------------------------------------------------------------------------

def test_exposition_roundtrip_values_and_labels():
    families = [
        {"name": "tony_test_gauge", "type": "gauge", "help": "a gauge",
         "samples": [
             ({"task_type": "worker", "index": "0"}, 1.5),
             ({"task_type": "worker", "index": "1"}, 3.0),
             ({}, 42.0),
         ]},
        {"name": "tony_test_total", "type": "counter", "help": "",
         "samples": [({"status": "ok"}, 7.0)]},
    ]
    parsed = prom.parse(prom.render(families))
    assert prom.get_sample(parsed, "tony_test_gauge",
                           task_type="worker", index="0") == 1.5
    assert prom.get_sample(parsed, "tony_test_gauge", index="1") == 3.0
    assert parsed[("tony_test_gauge", ())] == 42.0
    assert prom.get_sample(parsed, "tony_test_total", status="ok") == 7.0


def test_exposition_label_escaping_roundtrip():
    ugly = 'a"b\\c\nd'
    text = prom.render([{"name": "m", "type": "gauge", "help": "",
                         "samples": [({"k": ugly}, 1.0)]}])
    parsed = prom.parse(text)
    assert parsed[("m", (("k", ugly),))] == 1.0


def test_exposition_nan_and_inf():
    text = prom.render([{"name": "m", "type": "gauge", "help": "",
                         "samples": [({"v": "nan"}, float("nan")),
                                     ({"v": "pinf"}, float("inf")),
                                     ({"v": "ninf"}, float("-inf"))]}])
    parsed = prom.parse(text)
    assert math.isnan(prom.get_sample(parsed, "m", v="nan"))
    assert prom.get_sample(parsed, "m", v="pinf") == float("inf")
    assert prom.get_sample(parsed, "m", v="ninf") == float("-inf")


def test_exposition_name_sanitization():
    assert prom.sanitize_metric_name("9bad-name!x") == "_9bad_name_x"
    assert prom.sanitize_metric_name("") == "_"
    assert prom.task_metric_name("SERVING_TTFT_P50_S") == \
        "tony_serving_ttft_p50_s"
    assert prom.task_metric_name("tony_already") == "tony_already"
    # a hostile gauge name renders into a parseable line
    text = prom.render([{"name": "1 weird{name}", "type": "gauge",
                         "help": "", "samples": [({}, 1.0)]}])
    assert prom.parse(text)  # does not raise


def test_parse_rejects_malformed():
    with pytest.raises(ValueError):
        prom.parse("this is { not exposition\n")


# ---------------------------------------------------------------------------
# timeseries ring buffer + registry
# ---------------------------------------------------------------------------

def test_timeseries_bounded_with_full_run_coverage():
    ts = TimeSeries(max_points=16)
    for i in range(5000):
        ts.append(i, float(i))
    pts = ts.to_list()
    assert len(pts) <= 17                      # bounded (+ live tail)
    assert pts[0] == [0, 0.0]                  # run start survives
    assert pts[-1] == [4999, 4999.0]           # newest always present
    assert ts.stride > 1                       # it actually decimated
    assert [p[0] for p in pts] == sorted(p[0] for p in pts)


def test_timeseries_short_series_keeps_everything():
    ts = TimeSeries(max_points=64)
    ts.append(10, 1.0)
    ts.append(20, 2.0)
    assert ts.to_list() == [[10, 1.0], [20, 2.0]]


def test_timeseries_ignores_non_finite():
    ts = TimeSeries(max_points=8)
    ts.append(1, float("nan"))
    ts.append(2, float("inf"))
    assert ts.to_list() == []


def test_timeseries_decimation_under_width_1k_load():
    """ROADMAP item 3's 'verify it under load': a width-1024 gang's worth
    of MetricsStore series, each appended 8x past its cap, stays pinned
    at <= max_points per series (+ the live tail) with the run's start
    and newest sample both retained."""
    from tony_tpu.am.application_master import MetricsStore
    width, cap = 1024, 64
    store = MetricsStore(history_points=cap)
    batch = 16
    for i in range(width):
        for k in range(8 * cap // batch):
            store.update_metrics(
                {"task_type": "worker", "index": i,
                 "metrics": [{"name": "TRAIN_STEP_TIME_MS",
                              "value": float(k * batch + j)}
                             for j in range(batch)]})
    series = store.timeseries_dict()
    assert len(series) == width
    max_pts = max(len(per["TRAIN_STEP_TIME_MS"]) for per in series.values())
    assert max_pts <= cap + 1, max_pts
    # the series still covers the whole run, not just the last N minutes
    sample = series["worker:0"]["TRAIN_STEP_TIME_MS"]
    assert sample[0][1] == 0.0
    assert sample[-1][1] == float(8 * cap - 1)


def test_span_store_bounded_under_width_1k_load():
    """SpanStore at width-1k: 1024 tasks x 16 spans against a 512 cap —
    held count pinned at the cap, every overflow counted, never grown."""
    cap = 512
    store = SpanStore(max_spans=cap)
    for i in range(1024):
        store.add([{"name": "user_process", "span_id": f"s{i}-{j}",
                    "trace_id": "t", "task_id": f"worker:{i}",
                    "start_ms": j, "end_ms": j + 1, "status": "OK"}
                   for j in range(16)])
    assert len(store) == cap
    assert store.dropped == 1024 * 16 - cap


def test_registry_families_and_snapshot():
    reg = MetricsRegistry()
    reg.counter("tony_x_total", status="ok").inc()
    reg.counter("tony_x_total", status="ok").inc(2)
    reg.gauge("tony_g").set(5.5)
    reg.summary("tony_lat_seconds", method="m").observe(0.2)
    reg.summary("tony_lat_seconds", method="m").observe(0.4)
    parsed = prom.parse(prom.render(reg.families()))
    assert prom.get_sample(parsed, "tony_x_total", status="ok") == 3.0
    assert prom.get_sample(parsed, "tony_g") == 5.5
    assert prom.get_sample(parsed, "tony_lat_seconds_count",
                           method="m") == 2.0
    assert prom.get_sample(parsed, "tony_lat_seconds_sum",
                           method="m") == pytest.approx(0.6)
    assert prom.get_sample(parsed, "tony_lat_seconds_max",
                           method="m") == pytest.approx(0.4)


def test_summary_quantiles_bounded_and_exposed():
    """ISSUE 7 satellite: Summary tracks p50/p95/p99 through the
    fixed-width sketch (never a sample list) and exposes them as
    quantile-labeled samples that round-trip the exposition."""
    reg = MetricsRegistry()
    s = reg.summary("tony_rt_seconds", method="m")
    for i in range(1, 1001):
        s.observe(i / 1000.0)           # 1ms .. 1s, uniform
    assert s.sketch.cells() == s.SKETCH_BUCKETS + 2   # memory is fixed
    assert s.quantile(0.5) == pytest.approx(0.5, rel=0.35)
    assert s.quantile(0.99) == pytest.approx(0.99, rel=0.35)
    parsed = prom.parse(prom.render(reg.families()))
    p50 = prom.get_sample(parsed, "tony_rt_seconds",
                          method="m", quantile="0.5")
    p99 = prom.get_sample(parsed, "tony_rt_seconds",
                          method="m", quantile="0.99")
    assert p50 == pytest.approx(s.quantile(0.5))
    assert p99 == pytest.approx(s.quantile(0.99))
    assert p50 < p99
    # quantiles sit inside the observed range
    assert 0.001 <= p50 <= 1.0 and 0.001 <= p99 <= 1.0


# ---------------------------------------------------------------------------
# MetricsStore: copy regression (satellite 1) + timeseries + exposition
# ---------------------------------------------------------------------------

def _store(**kw):
    from tony_tpu.am.application_master import MetricsStore
    return MetricsStore(**kw)


def test_get_metrics_returns_copies_not_aliases():
    """Regression: the returned list used to share the stored dicts, so a
    caller mutating a metric corrupted the store."""
    store = _store()
    store.update_metrics({"task_type": "worker", "index": 0,
                          "metrics": [{"name": "G", "value": 1.0}]})
    out = store.get_metrics("worker", 0)
    out[0]["value"] = 999.0
    out[0]["name"] = "EVIL"
    again = store.get_metrics("worker", 0)
    assert again == [{"name": "G", "value": 1.0}]


def test_metrics_store_accumulates_timeseries():
    store = _store(history_points=8)
    for v in (1.0, 2.0, 3.0):
        store.update_metrics({"task_type": "worker", "index": 0,
                              "metrics": [{"name": "STEP_TIME",
                                           "value": v}]})
    hist = store.get_history("worker", 0)
    assert [p[1] for p in hist["STEP_TIME"]] == [1.0, 2.0, 3.0]
    assert store.timeseries_dict()["worker:0"]["STEP_TIME"] == \
        hist["STEP_TIME"]
    # the merged latest-gauge view is unchanged by the timeseries layer
    assert store.get_metrics("worker", 0) == [{"name": "STEP_TIME",
                                               "value": 3.0}]


def test_metrics_store_prometheus_families_with_attempt_label():
    store = _store()
    store.update_metrics({"task_type": "worker", "index": 1, "attempt": 2,
                          "metrics": [{"name": "TPU_UTILIZATION",
                                       "value": 88.0}]})
    parsed = prom.parse(prom.render(store.prometheus_families("app_7")))
    assert prom.get_sample(parsed, "tony_tpu_utilization", app_id="app_7",
                           task_type="worker", index="1", attempt="2") \
        == 88.0


def test_span_only_pushes_do_not_feed_wedge_detection():
    """Span piggyback traffic (metrics=[]) is trace transport, not a
    metrics interval — it must not count as a missing-duty sample for
    the heartbeating-but-idle detector."""
    store = _store(low_util_intervals=2)
    store.update_metrics({"task_type": "worker", "index": 0,
                          "metrics": [{"name": "TPU_UTILIZATION",
                                       "value": 60.0}]})
    for _ in range(5):   # busy phase emitting only spans
        store.update_metrics({"task_type": "worker", "index": 0,
                              "metrics": [],
                              "spans": [{"name": "checkpoint_save",
                                         "start_ms": 1, "end_ms": 2}]})
    assert store.low_utilization_tasks() == []


def test_metrics_store_routes_spans_to_sink():
    store = _store()
    got: list[dict] = []
    store.span_sink = got.extend
    store.update_metrics({"task_type": "worker", "index": 0, "metrics": [],
                          "spans": [{"name": "s", "start_ms": 1,
                                     "end_ms": 2}]})
    assert [s["name"] for s in got] == ["s"]


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_recorder_parentage_and_env_propagation():
    rec = SpanRecorder(trace_id="app_1", task_id="worker:0", attempt=1,
                       parent_id="rootspan")
    outer = rec.start("user_process")
    env = rec.env(outer)
    assert env == {C.TONY_TRACE_ID: "app_1",
                   C.TONY_PARENT_SPAN: outer.span_id}
    child_rec = SpanRecorder.from_env(env, task_id="worker:0")
    inner = child_rec.start("trainer_setup")
    child_rec.end(inner)
    rec.end(outer, "ERROR", attrs={"exit_code": 1})
    [inner_d] = child_rec.drain()
    assert inner_d["parent_id"] == outer.span_id
    assert inner_d["trace_id"] == "app_1"
    [outer_d] = rec.drain()
    assert outer_d["parent_id"] == "rootspan"
    assert outer_d["status"] == "ERROR"
    assert outer_d["attrs"]["exit_code"] == 1
    assert outer_d["end_ms"] >= outer_d["start_ms"]
    # ending twice is a no-op, not a new record
    rec.end(outer)
    assert rec.drain() == []


def test_span_recorder_without_context_is_local_only():
    rec = SpanRecorder.from_env({})
    assert not rec.enabled
    assert rec.env() == {}
    with rec.span("anything"):
        pass
    assert len(rec.drain()) == 1   # still records locally


def test_span_store_is_bounded():
    store = SpanStore(max_spans=3)
    store.add([Span(name=f"s{i}", start_ms=i, end_ms=i + 1).to_dict()
               for i in range(5)])
    assert len(store) == 3
    assert store.dropped == 2
    assert [s["name"] for s in store.to_list()] == ["s0", "s1", "s2"]
    # junk entries are ignored, not stored
    store2 = SpanStore(max_spans=10)
    store2.add([{"no_name": True}, "not-a-dict", None])
    assert len(store2) == 0


def test_span_dict_roundtrip():
    s = Span(name="x", trace_id="t", parent_id="p", task_id="worker:0",
             attempt=2, start_ms=10, end_ms=30, status="OK",
             attrs={"k": "v"})
    assert Span.from_dict(s.to_dict()).to_dict() == s.to_dict()
    assert s.duration_ms == 20


# ---------------------------------------------------------------------------
# TpuMetricsReporter drops + bounded close (satellite 2)
# ---------------------------------------------------------------------------

def _reporter():
    from tony_tpu.train.metrics import TpuMetricsReporter
    return TpuMetricsReporter(env={C.AM_HOST: "127.0.0.1", C.AM_PORT: "1",
                                   C.JOB_NAME: "worker", C.TASK_INDEX: "0",
                                   C.TASK_ATTEMPT: "0"})


def test_reporter_counts_drops_and_close_is_bounded():
    reporter = _reporter()
    release = threading.Event()
    started = threading.Event()

    def wedged_push(payload):
        started.set()
        release.wait(10)

    reporter._push = wedged_push
    before = REGISTRY.counter("tony_metrics_push_dropped_total").value
    # worker takes the first payload and wedges; maxsize-2 queue fills
    # with the next two; everything after that is a counted drop
    for i in range(6):
        reporter._enqueue({"metrics": [{"name": "G", "value": float(i)}]})
    assert started.wait(5)
    deadline = time.monotonic() + 5
    while reporter.dropped == 0 and time.monotonic() < deadline:
        reporter._enqueue({"metrics": [{"name": "G", "value": 0.0}]})
        time.sleep(0.01)
    assert reporter.dropped >= 1
    assert REGISTRY.counter("tony_metrics_push_dropped_total").value \
        > before
    # queue.Full path of close(): the wedged worker still gets a BOUNDED
    # join — close must return promptly, not hang and not skip the join
    t0 = time.monotonic()
    reporter.close(timeout=0.3)
    assert time.monotonic() - t0 < 3.0
    assert reporter._worker is None
    release.set()


def test_reporter_clean_close_joins_worker():
    reporter = _reporter()
    reporter._push = lambda payload: None
    reporter._enqueue({"metrics": [{"name": "G", "value": 1.0}]})
    worker = reporter._worker
    reporter.close(timeout=5)
    assert not worker.is_alive()


def test_reporter_spans_ride_the_push_payload():
    reporter = _reporter()
    pushed: list[dict] = []
    reporter._push = pushed.append
    reporter.report_spans([{"name": "s", "start_ms": 1, "end_ms": 2}])
    deadline = time.monotonic() + 5
    while not pushed and time.monotonic() < deadline:
        time.sleep(0.01)
    assert pushed and pushed[0]["spans"][0]["name"] == "s"
    reporter.close(timeout=5)


# ---------------------------------------------------------------------------
# liveliness: heartbeat lag + detection latency (satellite 3)
# ---------------------------------------------------------------------------

def test_liveliness_records_ping_lag_and_detection_latency():
    from tony_tpu.am.liveliness import LivelinessMonitor

    expired = threading.Event()
    monitor = LivelinessMonitor(hb_interval_ms=50, max_missed=3,
                                on_expired=lambda tid, att: expired.set())
    monitor.start()
    try:
        monitor.register("worker:0", attempt=0)
        time.sleep(0.12)
        assert monitor.ping("worker:0")
        # the gap ran ~70ms past the 50ms cadence
        assert monitor.last_ping_lag_sec == pytest.approx(0.07, abs=0.05)
        # silence → expiry; detection latency >= the 150ms window
        assert expired.wait(5), "expiry never fired"
        assert monitor.last_detection_latency_sec >= 0.15
        # and it lands in the registry for the /metrics scrape
        parsed = prom.parse(prom.render(REGISTRY.families()))
        assert prom.get_sample(
            parsed, "tony_liveliness_detection_latency_seconds_count") >= 1
        assert prom.get_sample(
            parsed, "tony_heartbeat_lag_seconds_count") >= 1
    finally:
        monitor.stop()


# ---------------------------------------------------------------------------
# scrape endpoints
# ---------------------------------------------------------------------------

def test_metrics_http_server_serves_valid_exposition():
    from tony_tpu.observability.http import MetricsHTTPServer

    store = _store()
    store.update_metrics({"task_type": "worker", "index": 0, "attempt": 0,
                          "metrics": [{"name": "TOKENS_PER_SEC",
                                       "value": 123.0}]})
    server = MetricsHTTPServer(
        lambda: prom.render(store.prometheus_families("app_x")
                            + REGISTRY.families()),
        port=0, host="127.0.0.1")
    server.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/metrics",
                timeout=10) as resp:
            assert resp.headers["Content-Type"].startswith("text/plain")
            parsed = prom.parse(resp.read().decode("utf-8"))
        assert prom.get_sample(parsed, "tony_tokens_per_sec",
                               app_id="app_x", task_type="worker") == 123.0
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/nope", timeout=10) as _:
            pytest.fail("404 expected")
    except urllib.error.HTTPError as e:
        assert e.code == 404
    finally:
        server.stop()


class _FakeEngine:
    """Snapshot-only stand-in — frontend GETs never touch the compute
    plane, so the exposition path is testable without a model."""
    n_slots = 2
    token_budget = 32
    queue_depth = 8
    temperature = 0.0

    def snapshot(self):
        return {"tokens_per_sec": 10.0, "slot_occupancy_pct": 50.0,
                "queue_depth": 1, "ttft_p50_s": None, "token_budget": 32}


def test_serving_frontend_content_negotiation():
    from tony_tpu.serve.frontend import ServeFrontend

    frontend = ServeFrontend(_FakeEngine(), port=0, host="127.0.0.1")
    frontend.start()
    base = f"http://127.0.0.1:{frontend.port}"
    try:
        # default stays JSON (existing tooling contract)
        with urllib.request.urlopen(f"{base}/v1/metrics", timeout=10) as r:
            snap = json.loads(r.read())
        assert snap["tokens_per_sec"] == 10.0
        # a Prometheus scraper's Accept header gets text exposition
        req = urllib.request.Request(
            f"{base}/v1/metrics",
            headers={"Accept": "application/openmetrics-text;q=0.9,"
                               "text/plain;version=0.0.4"})
        with urllib.request.urlopen(req, timeout=10) as r:
            assert r.headers["Content-Type"].startswith("text/plain")
            parsed = prom.parse(r.read().decode())
        assert prom.get_sample(parsed,
                               "tony_serving_tokens_per_sec") == 10.0
        assert prom.get_sample(parsed,
                               "tony_serving_slot_occupancy_pct") == 50.0
        # no-traffic gauges are NaN, not absent
        assert math.isnan(prom.get_sample(parsed,
                                          "tony_serving_ttft_p50_s"))
        # ?format=prometheus forces it; bare /metrics always exposition
        for url in (f"{base}/v1/metrics?format=prometheus",
                    f"{base}/metrics"):
            with urllib.request.urlopen(url, timeout=10) as r:
                prom.parse(r.read().decode())   # valid exposition
    finally:
        frontend.stop()


# ---------------------------------------------------------------------------
# docs drift (satellite 6): new keys documented
# ---------------------------------------------------------------------------

def test_new_observability_keys_are_documented():
    doc = open(os.path.join(os.path.dirname(__file__), "..", "docs",
                            "configuration.md"), encoding="utf-8").read()
    for key in ("tony.metrics.history-points", "tony.metrics.port",
                "tony.trace.enabled", "tony.trace.max-spans"):
        assert key in doc, f"{key} missing from docs/configuration.md"


# ---------------------------------------------------------------------------
# e2e: trace context propagates client → AM → executor → trainer, and the
# portal serves the waterfall + metrics.json from the flushed history
# ---------------------------------------------------------------------------

def _fast_conf(tmp_path, **overrides):
    from tony_tpu.conf import TonyConfiguration, keys as K
    conf = TonyConfiguration()
    conf.set(K.CLUSTER_WORKDIR, str(tmp_path), "test")
    conf.set(K.AM_MONITOR_INTERVAL_MS, 100, "test")
    conf.set(K.TASK_HEARTBEAT_INTERVAL_MS, 200, "test")
    conf.set(K.TASK_METRICS_INTERVAL_MS, 500, "test")
    conf.set(K.TASK_REGISTRATION_TIMEOUT_SEC, 60, "test")
    conf.set(K.AM_STOP_POLL_TIMEOUT_MS, 2000, "test")
    for k, v in overrides.items():
        conf.set(k, v, "test")
    return conf


def test_e2e_trace_metrics_and_portal(tmp_path):
    from tony_tpu.client.tony_client import TonyClient
    from tony_tpu.events.history import read_metrics_file, read_spans_file
    from tony_tpu.portal.cache import PortalCache
    from tony_tpu.portal.server import PortalServer

    hist_inter = str(tmp_path / "hist-int")
    conf = _fast_conf(tmp_path, **{"tony.history.intermediate": hist_inter})
    client = TonyClient(conf)
    client.init(["--executes", script("emit_observability.py"),
                 "--conf", "tony.worker.instances=1"])
    result = {}

    def _run():
        result["ok"] = client.run()

    t = threading.Thread(target=_run, daemon=True)
    t.start()
    # while the worker sleeps, scrape the LIVE AM /metrics endpoint
    am_scrape = None
    port_file = None
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline and am_scrape is None:
        if port_file is None and client.app_dir:
            candidate = os.path.join(client.app_dir,
                                     C.AM_METRICS_PORT_FILE)
            if os.path.exists(candidate):
                port_file = candidate
        if port_file is not None:
            try:
                with open(port_file) as f:
                    port = int(f.read().strip())
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/metrics",
                        timeout=5) as resp:
                    am_scrape = resp.read().decode("utf-8")
            except (OSError, ValueError):
                pass
        time.sleep(0.05)
    t.join(timeout=120)
    assert result.get("ok") is True, client.final_message
    # the live scrape happened and was valid exposition
    assert am_scrape is not None, "never reached the AM /metrics endpoint"
    prom.parse(am_scrape)

    history_dir = os.path.join(hist_inter, client.app_id)
    # --- spans flushed next to the event log, full parent chain ---------
    spans = read_spans_file(history_dir)
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], s)
    for name in ("application", "client_submit", "rendezvous",
                 "task:worker:0", "executor_localization",
                 "rendezvous_wait", "user_process", "trainer_setup"):
        assert name in by_name, (name, sorted(by_name))
    assert all(s["trace_id"] == client.app_id for s in spans), spans
    root = by_name["application"]
    task = by_name["task:worker:0"]
    proc = by_name["user_process"]
    trainer = by_name["trainer_setup"]
    assert task["parent_id"] == root["span_id"]
    assert proc["parent_id"] == task["span_id"]
    assert trainer["parent_id"] == proc["span_id"]
    assert by_name["client_submit"]["start_ms"] <= root["start_ms"]
    assert by_name["rendezvous"]["status"] == "OK"
    assert proc["status"] == "OK" and proc["end_ms"] > proc["start_ms"]
    assert task["task_id"] == "worker:0"

    # --- metrics.json: >= 2 points per pushed gauge ---------------------
    series = read_metrics_file(history_dir)
    points = series["worker:0"]["E2E_TEST_GAUGE"]
    assert len(points) >= 2
    assert [p[1] for p in points[:2]] == [1.0, 2.0]

    # --- portal: waterfall on the job page + metrics.json route ---------
    server = PortalServer(PortalCache(hist_inter, str(tmp_path / "fin")),
                          port=0, host="127.0.0.1")
    server.start()
    base = f"http://127.0.0.1:{server.port}"
    try:
        with urllib.request.urlopen(
                f"{base}/jobs/{client.app_id}/metrics.json",
                timeout=10) as resp:
            served = json.loads(resp.read())
        assert len(served["worker:0"]["E2E_TEST_GAUGE"]) >= 2
        with urllib.request.urlopen(f"{base}/jobs/{client.app_id}",
                                    timeout=10) as resp:
            page = resp.read().decode("utf-8")
        assert "Lifecycle waterfall" in page
        assert "trainer_setup" in page and "rendezvous" in page
        assert "spanbar" in page
        with urllib.request.urlopen(
                f"{base}/api/jobs/{client.app_id}/spans",
                timeout=10) as resp:
            api_spans = json.loads(resp.read())
        assert {s["name"] for s in api_spans} >= {"application",
                                                  "user_process"}
    finally:
        server.stop()
