"""Goodput ledger / MFU / SLO watchdog / on-demand profiler (PR 5).

Unit layer: the perf.py state machines with fake clocks. E2E layer: the
genuine client → AM → executor → user-python chain on the local backend
— the ledger invariant in history's goodput.json, relaunch downtime
under a chaos kill, and the full request_profile workflow (RPC →
heartbeat piggyback → executor file relay → ProfileCapture → metrics
RPC publish → history artifact + event, idempotent on double-request).
"""

from __future__ import annotations

import json
import os
import threading
import time

import pytest

from tony_tpu import constants as C
from tony_tpu.conf import TonyConfiguration, keys as K
from tony_tpu.events.handler import parse_events
from tony_tpu.events.history import read_goodput_file
from tony_tpu.events.schema import EventType
from tony_tpu.observability import perf

pytestmark = pytest.mark.profiling

SCRIPTS = os.path.join(os.path.dirname(__file__), "scripts")


def script(name: str) -> str:
    return os.path.join(SCRIPTS, name)


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self) -> float:
        return self.t

    def tick(self, s: float) -> None:
        self.t += s


# ---------------------------------------------------------------------------
# goodput ledger units
# ---------------------------------------------------------------------------

def test_ledger_phases_sum_to_wall_exactly():
    clock = FakeClock()
    ledger = perf.GoodputLedger(clock=clock)
    clock.tick(1.0)
    ledger.transition("compile")
    clock.tick(2.0)
    ledger.transition("train_step")
    clock.tick(5.0)
    ledger.carve("input_stall", 0.75)
    clock.tick(1.0)
    ledger.transition("idle")
    clock.tick(0.5)
    snap = ledger.snapshot()
    assert snap["wall_s"] == pytest.approx(9.5)
    assert sum(snap["phases"].values()) == pytest.approx(snap["wall_s"])
    assert snap["phases"]["init"] == pytest.approx(1.0)
    assert snap["phases"]["compile"] == pytest.approx(2.0)
    # carve moved stall seconds OUT of train_step, not on top of it
    assert snap["phases"]["input_stall"] == pytest.approx(0.75)
    assert snap["phases"]["train_step"] == pytest.approx(6.0 - 0.75)
    assert snap["phases"]["idle"] == pytest.approx(0.5)


def test_ledger_carve_from_closed_source_phase():
    """The end-of-run flush runs from idle but late stall seconds must
    still come out of train_step — carve(source=...) reattributes from a
    CLOSED phase without breaking the sum-to-wall invariant."""
    clock = FakeClock()
    ledger = perf.GoodputLedger(clock=clock)
    ledger.transition("train_step")
    clock.tick(4.0)
    ledger.transition("idle")
    clock.tick(0.1)
    ledger.carve("input_stall", 0.5, source="train_step")
    snap = ledger.snapshot()
    assert snap["phases"]["train_step"] == pytest.approx(3.5)
    assert snap["phases"]["input_stall"] == pytest.approx(0.5)
    assert snap["phases"]["idle"] == pytest.approx(0.1)
    assert sum(snap["phases"].values()) == pytest.approx(snap["wall_s"])


def test_ledger_open_phase_counts_mid_flight():
    clock = FakeClock()
    ledger = perf.GoodputLedger(clock=clock)
    clock.tick(3.0)
    snap = ledger.snapshot()   # "init" still open
    assert snap["phases"]["init"] == pytest.approx(3.0)
    assert sum(snap["phases"].values()) == pytest.approx(snap["wall_s"])


def test_ledger_seed_extends_wall():
    """The executor's localization/rendezvous seed is closed time that
    the trainer-side ledger's wall must include — the handoff preserves
    the sum-to-wall invariant across two processes."""
    clock = FakeClock()
    ledger = perf.GoodputLedger(
        clock=clock, seed={"localization": 2.0, "rendezvous_wait": 1.5})
    clock.tick(4.0)
    ledger.transition("idle")
    snap = ledger.snapshot()
    assert snap["wall_s"] == pytest.approx(7.5)
    assert snap["phases"]["localization"] == pytest.approx(2.0)
    assert snap["phases"]["rendezvous_wait"] == pytest.approx(1.5)
    assert sum(snap["phases"].values()) == pytest.approx(snap["wall_s"])


def test_ledger_from_env_and_metrics_roundtrip():
    env = {C.TONY_GOODPUT_SEED:
           json.dumps({"localization": 1.25, "rendezvous_wait": 0.5})}
    ledger = perf.GoodputLedger.from_env(env)
    metrics = ledger.metrics()
    gauges = {m["name"]: m["value"] for m in metrics}
    assert gauges[perf.goodput_metric_name("localization")] == 1.25
    parsed = perf.parse_goodput_gauges(gauges)
    assert parsed["phases"]["localization"] == 1.25
    assert parsed["wall_s"] == pytest.approx(gauges[
        perf.GOODPUT_WALL_METRIC])
    # garbage env never breaks a trainer
    assert perf.GoodputLedger.from_env(
        {C.TONY_GOODPUT_SEED: "not json"}).snapshot()["wall_s"] >= 0


def test_aggregate_goodput_math():
    per_task = {
        "worker:0": {
            perf.goodput_metric_name("train_step"): 8.0,
            perf.goodput_metric_name("compile"): 1.0,
            perf.goodput_metric_name("idle"): 1.0,
            perf.GOODPUT_WALL_METRIC: 10.0,
            "TRAIN_MFU_PCT": 45.0,
        },
        "worker:1": {
            perf.goodput_metric_name("train_step"): 6.0,
            perf.goodput_metric_name("input_stall"): 4.0,
            perf.GOODPUT_WALL_METRIC: 10.0,
        },
        "ps:0": {"SOME_OTHER_GAUGE": 3.0},   # no ledger -> excluded
    }
    out = perf.aggregate_goodput(per_task, relaunch_downtime_s=5.0)
    assert set(out["tasks"]) == {"worker:0", "worker:1"}
    assert out["tasks"]["worker:0"]["mfu_pct"] == 45.0
    job = out["job"]
    assert job["productive_s"] == pytest.approx(14.0)
    assert job["wall_s"] == pytest.approx(25.0)
    assert job["relaunch_downtime_s"] == 5.0
    assert job["goodput_pct"] == pytest.approx(100.0 * 14.0 / 25.0,
                                               abs=0.01)


def test_goodput_report_table():
    from tools.goodput_report import format_report
    out = perf.aggregate_goodput({
        "worker:0": {perf.goodput_metric_name("train_step"): 9.0,
                     perf.goodput_metric_name("idle"): 1.0,
                     perf.GOODPUT_WALL_METRIC: 10.0,
                     "TRAIN_MFU_PCT": 50.0}})
    text = format_report(out)
    assert "train_step" in text and "90.0%" in text
    assert "job goodput" in text and "50.00%" in text


# ---------------------------------------------------------------------------
# MFU units
# ---------------------------------------------------------------------------

class _Dev:
    def __init__(self, platform="tpu", kind="TPU v5 lite"):
        self.platform = platform
        self.device_kind = kind


def test_peak_flops_and_mfu_shared_definition():
    # the v5e chip names itself "TPU v5 lite": matched on purpose
    assert perf.peak_flops(_Dev()) == 197e12
    assert perf.peak_flops(_Dev(kind="TPU v5p")) == 459e12
    # the tools import the SAME function — one definition repo-wide
    import tools.aot_rank as aot_rank
    assert aot_rank.peak_flops is perf.peak_flops
    mfu = perf.mfu_pct(1000.0, 197e6, _Dev())
    assert mfu == pytest.approx(0.1)
    assert perf.mfu_pct(1000.0, 0.0, _Dev()) == 0.0


@pytest.mark.parametrize("dev", [
    _Dev(kind="TPU v9 mega"),               # an accelerator nobody listed
    _Dev(platform="cpu", kind="cpu"),       # no nominal CPU peak either
    _Dev(platform="gpu", kind="NVIDIA H100"),
], ids=["unknown-tpu", "cpu", "gpu"])
def test_peak_flops_raises_for_a_device_it_does_not_know(dev):
    """No function returns a peak rate for a device it does not know: a
    default would feed the trainer's MFU gauge, the AM, the portal and the
    alerts a utilization against a made-up peak."""
    with pytest.raises(ValueError, match="no peak FLOP/s known"):
        perf.peak_flops(dev)
    with pytest.raises(ValueError, match="no peak FLOP/s known"):
        perf.mfu_pct(1000.0, 197e6, dev)


def test_mfu_reported_for_llama_and_moe():
    """Acceptance: MFU inputs exist for BOTH model families, and the MoE
    config accounts ACTIVE params (top_k of n_experts), not total."""
    from tony_tpu.models.llama import get_config
    from tony_tpu.models.moe import get_moe_config
    llama = get_config("tiny")
    moe = get_moe_config("moe_tiny")
    assert llama.flops_per_token(64) > 0
    assert moe.flops_per_token(64) > 0
    assert moe.active_params() < moe.num_params()
    d, f, L = moe.dim, moe.ffn_dim, moe.n_layers
    expected_active = (type(llama).num_params(moe)
                       + L * ((moe.top_k - 1) * 3 * d * f
                              + d * moe.n_experts))
    assert moe.active_params() == expected_active


# One count of a training FLOP: the program's `flops_per_token` is the
# benchmark's `train_flops_per_token` (behind `mfu_pct`), to the last digit.

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


def _benchmark_on_path():
    import sys
    if _BENCH not in sys.path:
        sys.path.insert(0, _BENCH)      # its modules import `lib`


def _llama_family():
    """The benchmark's `llama` family and the train cell's configuration
    file, found as the harness finds them."""
    _benchmark_on_path()
    from lib import spec
    path = os.path.join(_BENCH, "configs", "mistral-7b-train.json")
    with open(path, encoding="utf-8") as f:
        cfg = json.load(f)
    return spec.load_family(path, cfg), cfg


def _as_source_keys(config) -> dict:
    """A LlamaConfig's widths under the names a configuration file
    gives them (what the family's counts.py reads)."""
    return {"hidden_size": config.dim, "intermediate_size": config.ffn_dim,
            "num_attention_heads": config.n_heads,
            "num_key_value_heads": config.n_kv_heads,
            "num_hidden_layers": config.n_layers,
            "vocab_size": config.vocab_size}


@pytest.mark.parametrize("name", [
    "mistral-7b-train", "llama3_8b", "llama3_70b", "llama3_1b_proxy",
    "bench_350m", "tiny"])
def test_program_counts_a_training_flop_as_the_benchmark_does(name):
    from tony_tpu.models.llama import get_config
    family, cfg = _llama_family()
    if name == "mistral-7b-train":      # as the family's program.py maps it
        config = family.program.program_config(cfg)
    else:
        config = get_config(name)
        cfg = _as_source_keys(config)
    for seq in (config.max_seq, 64, 333):
        assert config.flops_per_token(seq) == \
            family.counts.train_flops_per_token(cfg, seq)
    assert config.flops_per_token() == config.flops_per_token(config.max_seq)
    # the sizes both report agree too; what is not a matmul is the
    # embedding table and the norms
    assert config.num_params() == family.counts.total_params(cfg)
    assert config.matmul_params() == family.counts.matmul_params(cfg)
    assert config.num_params() - config.matmul_params() == (
        config.vocab_size * config.dim
        + (2 * config.n_layers + 1) * config.dim)


def test_train_cell_shape_reads_the_ledgers_mfu():
    """`train-4k`'s shape at the ledger's rate: 7.833 GFLOP a token (the
    parent counted 9.123) and, at 14 568 tokens/s on a v5e, the ledger's
    `mfu_pct` 57.92 (PERF.md section 6, PR 33)."""
    family, cfg = _llama_family()
    config = family.program.program_config(cfg)
    flops = config.flops_per_token(4096)
    assert flops == 7_832_862_720.0
    assert perf.mfu_pct(14568, flops, _Dev()) == pytest.approx(
        57.92, abs=0.005)


@pytest.mark.parametrize("name", ["moe_tiny", "mixtral_proxy"])
def test_moe_counts_active_matmuls_and_causal_attention(name):
    """6 x the weights a token is multiplied by — attention projections,
    top_k of the n_experts MLPs, the router, the head; no table, no norm
    — plus causal attention, as the dense count."""
    from tony_tpu.models.moe import get_moe_config
    moe = get_moe_config(name)
    d, f, L, hd = moe.dim, moe.ffn_dim, moe.n_layers, moe.head_dim
    per_layer = (2 * d * moe.n_heads * hd + 2 * d * moe.n_kv_heads * hd
                 + moe.top_k * 3 * d * f + d * moe.n_experts)
    active_matmul = L * per_layer + d * moe.vocab_size
    assert moe.matmul_params() == active_matmul
    assert moe.active_params() - moe.matmul_params() == (
        moe.vocab_size * d + (2 * L + 1) * d)
    for seq in (64, moe.max_seq):
        assert moe.flops_per_token(seq) == (
            6.0 * active_matmul + 6 * L * moe.n_heads * hd * seq)


def test_program_and_benchmark_agree_on_every_peak_both_name():
    """The trainer cannot import benchmark/, so the program keeps its own
    table of peaks; wherever both tables name a device they hold the same
    bf16 FLOP/s, and the benchmark's device is in the program's."""
    _benchmark_on_path()
    from lib.peaks import PEAKS
    shared = set(PEAKS) & set(perf.PEAK_FLOPS)
    assert "TPU v5 lite" in shared and set(PEAKS) <= set(perf.PEAK_FLOPS)
    for kind in shared:
        assert perf.PEAK_FLOPS[kind] == PEAKS[kind]["bf16_flops_per_s"]
        assert perf.peak_flops(_Dev(kind=kind)) == \
            PEAKS[kind]["bf16_flops_per_s"]


def test_tokens_in_batch_shapes():
    import numpy as np
    batch = {"inputs": np.zeros((4, 128)), "targets": np.zeros((4, 128))}
    assert perf.tokens_in_batch(batch) == 512
    assert perf.tokens_in_batch({"tokens": np.zeros((2, 65))}) == 130
    assert perf.tokens_in_batch({"images": np.zeros((8,))}) == 0
    assert perf.tokens_in_batch(None) == 0


# ---------------------------------------------------------------------------
# SLO watchdog units
# ---------------------------------------------------------------------------

def _series(values):
    return [[i, v] for i, v in enumerate(values)]


def test_slo_step_regression_latches_and_rearms():
    dog = perf.SloWatchdog(step_regression_pct=50.0)
    healthy = {"worker:0": _series([100, 101, 99, 100, 100, 102])}
    assert dog.check(healthy) == []
    slow = {"worker:0": _series([100, 101, 99, 100, 100, 180])}
    hits = dog.check(slow)
    assert len(hits) == 1 and hits[0]["kind"] == "step_time_regression"
    assert hits[0]["task_id"] == "worker:0"
    # latched: the same ongoing violation emits no second event
    assert dog.check(slow) == []
    assert dog.active() == ["step_time:worker:0"]
    # recovery re-arms the latch; a new regression fires again
    assert dog.check(healthy) == []
    assert dog.active() == []
    assert len(dog.check(slow)) == 1


def test_slo_goodput_floor_and_disabled_checks():
    dog = perf.SloWatchdog(goodput_floor_pct=60.0)
    assert dog.check({}, goodput_pct=75.0) == []
    hits = dog.check({}, goodput_pct=42.0)
    assert len(hits) == 1 and hits[0]["kind"] == "goodput_floor"
    assert dog.check({}, goodput_pct=41.0) == []     # latched
    assert dog.check({}, goodput_pct=80.0) == []     # recovered
    assert dog.active() == []
    # thresholds <= 0 disable everything
    off = perf.SloWatchdog()
    assert off.check({"w:0": _series([1, 1, 1, 1, 1, 99])},
                     goodput_pct=0.1) == []


# ---------------------------------------------------------------------------
# profile capture units
# ---------------------------------------------------------------------------

def _write_request(tmp_path, rid, steps=3):
    with open(os.path.join(tmp_path, C.PROFILE_REQUEST_FILE), "w",
              encoding="utf-8") as f:
        json.dump({"request_id": rid, "num_steps": steps}, f)


def test_profile_capture_counts_steps_and_publishes(tmp_path):
    started, stopped, published = [], [], []
    pc = perf.ProfileCapture(cwd=str(tmp_path), publish=published.append,
                             start_fn=started.append,
                             stop_fn=lambda: stopped.append(True))
    pc.poll()
    assert not pc.active and not started       # no request file yet
    _write_request(tmp_path, "req1", steps=3)
    pc.poll()
    assert pc.active and len(started) == 1
    assert started[0].endswith(os.path.join(C.PROFILES_DIR_NAME, "req1"))
    pc.on_step(); pc.on_step()
    assert pc.active and not published
    pc.on_step()
    assert not pc.active and stopped
    assert len(published) == 1
    pd = published[0]
    assert pd["request_id"] == "req1" and pd["num_steps"] == 3
    assert os.path.isdir(pd["path"])


def test_profile_capture_idempotent_per_request_id(tmp_path):
    started, published = [], []
    pc = perf.ProfileCapture(cwd=str(tmp_path), publish=published.append,
                             start_fn=started.append,
                             stop_fn=lambda: None)
    _write_request(tmp_path, "dup", steps=1)
    pc.poll(); pc.on_step()
    assert len(published) == 1
    # the request file is still on disk — the same id must never restart
    pc.poll()
    assert not pc.active and len(started) == 1
    # a NEW id does
    _write_request(tmp_path, "dup2", steps=1)
    pc.poll(); pc.on_step()
    assert len(published) == 2


# ---------------------------------------------------------------------------
# e2e: the genuine chain on the local backend
# ---------------------------------------------------------------------------

def _fast_conf(tmp_path, **overrides) -> TonyConfiguration:
    conf = TonyConfiguration()
    conf.set(K.CLUSTER_WORKDIR, str(tmp_path), "test")
    conf.set(K.AM_MONITOR_INTERVAL_MS, 100, "test")
    conf.set(K.TASK_HEARTBEAT_INTERVAL_MS, 200, "test")
    conf.set(K.TASK_METRICS_INTERVAL_MS, 500, "test")
    conf.set(K.TASK_REGISTRATION_TIMEOUT_SEC, 60, "test")
    conf.set(K.CONTAINER_ALLOCATION_TIMEOUT, 60_000, "test")
    conf.set(K.AM_STOP_POLL_TIMEOUT_MS, 3000, "test")
    for k, v in overrides.items():
        conf.set(k, v, "test")
    return conf


def test_goodput_ledger_e2e_sums_to_wall(tmp_path):
    """Acceptance: a local-backend run's goodput.json holds a ledger
    whose phases sum to wall-clock within 1%, with the executor's
    localization/rendezvous seed folded in and input_stall carved out;
    the AM derives a job goodput_pct from it."""
    from tony_tpu.client.tony_client import TonyClient
    hist = str(tmp_path / "hist-int")
    conf = _fast_conf(tmp_path,
                      **{"tony.history.intermediate": hist})
    client = TonyClient(conf)
    client.init(["--executes", script("goodput_task.py"),
                 "--conf", "tony.worker.instances=1"])
    assert client.run() is True, client.final_message

    goodput = read_goodput_file(os.path.join(hist, client.app_id))
    assert "worker:0" in goodput["tasks"], goodput
    entry = goodput["tasks"]["worker:0"]
    phases, wall = entry["phases"], entry["wall_s"]
    assert wall > 0
    assert abs(sum(phases.values()) - wall) <= 0.01 * wall, entry
    # the executor seed and the carve both made it into the books
    assert phases.get("rendezvous_wait", -1) >= 0
    assert phases["input_stall"] == pytest.approx(0.05, abs=0.01)
    assert phases["train_step"] > 0
    assert entry["mfu_pct"] == 41.5
    job = goodput["job"]
    assert job["relaunch_downtime_s"] == 0
    assert 0 < job["goodput_pct"] <= 100
    assert job["productive_s"] == pytest.approx(phases["train_step"],
                                                rel=0.01)


@pytest.mark.chaos
def test_relaunch_downtime_attributed_under_chaos_kill(tmp_path):
    """Acceptance: a chaos-harness mid-run kill's relaunch gap lands in
    goodput.json as job-level relaunch_downtime_s > 0 (wall-clock no
    task process existed to account for, charged against goodput)."""
    from tests.chaos import ChaosRun, KillTask
    run = ChaosRun(tmp_path, seed=11)
    run.run(
        ["--executes", script("chaos_gang_worker.py"),
         "--conf", "tony.worker.instances=2",
         "--conf", "tony.task.max-task-attempts=2"],
        injections=[KillTask("worker", 1, run.delay_ms(800, 1200),
                             attempt=0)])
    assert run.final_status == "SUCCEEDED", run.all_logs()
    assert len(run.relaunches()) == 1
    history_dir = os.path.join(run.client.app_dir, C.HISTORY_DIR_NAME,
                               run.client.app_id)
    goodput = read_goodput_file(history_dir)
    assert goodput["job"]["relaunch_downtime_s"] > 0, goodput


def test_request_profile_e2e(tmp_path):
    """Acceptance: request_profile against a live AM rides the heartbeat
    to the executor, the ProfileCapture state machine captures + ships
    the artifact over the metrics RPC, and the AM links it into history
    (profiles/<rid>/ + PROFILE_CAPTURED event). A double-request while
    in flight returns the same request_id and yields ONE artifact."""
    from tony_tpu.client.tony_client import TonyClient
    from tony_tpu.rpc.client import ClusterServiceClient
    hist = str(tmp_path / "hist-int")
    conf = _fast_conf(tmp_path,
                      **{"tony.history.intermediate": hist,
                         "tony.profiling.default-steps": 2})
    client = TonyClient(conf)
    client.init(["--executes", script("profile_capture_task.py"),
                 "--conf", "tony.worker.instances=1"])
    result = {}

    def _run():
        result["ok"] = client.run()

    t = threading.Thread(target=_run, daemon=True)
    t.start()
    # wait for the AM's RPC endpoint, then request a profile (twice)
    rpc = None
    first = None
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline and first is None:
        hostport = os.path.join(client.app_dir or "", C.AM_HOSTPORT_FILE)
        if client.app_dir and os.path.exists(hostport):
            if rpc is None:
                with open(hostport, "r", encoding="utf-8") as f:
                    host, _, port = f.read().strip().rpartition(":")
                rpc = ClusterServiceClient(host, int(port))
            resp = rpc.request_profile()
            if not resp.get("error"):
                first = resp
        time.sleep(0.1)
    assert first is not None, "request_profile never succeeded"
    assert first["task_id"] == "worker:0"
    assert first["num_steps"] == 2
    # idempotent while in flight: same id, flagged duplicate
    second = rpc.request_profile()
    assert second["request_id"] == first["request_id"]
    assert second.get("duplicate") is True
    rpc.close()
    t.join(timeout=120)
    assert result.get("ok") is True, client.final_message

    rid = first["request_id"]
    history_dir = os.path.join(hist, client.app_id)
    artifact = os.path.join(history_dir, C.PROFILES_DIR_NAME, rid,
                            "trace.xplane.pb")
    assert os.path.isfile(artifact), os.listdir(history_dir)
    finals = [os.path.join(history_dir, f)
              for f in os.listdir(history_dir) if f.endswith(".jhist")]
    assert len(finals) == 1
    captured = [e for e in parse_events(finals[0])
                if e.type == EventType.PROFILE_CAPTURED]
    assert len(captured) == 1, captured
    ev = captured[0].payload
    assert ev.request_id == rid
    assert (ev.task_type, ev.task_index) == ("worker", 0)
    assert ev.path == os.path.join(C.PROFILES_DIR_NAME, rid)
    assert ev.num_steps == 2


def test_portal_profile_post_rejects_finished_job(tmp_path):
    """The portal's one write route: a finished (or AM-less) job answers
    409, not a hang — the AM address file is only meaningful while the
    job runs."""
    import urllib.error
    import urllib.request
    from tony_tpu.events.handler import EventHandler
    from tony_tpu.events.history import JobMetadata
    from tony_tpu.portal.cache import PortalCache
    from tony_tpu.portal.server import PortalServer

    inter = tmp_path / "inter"
    app = "application_perf_1"
    md = JobMetadata(application_id=app, started=1000)
    handler = EventHandler(str(inter / app), md)
    handler.start()
    handler.stop("SUCCEEDED")
    cache = PortalCache(str(inter), str(tmp_path / "fin"))
    server = PortalServer(cache, port=0, host="127.0.0.1")
    server.start()
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{server.port}/api/jobs/{app}/profile",
            data=b"{}", method="POST")
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=10)
        assert exc.value.code == 409
        body = json.loads(exc.value.read())
        assert "running" in body["error"]
    finally:
        server.stop()
