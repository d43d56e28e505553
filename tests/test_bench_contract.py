"""The host control-plane harness's contract (tools/control_plane_bench.py):
what it appends to its history, what every appended line discloses, and
how tools/bench_compare.py gates that history. CPU numbers under CPU
names; the chip's numbers are benchmark/'s."""

import json
import os
import subprocess
import sys

import tools.control_plane_bench as bench

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _isolated_bench_paths(tmp_path, monkeypatch):
    """The one path the harness writes rides through this module global;
    redirecting it means no test can ever leak a fabricated measurement
    into the real tools/ history."""
    tools = tmp_path / "tools"
    tools.mkdir()
    monkeypatch.setattr(bench, "_HISTORY_PATH",
                        str(tools / "bench_history.jsonl"))
    monkeypatch.setattr(bench, "_commit_stamp", lambda: "testhead")
    yield tools


def test_history_append_and_regression_verdict(_isolated_bench_paths):
    """Every gated headline is appended commit- and time-stamped to
    bench_history.jsonl, and bench_compare flags a >2% worsening against
    the best same-backend baseline (value<=0 markers are skipped both as
    baseline and as the judged entry)."""
    from tools.bench_compare import compare, load_history
    base = {"metric": "control_plane_all_registered", "unit": "s",
            "backend": "cpu", "width": 1024}
    good, wedged, bad = (dict(base, value=v) for v in (0.365, 0.0, 0.5))
    for r in (good, wedged, dict(bad, error="dropped", scraped_metrics="x")):
        bench._append_history(r)
    entries = load_history(str(_isolated_bench_paths
                               / "bench_history.jsonl"))
    assert len(entries) == 3
    assert all(e["commit"] == "testhead" and e["measured_at"]
               for e in entries)
    # heavy diagnostic fields never reach the history
    assert not any("error" in e or "scraped_metrics" in e for e in entries)
    verdicts = compare(entries, threshold_pct=2.0)
    assert len(verdicts) == 1          # one (metric, backend) group
    v = verdicts[0]
    assert v["backend"] == "cpu" and v["regression"] is True
    assert v["baseline"] == 0.365 and v["value"] == 0.5
    # within threshold → no regression
    ok = compare([good, dict(good, value=0.37)], threshold_pct=2.0)
    assert ok[0]["regression"] is False
    # a unit that is not a time or a size judges higher-is-better
    rate = [{"metric": "r", "value": 10.0, "unit": "1/s", "backend": "cpu"},
            {"metric": "r", "value": 8.0, "unit": "1/s", "backend": "cpu"}]
    assert compare(rate, threshold_pct=2.0)[0]["regression"] is True
    # bytes (the control-plane spec fan-out gate) are lower-is-better
    # too: a chatty regression — spec bytes creeping back up — must fail
    fanout = [{"metric": "control_plane_spec_bytes", "value": 1.0e6,
               "unit": "bytes", "backend": "cpu"},
              {"metric": "control_plane_spec_bytes", "value": 1.2e6,
               "unit": "bytes", "backend": "cpu"}]
    assert compare(fanout, threshold_pct=2.0)[0]["regression"] is True
    assert compare(list(reversed(fanout)),
                   threshold_pct=2.0)[0]["regression"] is False


_STUB_LEG_METRICS = ("control_plane_spec_bytes", "control_plane_hb_p95",
                     "control_plane_all_registered",
                     "control_plane_resize_roundtrip")
_GATED_METRICS = _STUB_LEG_METRICS + (
    "control_plane_real_all_running", "resize_grow_latency",
    "control_plane_am_recovery")


def test_smallest_leg_runs_as_a_script_and_appends_its_headlines(tmp_path):
    """`python tools/control_plane_bench.py` from the checkout's root —
    the stub storm alone, at a width of 8 — prints ONE JSON line that
    names the CPU, and appends exactly the stub leg's gated headlines to
    the history it was pointed at."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    hist = tmp_path / "history.jsonl"
    env = dict(os.environ, TONY_BENCH_HISTORY_PATH=str(hist),
               TONY_CP_WIDTHS="8", TONY_CP_REAL_WIDTHS="",
               TONY_CP_RECOVERY_WIDTH="", JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join("tools", "control_plane_bench.py")],
        cwd=root, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    assert len(lines) == 1, lines
    result = json.loads(lines[0])
    assert result["metric"] == "control_plane"
    assert result["backend"] == "cpu"
    assert "error" not in result and "real_error" not in result
    row = result["control_plane"]["widths"][0]
    assert row["width"] == 8 and row["registered"] and row["bounded"]
    from tools.bench_compare import load_history
    entries = load_history(str(hist))
    assert tuple(e["metric"] for e in entries) == _STUB_LEG_METRICS
    assert all(e["backend"] == "cpu" and e["value"] > 0
               and e["unit"] in ("s", "ms", "bytes") and e["width"] == 8
               and "profiler_overhead_pct" in e for e in entries)


def test_checked_in_history_holds_only_what_the_harness_emits():
    """tools/bench_history.jsonl is the control-plane harness's record
    and nothing else's: every line is one of its gated CPU headlines in
    a lower-is-better unit, and bench_compare passes it. A rate, an MFU
    or a latency of the chip belongs to PERF_LEDGER.jsonl."""
    from tools.bench_compare import DEFAULT_HISTORY, compare, load_history
    entries = load_history(DEFAULT_HISTORY)
    assert entries
    for e in entries:
        assert e["metric"] in _GATED_METRICS, e["metric"]
        assert e["backend"] == "cpu" and e["unit"] in ("s", "ms", "bytes")
    assert not [v for v in compare(entries, threshold_pct=2.0)
                if v["regression"]]


@pytest.mark.warmpool
def test_coldstart_headline_units_gate_lower_is_better():
    """The cold-start demolition's two new headlines —
    control_plane_real_all_running and resize_grow_latency — carry unit
    "s" so bench_compare judges them lower-is-better, and value<=0
    fallback markers are skipped both as baseline and as the judged
    entry."""
    from tools.bench_compare import compare

    for metric in ("control_plane_real_all_running", "resize_grow_latency"):
        fast = {"metric": metric, "value": 3.2, "unit": "s",
                "backend": "cpu", "width": 256, "warm_pool": True}
        slow = {"metric": metric, "value": 4.5, "unit": "s",
                "backend": "cpu", "width": 256, "warm_pool": True}
        # got slower later → regression
        v = compare([fast, slow], threshold_pct=2.0)
        assert len(v) == 1 and v[0]["regression"] is True, metric
        # got faster later → pass
        v = compare([slow, fast], threshold_pct=2.0)
        assert v[0]["regression"] is False, metric
        # a value<=0 marker (failed/withheld run) never judges...
        marker = {"metric": metric, "value": 0.0, "unit": "s",
                  "backend": "cpu"}
        v = compare([fast, slow, marker], threshold_pct=2.0)
        assert v[0]["regression"] is True     # latest MEASURABLE judged
        # ...and never serves as a flattering baseline
        v = compare([marker, slow], threshold_pct=2.0)
        assert v[0]["regression"] is False
        assert v[0].get("note") == "no prior baseline"


@pytest.mark.recovery
def test_am_recovery_headline_gate_lower_is_better():
    """The AM-kill leg's control_plane_am_recovery headline carries unit
    "s" so bench_compare judges it lower-is-better (recovery got SLOWER
    later = regression), and value<=0 markers from failed/withheld runs
    never judge and never serve as a baseline."""
    from tools.bench_compare import compare

    fast = {"metric": "control_plane_am_recovery", "value": 3.1,
            "unit": "s", "backend": "cpu", "width": 8,
            "adopted": 8, "lost": 0, "replayed_records": 25}
    slow = dict(fast, value=5.0)
    v = compare([fast, slow], threshold_pct=2.0)
    assert len(v) == 1 and v[0]["regression"] is True
    v = compare([slow, fast], threshold_pct=2.0)
    assert v[0]["regression"] is False
    marker = dict(fast, value=0.0)
    v = compare([fast, slow, marker], threshold_pct=2.0)
    assert v[0]["regression"] is True       # latest MEASURABLE judged
    v = compare([marker, slow], threshold_pct=2.0)
    assert v[0]["regression"] is False
    assert v[0].get("note") == "no prior baseline"


@pytest.mark.recovery
def test_am_recovery_disclosure_stamps_adoption_fields():
    """Every control_plane_am_recovery history entry discloses what the
    recovery actually did — a fast downtime number that relaunched the
    gang (or replayed an empty journal) must be distinguishable from a
    genuine full adoption."""
    row = {"width": 8, "kill_after_ms": 4000, "recovery_s": 3.102,
           "adopted": 8, "lost": 0, "replayed_records": 25,
           "relaunches": 0, "am_attempt": 1}
    d = bench._am_recovery_disclosure(row)
    assert d == {"adopted": 8, "lost": 0, "replayed_records": 25,
                 "relaunches": 0, "kill_after_ms": 4000}
    # a degraded run's entry would say so on its face
    d = bench._am_recovery_disclosure({"adopted": 6, "lost": 2,
                                       "relaunches": 2})
    assert d["lost"] == 2 and d["relaunches"] == 2
    assert d["replayed_records"] == 0


@pytest.mark.warmpool
def test_cp_disclosure_stamps_warm_fields():
    """Every control-plane bench line discloses whether it rode the warm
    pool and what the caches did — a warm headline that hid its lease
    and hit counts would be indistinguishable from a cold one."""
    row = {"warm": True, "warm_leases": 4, "warm_misses": 1,
           "spawn_s": 0.202, "loc_cache_hits": 256, "loc_cache_misses": 0,
           "submit_to_all_running_s": 3.9}
    d = bench._cp_disclosure(row, cold_baseline_s=4.4)
    assert d == {"warm_pool": True, "warm_leases": 4, "warm_misses": 1,
                 "spawn_s": 0.202, "loc_cache_hits": 256,
                 "loc_cache_misses": 0, "cold_baseline_s": 4.4}
    # cold rows disclose too (warm_pool False, no baseline field)
    d = bench._cp_disclosure({"warm": False, "spawn_s": 0.6})
    assert d["warm_pool"] is False
    assert "cold_baseline_s" not in d
