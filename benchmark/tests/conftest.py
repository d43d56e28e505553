"""Tests of the benchmark itself. Run by hand and in the CPU rehearsal:
`JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`. Tier-1 (tests/) is
not touched."""

import json
import os
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
REHEARSE = os.path.join(BENCH, "tests", "data", "BENCHMARK.rehearse.json")


def rehearse(workload: str, *extra: str, seconds: float = 4, seed: int = 7,
             out: str = "") -> dict:
    """One rehearsal run of the whole harness on the CPU at tiny sizes;
    returns what its `rehearsal` line says."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, os.path.join(BENCH, "run.py"), "--rehearse",
           "--benchmark-file", REHEARSE, "--workload", workload, "--seed",
           str(seed), "--seconds", str(seconds), "--trace", "0", *extra]
    if out:
        cmd += ["--out", out]
    return rehearsal_line(subprocess.run(
        cmd, env=env, capture_output=True, text=True, timeout=600))


def rehearsal_line(r: subprocess.CompletedProcess) -> dict:
    """What the `rehearsal` line of a finished --rehearse run says."""
    assert r.returncode == 3, r.stdout[-3000:] + r.stderr[-3000:]
    assert "platform: cpu" in r.stdout
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("rehearsal ")]
    assert lines, r.stdout[-3000:]
    got = json.loads(lines[-1][len("rehearsal "):])
    got["stdout"], got["stderr"] = r.stdout, r.stderr
    return got
