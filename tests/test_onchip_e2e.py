"""chip_smoke.py mechanics, driven on the CPU backend.

The script's purpose is the real-chip proof (client -> AM -> executor ->
a worker process on the TPU), which can't run under the test suite's
forced-CPU env — but every moving part EXCEPT the chip can: the
submissions, the log scrape, the serving requests, and the honest
non-zero verdict when the device isn't a TPU. Pinning those here means a
chip call can't be wasted on a broken script."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, **env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("XLA_FLAGS", None)      # one CPU device, as on a one-chip host
    return subprocess.run([sys.executable, SMOKE, *args], env=env,
                          capture_output=True, text=True, timeout=360)


def test_chip_smoke_cpu_rehearsal_runs_both_phases_and_claims_no_chip():
    proc = _run(["--rehearse"])
    # honest verdict: both phases ran, but a CPU backend is NOT on-chip
    # evidence, so the script must exit non-zero and print no result line
    assert proc.returncode == 3, proc.stdout + proc.stderr
    assert "PHASE train OK" in proc.stdout
    assert "PHASE serve OK" in proc.stdout
    assert "train job ended SUCCEEDED" in proc.stdout
    assert "(1, 'cpu', 'cpu')" in proc.stdout          # the device found
    last = proc.stdout.strip().splitlines()[-1]
    assert '"ok"' not in last and "not a chip run" in last


@pytest.mark.parametrize("args,env,rc", [
    # a phase made to fail: the job FAILS, so does the script
    (["--rehearse", "--config", "no_such_preset"], {}, 1),
    # as the driver runs it, on a machine whose jax is held to the CPU
    ([], {}, 2),
    # the debugging switches make it another program than a user's
    (["--rehearse"], {"TONY_FLASH_FORCE": "blockwise"}, 2),
], ids=["failed-phase", "no-accelerator", "flash-switch-set"])
def test_chip_smoke_exit_status_is_the_truth(args, env, rc):
    proc = _run(args, **env)
    assert proc.returncode == rc, proc.stdout + proc.stderr
    assert '"ok"' not in proc.stdout
