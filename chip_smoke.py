#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path of tony-tpu once, through the entry points a user
would call — TonyClient -> AM -> executor -> user process on the chip — at
the full widths of `llama3_1b_proxy` (dim 2048, 16 layers, 16/8 heads, ffn
8192, vocab 32 000; weights random from --seed, data synthetic from --seed):

  train   submit examples/llama-pretrain/pretrain.py on the local backend,
          one worker, seq 4096, a few optimizer steps. Passes only if the
          job ends SUCCEEDED, the worker names platform tpu, the losses are
          finite and fall, and the step it lowered holds the Pallas kernels
          (flash fwd, dq, dk/dv and RMSNorm).
  serve   submit a `serving` job (python -m tony_tpu.serve, bf16, default
          slots / token budget), wait for the registered endpoint, send
          /v1/generate requests with prompt lengths 3, 37, 129, 512 and 700
          (one streamed), check every reply, /healthz and /v1/metrics, stop
          the job and see the port close.

With no arguments it needs ONE TPU chip: the last line of its standard
output is then exactly
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
and the exit code 0. Any failed phase, or a machine where jax finds no
TPU, exits non-zero and prints no such line.

  --rehearse     the rehearsal of the on-chip-measurement guide: both phases
                 at the `tiny` config on whatever device jax finds (run it
                 with JAX_PLATFORMS=cpu; with --four-chips also give
                 XLA_FLAGS=--xla_force_host_platform_device_count=4).
                 Reports the device it found; on anything but a TPU it
                 exits 3 and claims no chip.
  --four-chips   needs a host with four chips. Runs ONLY the seeded
                 llama3_1b_proxy trainer (depth cut to 8 layers so that the
                 one-device reference fits, batch 4, seq 4096) on a mesh of
                 one device, then in one worker over a 4-device fsdp mesh
                 and over fsdp=2 x tp=2, compares the per-step losses
                 within LOSS_RTOL and requires a parameter sharded over 4
                 devices. The last line's count is then 4.

This process never imports jax: the chip belongs to the worker process,
one process at a time, the phases in sequence, and no probe before them.
"""

from __future__ import annotations

import argparse
import http.client
import json
import math
import os
import random
import re
import shutil
import socket
import sys
import tempfile
import threading
import time
from urllib.parse import urlparse

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

try:
    from tony_tpu import constants as C
    from tony_tpu.client.tony_client import TonyClient
    from tony_tpu.conf import TonyConfiguration, keys as K
    from tony_tpu.rpc.client import ClusterServiceClient
except ImportError as e:
    print(f"chip_smoke: this script drives the tony_tpu checkout it lives "
          f"in, and there is none beside it ({e})", file=sys.stderr)
    sys.exit(2)

# train phase, one chip. Batch 2: the v5e compiler's buffer assignment for
# this very step (the arguments plus one heap of temporaries: the count
# the chip obeys, PERF.md section 6, PR 47) is 13.86 of ~15.75 GiB usable
# since `save_flash` keeps seven tensors a block (10.13 before;
# tests/test_tpu_compile.py holds it). memory_analysis()'s arguments +
# temporaries reads 16.61 for it (6.36 + 10.25; 11.8 before PR 47, and
# 16.1 at batch 4, which "did run on the chip", CHANGES.md, PR 22: that
# sum charges the layer scan's saved stacks twice), and the chip's
# peak_bytes_in_use (6.40 GiB at either batch) leaves out the temporaries.
MODEL = "llama3_1b_proxy"
SEQ_LEN = 4096
BATCH = 2
STEPS = 6
REHEARSE_STEPS = 20     # tiny learns slowly through its 10 warm-up steps
# four-chip comparison: 8 of 16 layers so that batch 4 (one row a device
# under fsdp=4) also fits the ONE-device reference (10.87 GiB by the
# buffer assignment, since PR 47's policy)
FOUR_LAYERS = 8
FOUR_BATCH = 4
FOUR_STEPS = 6
# per-step loss, one device against the sharded mesh: same weights, same
# batches, bf16 — only the order of the reductions differs
LOSS_RTOL = 1e-2
# serve phase
PROMPT_LENS = (3, 37, 129, 512, 700)
REHEARSE_PROMPT_LENS = (3, 37, 100)     # tiny's max_seq is 128
NEW_TOKENS = 8
KERNELS = ("tony_flash_fwd", "tony_flash_bwd_dq", "tony_flash_bwd_dkv",
           "tony_rmsnorm")

JOB_TIMEOUT_S = 600

OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")


class PhaseFailed(Exception):
    pass


def say(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _T0:.0f}s] {msg}", flush=True)


_T0 = time.monotonic()


def require_no_jax() -> None:
    """The chip belongs to the worker: a parent that imported jax could
    hold it, and the worker would then fail or hang."""
    if "jax" in sys.modules:
        raise RuntimeError("chip_smoke's own process must never import jax")


def check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(f"not true: {what}")
    say(f"  ok: {what}")


# ---------------------------------------------------------------------------
# submitting and reading back
# ---------------------------------------------------------------------------

def _container_logs(client: TonyClient) -> str:
    """Everything the job's containers wrote (the user process inherits
    the executor's stdout/stderr files)."""
    out = []
    root = os.path.join(client.app_dir, C.CONTAINERS_DIR_NAME)
    for d, _, files in sorted(os.walk(root)):
        for f in sorted(files):
            if f in ("stdout", "stderr"):
                with open(os.path.join(d, f), encoding="utf-8",
                          errors="replace") as fh:
                    out.append(fh.read())
    return "\n".join(out)


def _keep_logs(client: TonyClient, phase: str) -> None:
    """Copy the app's logs where the chip tool brings them back from."""
    dst = os.path.join(OUT_DIR, phase)
    shutil.rmtree(dst, ignore_errors=True)
    os.makedirs(dst, exist_ok=True)
    for name in (C.AM_STDOUT, C.AM_STDERR):
        src = os.path.join(client.app_dir, name)
        if os.path.exists(src):
            shutil.copy(src, os.path.join(dst, name))
    root = os.path.join(client.app_dir, C.CONTAINERS_DIR_NAME)
    for d, _, files in os.walk(root):
        for f in files:
            if f in ("stdout", "stderr"):
                rel = os.path.relpath(d, root).replace(os.sep, "_")
                shutil.copy(os.path.join(d, f),
                            os.path.join(dst, f"{rel}.{f}"))


def _new_client(workdir: str, argv: list[str]) -> TonyClient:
    conf = TonyConfiguration()
    conf.set(K.CLUSTER_WORKDIR, workdir, "chip_smoke")
    # a job that hangs must fail its phase inside the script's 1200 s: a
    # cold train job took 105 s on the chip, a cold replica 20 s to register
    conf.set(K.APPLICATION_TIMEOUT, JOB_TIMEOUT_S * 1000, "chip_smoke")
    client = TonyClient(conf)
    client.init(argv)
    return client


def _device_of(logs: str):
    """(count, kind, platform) from the device line the worker and the
    replica both log (train/metrics.py log_devices)."""
    m = re.search(r"devices: (\d+) x (.+?) \(backend=(\w+)\)", logs)
    return (int(m.group(1)), m.group(2), m.group(3)) if m else None


def _watch_for_wrong_device(client: TonyClient, want_tpu: bool,
                            stop: threading.Event) -> None:
    """A worker that came up on another platform than asked would grind
    through a 1B model on the CPU: stop the job as soon as its device line
    says so, instead of waiting for it."""
    while not stop.wait(1.0):
        dev = _device_of(_container_logs(client)) if client.app_dir else None
        if dev is None:
            continue
        if want_tpu and dev[2] != "tpu":
            say(f"  worker found {dev[0]} x {dev[1]} (backend={dev[2]}), "
                f"not a TPU: stopping the job")
            client.kill()
        return


def run_train_job(name: str, workdir: str, args, *, model: str, seq: int,
                  batch: int, steps: int, n_layers: int = 0,
                  conf_extra: tuple[str, ...] = ()) -> dict:
    """Submit the pretrain example with one worker, wait for it, and read
    the evidence out of the worker's log."""
    params = (f"--config {model} --steps {steps} --batch-size {batch} "
              f"--seq-len {seq} --seed {args.seed} --log-every 1")
    if n_layers:
        params += f" --n-layers {n_layers}"
    argv = ["--executes",
            os.path.join(REPO, "examples", "llama-pretrain", "pretrain.py"),
            "--task_params", params,
            "--conf", "tony.worker.instances=1",
            "--conf", "tony.application.framework=jax"]
    for c in conf_extra:
        argv += ["--conf", c]
    client = _new_client(workdir, argv)
    require_no_jax()
    say(f"{name}: submitting {model} x {seq}, batch {batch}, {steps} steps"
        + (f", {n_layers} layers" if n_layers else "")
        + (f", {' '.join(conf_extra)}" if conf_extra else ""))
    stop = threading.Event()
    watcher = threading.Thread(
        target=_watch_for_wrong_device,
        args=(client, not args.rehearse, stop), daemon=True)
    watcher.start()
    try:
        client.run()
    finally:
        stop.set()
        watcher.join()
        _keep_logs(client, name)
    logs = _container_logs(client)
    out = {"status": client.final_status, "logs": logs,
           "device": _device_of(logs),
           "losses": [float(x) for x in
                      re.findall(r"step \d+ loss ([-\w.]+) \(", logs)]}
    m = re.search(r"with Pallas kernels: (.+)", logs)
    out["kernels"] = ({k: int(v) for k, v in
                       re.findall(r"(\w+)=(\d+)", m.group(1))} if m else {})
    for key, pat in (
            ("compile_s", r"first step dispatched in ([\d.]+)s"),
            ("peak_hbm_gib", r"peak HBM in use ([\d.]+) GiB"),
            ("cache_dir", r"persistent XLA compile cache at (\S+)"),
            ("param_devices", r"sharded over (\d+) of \d+ mesh devices")):
        m = re.search(pat, logs)
        out[key] = m.group(1) if m else None
    say(f"{name}: {client.final_status}; device {out['device']}; losses "
        f"{out['losses']}; kernels {out['kernels']}; first step (trace + "
        f"compile) {out['compile_s']} s; peak HBM {out['peak_hbm_gib']} GiB; "
        f"compile cache {out['cache_dir']}")
    if client.final_status != "SUCCEEDED":
        sys.stderr.write(logs[-6000:] + "\n")
    return out


def check_train(out: dict, steps: int) -> None:
    check(out["status"] == "SUCCEEDED", "train job ended SUCCEEDED through "
          "client -> AM -> executor")
    check(out["device"] is not None, "worker logged its device line")
    losses = out["losses"]
    check(len(losses) == steps, f"{steps} per-step losses logged")
    check(all(math.isfinite(x) for x in losses), "losses are finite")
    check(losses[-1] < losses[0],
          f"loss falls ({losses[0]:.4f} -> {losses[-1]:.4f})")
    if out["device"][2] == "tpu":
        check(all(out["kernels"].get(k, 0) > 0 for k in KERNELS),
              "the lowered step holds flash fwd, dq, dk/dv and RMSNorm "
              "Pallas kernels")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_train(workdir: str, args) -> tuple:
    if args.rehearse:
        model, seq, batch, steps = (args.config or "tiny", 64, 4,
                                    REHEARSE_STEPS)
    else:
        model, seq, batch, steps = args.config or MODEL, SEQ_LEN, BATCH, STEPS
    out = run_train_job("train", workdir, args, model=model, seq=seq,
                        batch=batch, steps=steps)
    check_train(out, steps)
    return out["device"]


def _http(url: str, method: str = "GET", body: dict | None = None,
          timeout: float = 300.0):
    u = urlparse(url)
    conn = http.client.HTTPConnection(u.hostname, u.port, timeout=timeout)
    try:
        data = json.dumps(body).encode() if body is not None else None
        conn.request(method, u.path, body=data,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, resp.read().decode("utf-8", errors="replace")
    finally:
        conn.close()


def _wait_endpoint(client: TonyClient, deadline: float) -> str:
    """The endpoint the replica registered with the AM, read the way
    examples/llama-serve/serve_submit.py does."""
    hostport = os.path.join(client.app_dir, C.AM_HOSTPORT_FILE)
    while time.monotonic() < deadline and not os.path.exists(hostport):
        time.sleep(0.2)
    if not os.path.exists(hostport):
        raise PhaseFailed("AM never came up (no amhostport file)")
    with open(hostport, encoding="utf-8") as f:
        host, _, port = f.read().strip().rpartition(":")
    rpc = ClusterServiceClient(host, int(port), retries=2,
                               retry_sleep_sec=0.2, timeout_sec=5.0,
                               auth_token=client.auth_token)
    try:
        while time.monotonic() < deadline:
            if client._am_proc.poll() is not None:
                raise PhaseFailed("AM exited before an endpoint registered")
            try:
                infos = rpc.get_task_infos()
            except (OSError, RuntimeError, ValueError):   # AM mid-boot
                infos = []
            for info in infos:
                if info.get("name") == "serving-endpoint":
                    return info["url"]
            time.sleep(0.5)
    finally:
        rpc.close()
    raise PhaseFailed("serving endpoint never registered")


def phase_serve(workdir: str, args) -> tuple:
    model = args.config or ("tiny" if args.rehearse else MODEL)
    lens = REHEARSE_PROMPT_LENS if args.rehearse else PROMPT_LENS
    vocab = 256 if args.rehearse else 32_000
    client = _new_client(workdir, [
        "--conf", "tony.serving.instances=1",
        "--conf", f"tony.serving.command={sys.executable} -m tony_tpu.serve "
                  f"--config {model}"])
    require_no_jax()
    say(f"serve: submitting a serving job, {model}, default slots and "
        f"token budget")
    client.submit()
    monitor = threading.Thread(target=client.monitor, daemon=True)
    monitor.start()
    try:
        endpoint = _wait_endpoint(client, time.monotonic() + 300.0)
        say(f"serve: endpoint {endpoint}")
        dev = _device_of(_container_logs(client))
        check(dev is not None, "replica logged its device line")
        say(f"serve: replica device {dev}")
        if not args.rehearse:
            check(dev[2] == "tpu", "replica names platform tpu")
        rng = random.Random(args.seed)
        for i, n in enumerate(lens):
            prompt = [rng.randrange(vocab) for _ in range(n)]
            stream = i == 1                 # the 37-token prompt streams
            t0 = time.monotonic()
            status, text = _http(
                f"{endpoint}/v1/generate", "POST",
                {"prompt": prompt, "max_new_tokens": NEW_TOKENS,
                 "stream": stream})
            if stream:
                lines = [json.loads(ln) for ln in text.splitlines() if ln]
                tokens = [ln["token"] for ln in lines if "token" in ln]
                done = lines[-1].get("done") if lines else False
            else:
                tokens, done = json.loads(text).get("tokens", []), True
            say(f"serve: prompt {n} tokens{' (stream)' if stream else ''} "
                f"-> {status} {tokens} in {time.monotonic() - t0:.1f}s "
                f"(first of its length: includes its compile)")
            check(status == 200 and done and len(tokens) == NEW_TOKENS
                  and all(0 <= t < vocab for t in tokens),
                  f"prompt length {n}: 200 with {NEW_TOKENS} tokens")
        status, text = _http(f"{endpoint}/healthz")
        check(status == 200, "/healthz answers 200")
        status, text = _http(f"{endpoint}/v1/metrics")
        check(status == 200 and json.loads(text) is not None,
              "/v1/metrics answers 200 with JSON")
    except BaseException:
        sys.stderr.write(_container_logs(client)[-6000:] + "\n")
        raise
    finally:
        client.cleanup()
        monitor.join(timeout=30)
        _keep_logs(client, "serve")
    u = urlparse(endpoint)
    deadline = time.monotonic() + 30.0
    closed = False
    while time.monotonic() < deadline and not closed:
        try:
            socket.create_connection((u.hostname, u.port), timeout=2).close()
            time.sleep(0.5)
        except OSError:
            closed = True
    check(closed, f"job stopped and port {u.port} closed")
    return dev


def phase_four_chips(workdir: str, args) -> tuple:
    """The same seeded trainer on one device of the host and over a
    4-device mesh in ONE worker process, compared step by step."""
    kw = dict(model=args.config or MODEL, seq=SEQ_LEN, batch=FOUR_BATCH,
              steps=FOUR_STEPS, n_layers=FOUR_LAYERS)
    if args.rehearse:       # four virtual CPU devices, tiny as it is
        kw.update(model=args.config or "tiny", seq=64, n_layers=0,
                  steps=REHEARSE_STEPS)
    steps = kw["steps"]
    runs = [("one_device", ("tony.worker.tpus=1", "tony.tpu.mesh-shape=1",
                            "tony.tpu.mesh-axes=fsdp"), 1),
            ("fsdp4", ("tony.worker.tpus=4", "tony.tpu.mesh-shape=4",
                       "tony.tpu.mesh-axes=fsdp"), 4),
            ("fsdp2_tp2", ("tony.worker.tpus=4", "tony.tpu.mesh-shape=2,2",
                           "tony.tpu.mesh-axes=fsdp,tp"), 4)]
    ref = None
    for name, conf_extra, want_devices in runs:
        out = run_train_job(name, workdir, args, conf_extra=conf_extra, **kw)
        check_train(out, steps)
        check(out["device"][0] == 4
              and (args.rehearse or out["device"][2] == "tpu"),
              f"{name}: the worker holds 4 devices")
        say(f"{name}: one parameter has shards on {out['param_devices']} "
            f"devices")
        check(out["param_devices"] == str(want_devices),
              f"{name}: a parameter is sharded over {want_devices} "
              f"device(s)")
        if ref is None:
            ref = out
            continue
        for i, (a, b) in enumerate(zip(ref["losses"], out["losses"]), 1):
            check(abs(a - b) <= LOSS_RTOL * abs(a),
                  f"{name} step {i}: loss {b:.4f} against one device "
                  f"{a:.4f} within {LOSS_RTOL:g} relative")
    return out["device"]


def main() -> int:
    p = argparse.ArgumentParser(
        description="Drive tony-tpu's main path once on the chip "
                    "(client -> AM -> executor -> worker); see the module "
                    "docstring.")
    p.add_argument("--rehearse", action="store_true",
                   help="tiny config on whatever device jax finds (use "
                        "with JAX_PLATFORMS=cpu); never exits 0 off a TPU")
    p.add_argument("--four-chips", action="store_true",
                   help="ONLY the sharded-trainer comparison: one device "
                        "against fsdp=4 and fsdp=2 x tp=2, each in one "
                        "worker (needs a host with 4 chips)")
    p.add_argument("--config", default="",
                   help=f"model preset (default {MODEL}; tiny with "
                        f"--rehearse)")
    p.add_argument("--seed", type=int, default=0,
                   help="seed of the weights, the data and the prompts")
    args = p.parse_args()

    for var in ("TONY_FLASH_INTERPRET", "TONY_FLASH_FORCE"):
        if os.environ.get(var):
            print(f"chip_smoke: {var} is set; it pins or interprets the "
                  f"attention kernels, so this would not be the program a "
                  f"user runs", file=sys.stderr)
            return 2
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if not args.rehearse and platforms and "tpu" not in platforms.split(","):
        print(f"chip_smoke: JAX_PLATFORMS={platforms} holds jax off the "
              f"TPU, so no worker can find one. Run it on the chip, or "
              f"rehearse with --rehearse.", file=sys.stderr)
        return 2
    require_no_jax()

    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    say(f"workdir {workdir}; logs are kept under {OUT_DIR}")
    devices = []
    try:
        if args.four_chips:
            devices.append(phase_four_chips(workdir, args))
        else:
            for phase, fn in (("train", phase_train),
                              ("serve", phase_serve)):
                devices.append(fn(workdir, args))
                say(f"PHASE {phase} OK")
    except PhaseFailed as e:
        say(f"FAILED: {e}")
        return 1
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    count, kind, platform = devices[0]
    if any(d != devices[0] for d in devices):
        say(f"FAILED: the phases ran on different devices: {devices}")
        return 1
    if platform != "tpu":
        say(f"every phase passed on {count} x {kind} (platform {platform}): "
            f"a rehearsal, not a chip run — exit 3")
        return 3
    if count != (4 if args.four_chips else 1):
        say(f"FAILED: found {count} TPU devices, this mode wants "
            f"{4 if args.four_chips else 1}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
