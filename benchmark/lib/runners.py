"""The runner kinds, chosen by a traffic file's `kind`: `train` and
`serve-open`. Each submits its job through client -> AM ->
executor, warms up the cell's own shapes, measures a window, stops the
job, and then has the comparison with the reference made in a process of
its own (lib/check.py). What a runner gathers sits on the `Run` object,
where the per-layer metric readers (benchmark/metrics/<name>.py) find it.
"""

from __future__ import annotations

import asyncio
import json
import os
import random
import shutil
import socket
import subprocess
import sys
import tempfile
import time

from lib import loadgen, orchestrate, stats, traffic

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
JOB_TIMEOUT_S = 1500
ENDPOINT_TIMEOUT_S = 1100       # a first run compiles every program
CHECK_TIMEOUT_S = 900


class NoChip(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


class Run:
    """One run of one cell: its inputs, and what it gathered."""

    def __init__(self, args, spec: dict, t_start: float):
        self.args, self.t_start = args, t_start
        self.bench, self.cell = spec["bench"], spec["cell"]
        self.config, self.mix = spec["config"], spec["mix"]
        self.config_path, self.mix_path = spec["config_path"], spec["mix_path"]
        self.metrics_dir, self.family = spec["metrics_dir"], spec["family"]
        # absolute: the launched process runs in its container's directory
        self.out_dir = os.path.abspath(args.out or os.path.join(
            orchestrate.ROOT, "benchmark_out", self.cell["name"]))
        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir, exist_ok=True)
        self.facts: dict = {}           # end-to-end values by metric name
        self.attempted = 0
        self.failed = 0
        self.compares: list = []
        self.correct = False
        self.device: dict = {}
        self.launch_s = None
        self.client = None              # serving: window and records
        self.engine = None              # serving: /v1/metrics at the close
        self.gauges: list = []
        self.worker = None              # training: the worker's record
        self.trace = None               # reduced profiler trace
        self.compiles_in_window = None

    def log(self, msg: str) -> None:
        print(f"[bench +{time.monotonic() - self.t_start:.1f}s] {msg}",
              flush=True)

    def compare(self, *args, **kw) -> None:
        self.compares.append(stats.compared(*args, **kw))


# -- shared steps -----------------------------------------------------------

def _launcher_args(run: Run) -> str:
    """What both launchers take: the configuration, the seed and, in the
    tests, the fault to plant."""
    a = run.args
    args = f"--config {run.config_path} --seed {a.seed}"
    if a.sabotage != "none":
        args += f" --sabotage {a.sabotage}"
    return args


def _check_device(run: Run, logs: str) -> bool:
    """True once the launched process has logged its device line and it is
    the chip the cell asks for; NoChip where it is not."""
    dev = orchestrate.device_of(logs)
    if dev is None:
        return False
    count, kind, platform = dev
    run.device.update(platform=platform, kind=kind, count=count)
    if platform != "tpu" and not run.args.rehearse:
        raise NoChip(f"the launched process found {count} x {kind} "
                     f"(backend={platform}), not a TPU")
    if count < int(run.cell["chips"]) and not run.args.rehearse:
        raise NoChip(f"the cell asks for {run.cell['chips']} chips, jax "
                     f"found {count}")
    return True


def _launch_s(run: Run, job, logs: str) -> None:
    starts = orchestrate.marked_json(logs, "BENCH_START")
    if starts and job.submitted_at is not None:
        run.launch_s = starts[0]["t"] - job.submitted_at


def _reduce_trace(run: Run, trace_dir: str) -> None:
    """The profiler's trace to numbers, in a process of its own (it needs
    jax's reader; this process stays off jax)."""
    out = os.path.join(run.out_dir, "trace_reduced.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(HERE, "trace.py"),
                        trace_dir, out], env=env, capture_output=True,
                       text=True, timeout=300)
    if r.returncode != 0 or not os.path.exists(out):
        run.log(f"trace reduction failed: {r.stderr[-2000:]}")
        return
    with open(out, encoding="utf-8") as f:
        run.trace = json.load(f)


def _run_check(run: Run, argv: list) -> None:
    """lib/check.py in a process of its own, after the job has released
    the chip; its compare lines are passed through."""
    t = time.monotonic()
    cmd = [sys.executable, os.path.join(HERE, "check.py")] + argv
    if run.args.rehearse:
        cmd.append("--rehearse")
    r = subprocess.run(cmd, capture_output=True, text=True,
                       timeout=CHECK_TIMEOUT_S)
    verdict = None
    for line in r.stdout.splitlines():
        if line.startswith("CHECK "):
            verdict = json.loads(line[6:])
        else:
            print(line, flush=True)
    with open(os.path.join(run.out_dir, "check.stderr"), "w",
              encoding="utf-8") as f:
        f.write(r.stderr)
    print(f"check_s {time.monotonic() - t:.2f} (after the window; not part "
          f"of setup_s)", flush=True)
    if verdict is None:
        run.log(f"the check gave no verdict (rc={r.returncode}): "
                f"{r.stderr[-3000:]}")
        run.compares.append({"name": "check_ran", "value": 0, "limit": 1,
                             "ok": False})
        return
    run.compares += verdict["compared"]


def _wait_port_closed(url: str, timeout_s: float) -> bool:
    host, port = loadgen.endpoint_hostport(url)
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            socket.create_connection((host, port), timeout=2).close()
            time.sleep(0.25)
        except OSError:
            return True
    return False


def _count_compiles(compiles: list, t0: float, t1: float) -> int:
    return sum(1 for t, _, _ in compiles if t0 <= t < t1)


# -- training ---------------------------------------------------------------

def run_train(run: Run) -> None:
    a = run.args
    workdir = tempfile.mkdtemp(prefix="benchmark_")
    rec_dir = os.path.join(run.out_dir, "worker")
    params = (f"{_launcher_args(run)} --traffic {run.mix_path} "
              f"--seconds {a.seconds} --trace {a.trace} --out {rec_dir}")
    job = orchestrate.Job(workdir, [
        "--executes", os.path.join(BENCH_DIR, "launch", "train_worker.py"),
        "--task_params", params,
        "--conf", "tony.worker.instances=1",
        "--conf", "tony.application.framework=jax"], JOB_TIMEOUT_S)
    run.log("submitting the training job through client -> AM -> executor")
    job.submit()
    try:
        seen = False
        while True:
            status = job.wait(1.0)
            if not seen:
                seen = _check_device(run, job.container_logs(("stderr",)))
            if status is not None:
                break
            if time.monotonic() - job.submitted_at > JOB_TIMEOUT_S:
                raise RuntimeError("the training job did not end in time")
    except NoChip:
        job.kill()
        raise
    finally:
        job.stop()
        job.keep_logs(os.path.join(run.out_dir, "logs"))
        logs = job.container_logs()
        shutil.rmtree(workdir, ignore_errors=True)
    _launch_s(run, job, logs)
    record_path = os.path.join(rec_dir, "worker_record.json")
    if status != "SUCCEEDED" or not os.path.exists(record_path):
        run.log(f"the job ended {status}; worker log tail:\n{logs[-4000:]}")
        run.attempted, run.failed = 1, 1
        return
    with open(record_path, encoding="utf-8") as f:
        w = run.worker = json.load(f)
    run.device.update(w["device"])
    ends, t0 = w["step_ends"], w["window_t0"]
    steps = len(ends)
    run.attempted, run.failed = steps, 0
    run.facts["setup_s"] = t0 - run.t_start
    run.facts["train_tokens_per_s"] = (
        w["tokens_per_step"] * steps / (ends[-1] - t0))
    run.compiles_in_window = _count_compiles(w["compiles"], t0, ends[-1])
    durs = [b - x for x, b in zip([t0] + ends[:-1], ends)]
    print("split " + json.dumps({
        "window_s": ends[-1] - t0, "steps": steps,
        "step_ms_mean": 1e3 * sum(durs) / steps,
        "step_ms_min": 1e3 * min(durs), "step_ms_max": 1e3 * max(durs),
        "input_stall_s": w["input_stall_s"], "compile_s": w["compile_s"],
        "launch_s": run.launch_s}), flush=True)
    if a.trace:
        _reduce_trace(run, os.path.join(rec_dir, "trace"))
    _run_check(run, ["train", "--config", run.config_path, "--traffic",
                     run.mix_path, "--seed", str(a.seed), "--record",
                     record_path])


# -- serving ----------------------------------------------------------------

class _Hooks:
    """What the harness does at the window's edges, called by the load
    generator from inside its loop."""

    def __init__(self, run: Run, ctl_dir: str):
        self.run, self.ctl = run, ctl_dir
        self.t0 = None

    def window_open(self, t0: float) -> None:
        self.t0 = t0
        self.run.facts["setup_s"] = t0 - self.run.t_start
        if self.run.args.trace:
            delay = max(0.0, t0 + float(self.run.mix.get("trace_after_s", 5))
                        - time.monotonic())
            asyncio.get_running_loop().call_later(delay, self.ask_trace)

    def ask_trace(self) -> None:
        with open(os.path.join(self.ctl, "trace_request.json"), "w",
                  encoding="utf-8") as f:
            json.dump({"seconds": float(self.run.mix.get("trace_s", 3))}, f)

    def window_closed(self) -> None:
        with open(os.path.join(self.ctl, "report_request"), "w",
                  encoding="utf-8") as f:
            f.write("report\n")


async def _serve_window(run: Run, url: str, ctl_dir: str) -> None:
    a, mix = run.args, run.mix
    host, port = loadgen.endpoint_hostport(url)
    vocab = run.config["vocab_size"]
    tok_rng = random.Random(a.seed)
    lengths = traffic.prompt_lengths(mix)
    t = time.monotonic()
    warm = await loadgen.warm_up(
        host, port, lengths, lambda n: traffic.Request(
            -1, [tok_rng.randrange(vocab) for _ in range(n)], 2))
    bad = [r for r in warm if not loadgen.complete(r)]
    if bad:
        raise RuntimeError(f"warm-up request failed: {bad[0]}")
    run.log(f"warmed {len(lengths)} prompt lengths in "
            f"{time.monotonic() - t:.1f}s")
    stream = traffic.request_stream(mix, vocab, a.seed)
    hooks = _Hooks(run, ctl_dir)
    stop = asyncio.Event()
    sampler = asyncio.create_task(
        loadgen.sample_gauges(host, port, run.gauges, stop))
    run.client = await loadgen.open_loop(
        host, port, stream, a.seconds, float(mix["ramp_periods"])
        * traffic.period_seconds(mix), float(mix["drain_s"]), hooks)
    stop.set()
    await sampler
    run.engine = await loadgen.get_json(host, port, "/v1/metrics")


def _serve_facts(run: Run) -> list:
    """End-to-end values from the client's records; returns the finished
    requests the check may sample."""
    c, mix = run.client, run.mix
    t0, t1 = c["t0"], c["t1"]
    recs = c["records"]
    judged = loadgen.judged(c)
    done = [r for r in judged if loadgen.complete(r)]
    run.attempted, run.failed = len(judged), len(judged) - len(done)
    gaps = [1e3 * (b - x) for r in done
            for x, b in zip(r["stamps"], r["stamps"][1:])]
    if gaps:
        run.facts["itl_p95_ms"] = stats.percentile(gaps, 95)
    slice_s = float(mix.get("slice_s", 5.0))
    n = int((t1 - t0) / slice_s)
    slices = [0] * n
    for r in recs:
        for s in r["stamps"]:
            k = int((s - t0) / slice_s)
            if 0 <= k < n and s >= t0:
                slices[k] += 1
    print(f"slices tokens received per {slice_s:g}-s slice of the window: "
          f"{slices}", flush=True)
    return done


def _sample_for_check(run: Run, done: list) -> str:
    """A seeded sample of the finished requests, the longest in it."""
    mix = run.mix
    k = int(mix.get("check_requests", 8))
    rng = random.Random(run.args.seed + 1)
    longest = max(done, key=lambda r: r["prompt_len"] + len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    picked = [longest] + rng.sample(rest, min(k - 1, len(rest)))
    # the prompts are made again from the seed, not read from the records
    stream = traffic.request_stream(mix, run.config["vocab_size"],
                                    run.args.seed)
    need = {r["index"] for r in picked}
    prompts = {}
    for req in stream:
        if req.index in need:
            prompts[req.index] = req.prompt
            if len(prompts) == len(need):
                break
    path = os.path.join(run.out_dir, "check_sample.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"pad_to": int(mix.get("check_pad_to", 256)),
                   "resident_bytes": run.device["memory_peak_bytes"],
                   "requests": [
            {"index": r["index"], "prompt": prompts[r["index"]],
             "tokens": r["tokens"]} for r in picked]}, f)
    return path


def run_serve(run: Run) -> None:
    a, mix = run.args, run.mix
    traffic.check_budget(mix, run.config["run"]["token_budget"])
    periods = a.seconds / traffic.period_seconds(mix)
    if periods < 1 or periods != int(periods):
        # a part of a period is another mix at every seed
        raise SystemExit(
            f"benchmark: traffic {mix['name']} is judged over whole periods "
            f"of {traffic.period_seconds(mix):g} s; --seconds {a.seconds:g} "
            f"is not a whole number of them")
    workdir = tempfile.mkdtemp(prefix="benchmark_")
    ctl_dir = os.path.join(run.out_dir, "ctl")
    os.makedirs(ctl_dir, exist_ok=True)
    command = (f"{sys.executable} "
               f"{os.path.join(BENCH_DIR, 'launch', 'replica.py')} "
               f"{_launcher_args(run)} --ctl-dir {ctl_dir}")
    if a.control.startswith("program-"):
        command += f" --control {a.control[len('program-'):]}"
    job = orchestrate.Job(workdir, [
        "--conf", "tony.serving.instances=1",
        "--conf", "tony.serving.command=" + command], JOB_TIMEOUT_S)
    run.log("submitting the serving job through client -> AM -> executor")
    job.submit()
    url = None
    try:
        url = job.wait_endpoint(time.monotonic() + ENDPOINT_TIMEOUT_S)
        run.log(f"endpoint {url} registered")
        logs = job.container_logs()
        if not _check_device(run, logs):
            raise RuntimeError("the replica logged no device line")
        _launch_s(run, job, logs)
        asyncio.run(_serve_window(run, url, ctl_dir))
        run.log(f"window closed: t0 {run.client['t0']:.3f}, "
                f"{len(run.client['records'])} requests sent")
        report = os.path.join(ctl_dir, "report.json")
        deadline = time.monotonic() + 20
        while not os.path.exists(report) and time.monotonic() < deadline:
            time.sleep(0.1)
        if a.trace:
            deadline = time.monotonic() + 30
            while (not os.path.exists(os.path.join(ctl_dir, "trace_done"))
                   and time.monotonic() < deadline):
                time.sleep(0.1)
    except NoChip:
        job.kill()
        raise
    finally:
        job.stop()
        job.keep_logs(os.path.join(run.out_dir, "logs"))
        shutil.rmtree(workdir, ignore_errors=True)
    if url is not None and not _wait_port_closed(url, 60):
        run.log("the replica's port did not close")
    with open(os.path.join(ctl_dir, "report.json"), encoding="utf-8") as f:
        rep = json.load(f)
    run.device.update(rep["device"])
    c = run.client
    run.compiles_in_window = _count_compiles(rep["compiles"], c["t0"],
                                             c["t1"])
    done = _serve_facts(run)
    eng = run.engine or {}
    adm = sum(1 for r in c["records"]
              if r["stamps"] and c["t0"] <= r["stamps"][0] < c["t1"])
    pre, step = eng.get("prefill_s_p50"), eng.get("decode_ms_per_token_p50")
    busy = [g["active_slots"] for g in run.gauges
            if c["t0"] <= g["t"] < c["t1"] and g["active_slots"] is not None]
    split = {"window_s": c["t1"] - c["t0"], "admissions": adm,
             "busy_slots_mean": sum(busy) / len(busy) if busy else None,
             "busy_slots_min_max": [min(busy), max(busy)] if busy else None,
             "engine_prefill_p50_ms": None if pre is None else 1e3 * pre,
             "engine_prefill_p95_ms": (None if eng.get("prefill_s_p95") is None
                                       else 1e3 * eng["prefill_s_p95"]),
             "engine_step_p50_ms": step,
             "engine_queue_wait_p95_s": eng.get("queue_wait_s_p95"),
             "launch_s": run.launch_s}
    if pre is not None and step:
        split["admission_s_est"] = adm * pre
        split["decode_steps_est"] = (c["t1"] - c["t0"] - adm * pre) / (
            step / 1e3)
    print("split " + json.dumps(split), flush=True)
    with open(os.path.join(run.out_dir, "client.json"), "w",
              encoding="utf-8") as f:
        json.dump({"seed": a.seed, "t0": c["t0"], "t1": c["t1"],
                   "engine": run.engine, "gauges": run.gauges, "records": [
                       {k: v for k, v in r.items() if k != "tokens"}
                       for r in c["records"]]}, f)
    if a.trace:
        _reduce_trace(run, os.path.join(ctl_dir, "trace"))
    if not done:
        run.log("no request finished: nothing to compare")
        run.compares.append({"name": "finished_requests", "value": 0,
                             "limit": 1, "ok": False})
        return
    argv = ["serve", "--config", run.config_path, "--seed", str(a.seed),
            "--sample", _sample_for_check(run, done)]
    if a.control == "int8":
        argv += ["--control", "int8"]
    _run_check(run, argv)


RUNNERS = {"train": run_train, "serve-open": run_serve}
