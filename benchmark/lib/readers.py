"""What several per-layer metric readers share. A reader is
benchmark/metrics/<name>.py with `read(run)`; it returns None where it
finds nothing to read, and the harness then leaves the metric out.

Operations and bytes are counted by the `counts.py` of the cell's family
(`run.family.counts`). Names the readers take from the program: the jitted
programs `jit__decode_sample_step` and `jit__admit_step` (serve/engine.py),
`jit_train_step` (train/step.py), and the Pallas kernels `tony_flash_fwd`,
`tony_flash_bwd_dq`, `tony_flash_bwd_dkv` (ops/attention.py).
"""

from __future__ import annotations

import statistics

from lib import loadgen, peaks, stats, trace

DECODE_PROGRAM = "jit__decode_sample_step"
ADMIT_PROGRAM = "jit__admit_step"
FLASH_KERNELS = {"fwd": "tony_flash_fwd", "bwd_dq": "tony_flash_bwd_dq",
                 "bwd_dkv": "tony_flash_bwd_dkv"}


def on_chip(run) -> bool:
    """A device number comes only from a chip run."""
    return run.device.get("platform") == "tpu"


def program_median_ms(run, program: str):
    """Median device time of one execution of a jitted program."""
    mods = (run.trace or {}).get("modules", {})
    if program not in mods or not mods[program]["durations_s"]:
        return None
    return 1e3 * statistics.median(mods[program]["durations_s"])


def judged(run) -> list:
    """The complete requests the window judges."""
    if not run.client:
        return []
    return [r for r in loadgen.judged(run.client) if loadgen.complete(r)]


def mean_context_tokens(run) -> float | None:
    """Tokens of context in flight (prompt + tokens so far, summed over
    the requests streaming), averaged over the window's whole seconds."""
    c = run.client
    if not c:
        return None
    sums = []
    t = c["t0"] + 0.5
    while t < c["t1"]:
        total = 0
        for r in c["records"]:
            st = r["stamps"]
            if st and st[0] <= t <= st[-1]:
                total += r["prompt_len"] + sum(1 for s in st if s <= t)
        sums.append(total)
        t += 1.0
    return sum(sums) / len(sums) if sums else None


def decode_hbm_pct(run):
    """Bytes a decode step must read (weights once + the K/V rows of the
    context in flight) over the step's device time, as a share of the
    chip's peak memory bandwidth."""
    step_ms = program_median_ms(run, DECODE_PROGRAM)
    ctx = mean_context_tokens(run)
    if step_ms is None or ctx is None or not on_chip(run):
        return None
    need = run.family.counts.decode_step_bytes(run.config, [ctx])
    peak = peaks.peaks_of(run.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * need / (step_ms / 1e3) / peak


def flash_roofline(run):
    """(share %, which bound) of the three flash kernels together: the
    least time the chip could take for their calls over the device time
    they took."""
    if not run.trace or not on_chip(run):
        return None
    pk, counts = peaks.peaks_of(run.device["kind"]), run.family.counts
    b, s = int(run.mix["batch_size"]), int(run.mix["seq_len"])
    least = took = 0.0
    bound = set()
    for kernel, name in FLASH_KERNELS.items():
        t, calls = trace.kernel_time_s(run.trace, name)
        if not calls:
            continue
        by_ops = counts.flash_call_flops(run.config, s, b, kernel) \
            / pk["bf16_flops_per_s"]
        by_bytes = counts.flash_call_bytes(run.config, s, b, kernel) \
            / pk["hbm_bytes_per_s"]
        bound.add("compute" if by_ops >= by_bytes else "memory")
        least += calls * max(by_ops, by_bytes)
        took += t
    if not took:
        return None
    return 100.0 * least / took, "/".join(sorted(bound))


def percentile_ms(values_s, q):
    v = stats.percentile(list(values_s), q)
    return None if v is None else 1e3 * v
