"""Model step: the operations forward and backward need per token (the
benchmark's own count: matmuls and causal attention, no embedding lookup,
no recompute) times tokens per second, over the chip's bf16 peak. The rate
is a step's tokens over the window's median step time on the host clock:
the traced run's own tokens/s is slowed by the profiler's start and stop.
Moves train_tokens_per_s."""

import statistics

from lib import peaks


def read(run):
    w = run.worker
    if not w or not w["step_ends"] or run.device.get("platform") != "tpu":
        return None
    ends = [w["window_t0"]] + w["step_ends"]
    step_s = statistics.median(b - a for a, b in zip(ends, ends[1:]))
    flops = run.family.counts.train_flops_per_token(
        run.config, int(run.mix["seq_len"]))
    peak = peaks.peaks_of(run.device["kind"])["bf16_flops_per_s"]
    return (100.0 * flops * w["tokens_per_step"] / step_s
            / (peak * run.device["count"]))
