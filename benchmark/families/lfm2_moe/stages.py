"""The device stages of the `lfm2_moe` family's programs, as the program
names them (tony_tpu/models/moe.py, ops/expert_matmul.py,
models/lfm2.py), and what the family's per-layer readers
(benchmark/metrics/) make of a traced run with them and with the engine's
counters. Reading the profile is lib/stages.py's; no jax here.
"""

from __future__ import annotations

from lib import peaks, readers, stages

# `tony_moe_route` and `tony_short_conv` are scopes the operations were
# traced under, `tony_expert_matmul` the grouped-matmul kernel's name
ROUTE, EXPERTS, CONV = ("tony_moe_route", "tony_expert_matmul",
                        "tony_short_conv")
STAGES = (ROUTE, EXPERTS, CONV)


def ms_per_step(run, stage: str):
    return stages.stage_ms_per_step(run, STAGES, stage)


def counted(run) -> dict | None:
    """Per expert layer of a decode step, over every step the replica has
    read: `experts_hit` (the experts that got rows, as the step itself
    counted them on the device) and `rows`; and `riders` a step. None for
    a program without the counters."""
    eng = run.engine or {}
    layer_steps = eng.get("moe_layer_steps_total")
    steps = eng.get("decode_steps_total")
    if not layer_steps or not steps \
            or eng.get("moe_experts_hit_total") is None:
        return None
    return {"experts_hit": eng["moe_experts_hit_total"] / layer_steps,
            "rows": eng.get("moe_rows_total", 0) / layer_steps,
            "riders": eng.get("decode_slot_steps_total", 0) / steps}


def expert_roofline(run):
    """(share %, which bound) of `tony_expert_matmul` in the traced decode
    steps: the least time for the mean expert layer-step the program
    counted (the hit experts' weights once, the routed rows' operations,
    by the family's counts.py), times a step's expert layers, over the
    kernel's mean device time a step."""
    took_ms = ms_per_step(run, EXPERTS)
    got = counted(run)
    if took_ms is None or got is None:
        return None
    cfg, counts = run.config, run.family.counts
    pk = peaks.peaks_of(run.device["kind"])
    tokens = got["rows"] / cfg["num_experts_per_tok"]
    by_ops = counts.expert_layer_flops(cfg, tokens) / pk["bf16_flops_per_s"]
    by_bytes = counts.expert_layer_bytes(cfg, tokens, got["experts_hit"]) \
        / pk["hbm_bytes_per_s"]
    least_ms = 1e3 * counts.layers(cfg)["expert"] * max(by_ops, by_bytes)
    return (100.0 * least_ms / took_ms,
            "compute" if by_ops >= by_bytes else "memory")


def decode_hbm_pct(run):
    """Bytes the mean decode step must read (shared weights once, the hit
    experts the program counted, the K/V rows of the context in flight,
    the riders' conv states) over the step's device time, as a share of
    the chip's peak memory bandwidth."""
    step_ms = readers.program_median_ms(run, readers.DECODE_PROGRAM)
    ctx, got = readers.mean_context_tokens(run), counted(run)
    if step_ms is None or ctx is None or got is None \
            or not readers.on_chip(run):
        return None
    need = run.family.counts.decode_step_bytes(
        run.config, [ctx], experts_hit=got["experts_hit"],
        riders=got["riders"])
    peak = peaks.peaks_of(run.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * need / (step_ms / 1e3) / peak
