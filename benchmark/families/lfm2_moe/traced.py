"""What the `lfm2_moe` family's per-layer readers make of the counts its
model puts on a traced decode step's spans (tony_tpu/models/lfm2.py
STEP_COUNTS, on the `tony.engine.emit` that lands the step):
`moe_experts_hit`, the experts that got at least one row, summed over the
step's expert layers, and `moe_rows`, the rows routed. lib/stepspans.py
pairs each step with its device program and hands the paired steps here;
stages.py reads the same quantities off the whole life's counters.
"""

from __future__ import annotations

from lib import peaks, stages


def _expert_layers(run) -> int:
    return run.family.counts.layers(run.config)["expert"]


def step_bytes_kw(run, step: dict) -> dict:
    """`counts.decode_step_bytes`' keywords for one paired step: its own
    experts hit a layer and its riders (their conv states)."""
    return {"experts_hit": step["moe_experts_hit"] / _expert_layers(run),
            "riders": step["riders"]}


def experts_hit_pct(run, steps: list):
    return 100.0 * sum(s["moe_experts_hit"] for s in steps) / (
        run.config["num_experts"] * _expert_layers(run) * len(steps))


def expert_roofline(run, steps: list):
    """(share %, which bound) of the grouped expert matmul over the paired
    steps: the least time for each step's own experts hit (their weights
    once) and routed rows (their operations), by the family's counts.py,
    over the kernel's device time in those same executions."""
    kernel = stages.family_stages(run).EXPERTS
    cfg, counts = run.config, run.family.counts
    pk, layers = peaks.peaks_of(run.device["kind"]), _expert_layers(run)
    least = took = 0.0
    bound = set()
    for s in steps:
        if kernel not in s["stages"]:
            continue
        tokens = s["moe_rows"] / layers / cfg["num_experts_per_tok"]
        by_ops = counts.expert_layer_flops(cfg, tokens) \
            / pk["bf16_flops_per_s"]
        by_bytes = counts.expert_layer_bytes(
            cfg, tokens, s["moe_experts_hit"] / layers) \
            / pk["hbm_bytes_per_s"]
        bound.add("compute" if by_ops >= by_bytes else "memory")
        least += layers * max(by_ops, by_bytes)
        took += s["stages"][kernel]
    return (100.0 * least / took, "/".join(sorted(bound))) if took else None
