"""Serving engine: of an expert layer's experts, the share a traced decode
step read the weights of (`moe_experts_hit` on the `tony.engine.emit` span
that lands it, counted on the device), over the steps lib/stepspans.py
paired, by the family's `traced.py`; where `experts_hit_pct` is the whole
life's, ramp and drain in it. It describes the traffic: how many streams
ride a step. Moves itl_p95_ms."""

from lib import stepspans


def read(run):
    return stepspans.family_reader(run, "experts_hit_pct")
