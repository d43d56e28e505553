"""Kernels: the grouped expert matmul's share of its roofline in the traced
decode steps, each paired step's least time reckoned from its own
`moe_experts_hit` and `moe_rows` (lib/stepspans.py pairs the steps, the
family's `traced.py` reckons them) over `tony_expert_matmul`'s device time
in those same executions; where `expert_matmul_roofline` sets the whole
life's mean counts against the traced steps' time. Moves itl_p95_ms."""

from lib import stepspans


def read(run):
    got = stepspans.family_reader(run, "expert_roofline")
    return None if got is None else got[0]
