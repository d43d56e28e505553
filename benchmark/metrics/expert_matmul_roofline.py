"""Kernels: the grouped expert matmul's share of its roofline in the traced
decode steps (`tony_expert_matmul`: the least time for the weights of the
experts the program counted as hit, once, and for the routed rows'
operations, by the family's counts, over the kernel's device time). Moves
itl_p95_ms."""

from lib import stages


def read(run):
    got = stages.family_stages(run).expert_roofline(run)
    return None if got is None else got[0]
