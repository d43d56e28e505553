"""Kernels: the block-sparse prefill kernel's share of its roofline over
the traced admissions (`tony_sparse_attn`: the least time the chip could
take for the attended pairs' operations and for Q, O and each K/V row
once, by the family's counts, over the kernel's device time). Moves
itl_p95_ms."""

from lib import stages


def read(run):
    family = stages.family_stages(run)
    got = family.kernel_roofline(run, family.SPARSE_KERNEL, "sparse_call")
    return None if got is None else got[0]
