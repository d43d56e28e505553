"""Kernels: the chunked lightning-attention prefill kernel's share of its
roofline over the traced admissions (`tony_lightning_chunk`: the least
time for the recurrence's operations and for Q, K, V and O once, by the
family's counts, over the kernel's device time). Moves itl_p95_ms."""

from lib import stages


def read(run):
    family = stages.family_stages(run)
    got = family.kernel_roofline(run, family.LIGHTNING_KERNEL,
                                 "lightning_call")
    return None if got is None else got[0]
