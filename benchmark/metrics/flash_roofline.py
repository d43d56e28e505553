"""Kernels: the flash-attention kernels' share of their roofline in the
traced steps (tony_flash_fwd, _bwd_dq, _bwd_dkv together; compute-bound at
4096 tokens). Moves train_tokens_per_s."""

from lib import readers


def read(run):
    got = readers.flash_roofline(run)
    return None if got is None else got[0]
