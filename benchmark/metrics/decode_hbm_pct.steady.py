"""Kernels: bytes a decode step must read over its device time, as a
share of peak HBM bandwidth, under ordinary load. Moves itl_p95_ms."""

from lib import readers


def read(run):
    return readers.decode_hbm_pct(run)
