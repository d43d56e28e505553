"""Kernels: bytes a decode step must read over its device time, as a share
of peak HBM bandwidth, for a family whose step reads only the experts its
riders hit (the shared weights once + the mean experts hit a step, as the
program counted them, + the K/V rows of the context in flight + the
riders' conv states). Moves itl_p95_ms."""

from lib import stages


def read(run):
    return stages.family_stages(run).decode_hbm_pct(run)
