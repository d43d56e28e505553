"""Kernels: bytes the traced decode steps must read over their device
time, as a share of peak HBM bandwidth, each paired step counted for its
own riders at its own mean context (and, where the family's `traced.py`
says how, for what its model counted in it: the experts hit) as its spans
say them (lib/stepspans.py); where `decode_hbm_pct.steady` and `.moe` set
one slot at the window's mean context and the whole life's counters
against the traced steps' median time. Moves itl_p95_ms."""

from lib import stepspans


def read(run):
    return stepspans.decode_hbm_pct(run)
