"""Serving engine: the share of the profile in which the device sat idle
while the loop had work: device-idle seconds under every leaf span (and
every `/self`) of the loop's thread but `tony.engine.idle_wait`, plus
those under no span, over the profile's window (lib/hostspans.py).
`idle_wait` is the loop's wait when no slot rides, nothing is landing and
nothing was admitted: idleness for want of work, which `idle_share` (1 -
busy_s / window_s) cannot tell from a slow host. Moves itl_p95_ms."""

from lib import hostspans, readers


def read(run):
    spans = hostspans.of_run(run)
    if not spans or not readers.on_chip(run):
        return None
    held = sum(s for leaf, s in spans["idle_by_span"].items()
               if leaf != hostspans.IDLE_WAIT) + spans["unattributed_s"]
    return 100.0 * held / spans["window_s"]
