"""Serving engine: the tracing's own coverage — of the device's idle time
in the profile (`window_s - busy_s`), the share that falls inside a named
leaf span of the engine's loop (lib/hostspans.py). Moves itl_p95_ms."""

from lib import hostspans, readers


def read(run):
    spans = hostspans.of_run(run)
    if not spans or not spans["idle_s"] or not readers.on_chip(run):
        return None
    return 100.0 * spans["idle_in_leaves_s"] / spans["idle_s"]
