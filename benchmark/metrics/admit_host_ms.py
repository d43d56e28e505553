"""Serving engine: the host's share of one admission — the duration of a
`tony.engine.admit` span less the device-busy time inside it — median
over the admissions the profile caught whole (lib/hostspans.py). Moves
itl_p95_ms."""

from lib import hostspans, readers


def read(run):
    spans = hostspans.of_run(run)
    if not spans or not readers.on_chip(run):
        return None
    return spans.get("admit_host_ms_p50")
