"""Serving engine: slots that rode a decode step (`riders` on its
`tony.engine.decode.dispatch` span), mean over the traced steps that
lib/stepspans.py paired with their device program: the occupancy of the
steps whose device times the other readers divide by, where
`batch_per_step` is the whole life's. Moves itl_p95_ms."""

from lib import stepspans


def read(run):
    return stepspans.batch_per_step(run)
