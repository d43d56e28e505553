"""Harness: how late the load generator sent a request after it was due,
95th percentile; a starved generator must not read as a fast server.
Moves itl_p95_ms."""

from lib import readers


def read(run):
    return readers.percentile_ms(
        (r["sent"] - r["due"] for r in readers.judged(run)), 95)
