"""Serving engine: time to first token at the client, from when the
request was due; recorded, not judged (PERF.md, Open questions). Moves
itl_p95_ms."""

from lib import readers


def read(run):
    return readers.percentile_ms(
        (r["stamps"][0] - r["due"] for r in readers.judged(run)), 90)
