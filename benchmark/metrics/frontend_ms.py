"""Front end (serve/frontend.py): the client's time to first token from
the send, less the engine's own from the submit, median. Moves itl_p95_ms."""

import statistics

from lib import readers


def read(run):
    d = [1e3 * (r["stamps"][0] - r["sent"] - r["done"]["ttft_s"])
         for r in readers.judged(run)
         if r["done"].get("ttft_s") is not None]
    return statistics.median(d) if d else None
