"""Serving engine: of the rows the K/V cache holds (slots x token budget),
the share a decode step's attention read, mean over every step the replica
has run (`cache_rows_read_total / cache_rows_budget_total` of /v1/metrics
at the window's close; the engine reckons them from each slot's attend
length rounded up to the read kernel's chunk, 0 for a slot that does not
ride). A program without the counters reads the whole budget every step
and reports nothing here. Moves itl_p95_ms."""


def read(run):
    eng = run.engine or {}
    total = eng.get("cache_rows_budget_total")
    if not total or eng.get("cache_rows_read_total") is None:
        return None
    return 100.0 * eng["cache_rows_read_total"] / total
