"""Serving engine: of the blocks of context the decoded tokens had, the
share a sparse layer attended to (`sparse_blocks_attended_total /
sparse_context_blocks_total` of /v1/metrics at the window's close): how
sparse the cache reads were. Moves itl_p95_ms."""


def read(run):
    eng = run.engine or {}
    total = eng.get("sparse_context_blocks_total")
    if not total or eng.get("sparse_blocks_attended_total") is None:
        return None
    return 100.0 * eng["sparse_blocks_attended_total"] / total
