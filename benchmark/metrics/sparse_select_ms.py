"""Model step: device time of the sparse layers' block selection in one
decode step (compressed-key scores, pooling, top-k of every sparse layer:
the operations traced under the program's scope `tony_sparse_select`),
mean over the traced decode steps. Moves itl_p95_ms."""

from lib import stages


def read(run):
    return stages.family_stages(run).ms_per_step(run, "tony_sparse_select")
