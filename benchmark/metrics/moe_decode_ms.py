"""Model step: device time of the expert layers' routing and grouped
matmuls in one decode step (the operations traced under the program's
scope `tony_moe_route` and the kernel `tony_expert_matmul`, all expert
layers of the step), mean over the traced decode steps. Moves
itl_p95_ms."""

from lib import stages


def read(run):
    family = stages.family_stages(run)
    parts = [family.ms_per_step(run, s) for s in (family.ROUTE,
                                                  family.EXPERTS)]
    return None if None in parts else sum(parts)
