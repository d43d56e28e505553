"""Model step: device time of the expert layers' routing alone in one
decode step (scores, top-k, the layout of rows by expert and the weighted
gather back: the operations traced under the program's scope
`tony_moe_route`), mean over the traced decode steps. Moves itl_p95_ms."""

from lib import stages


def read(run):
    family = stages.family_stages(run)
    return family.ms_per_step(run, family.ROUTE)
