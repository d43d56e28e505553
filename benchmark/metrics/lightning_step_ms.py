"""Model step: device time of the lightning layers' state updates in one
decode step (every call of the kernel `tony_lightning_step`, one a
lightning layer), mean over the traced decode steps. Moves itl_p95_ms."""

from lib import stages


def read(run):
    return stages.family_stages(run).ms_per_step(run, "tony_lightning_step")
