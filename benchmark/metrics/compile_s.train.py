"""Trainer loop: host seconds of the trainer's first `run()`, to one step:
the step traced and compiled (or loaded from the compile cache), run once
and its loss read back. Moves setup_s."""


def read(run):
    return run.worker["compile_s"] if run.worker else None
