"""Trainer loop: time a step of the window waited for its batch (the
`input_stall` phase of the trainer's goodput ledger, which it carves from
the PrefetchIterator's stall counter at every log boundary), per step.
Moves train_tokens_per_s."""


def read(run):
    w = run.worker
    if not w or not w["step_ends"]:
        return None
    return 1e3 * w["input_stall_s"] / len(w["step_ends"])
