"""Submission (client/, am/, executor/): host clock from the submit to the
launched process's first line. Moves setup_s."""


def read(run):
    return run.launch_s
