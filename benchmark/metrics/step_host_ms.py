"""Serving engine: the host's share of the gap between two decode steps,
from the program's own host-clock counter (`EngineStats.step_host_s`:
from the previous step's tokens landing on the host to this step's
dispatch returning, less the admissions in between), median of the last
2048 steps at the window's close. Moves itl_p95_ms."""


def read(run):
    return (run.engine or {}).get("step_host_ms_p50")
