"""Model step: device time of one decode step, median of the trace, under
ordinary load. Moves itl_p95_ms."""

from lib import readers


def read(run):
    return readers.program_median_ms(run, readers.DECODE_PROGRAM)
