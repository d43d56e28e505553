"""Serving engine: device time of one admission (the batch-1 prefill
program), median over the traced admissions. Moves itl_p95_ms."""

from lib import readers


def read(run):
    return readers.program_median_ms(run, readers.ADMIT_PROGRAM)
