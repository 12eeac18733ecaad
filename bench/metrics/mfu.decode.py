"""Whole step: model flops of the window's steps over their device time
at the chip's peak."""
from bench.readers import mfu

#: the step program, as the trace names it
PROGRAMS = ("_ragged_sample_step",)


def read(run):
    return mfu(run, PROGRAMS)
