"""Paged runner (core/paged_runner.py): mean device milliseconds of one
run of the fused sampled step in the trace."""
from bench.readers import step_device_ms

#: the step program, as the trace names it
PROGRAMS = ("_ragged_sample_step",)


def read(run):
    return step_device_ms(run, PROGRAMS)
