"""Engine loop, consumption and streaming: host milliseconds per fused
step spent consuming a finished step's tokens (the pulls of the rest of
its results, detokenizing, streaming, finish detection): the self time
of the ``engine.drain`` spans inside the traced window, less their wait
for the step and its token pull (``runner.materialize``), over the
number of ``engine.step`` spans in it.  None without a trace, or where
the program records no such spans."""
from bench.spans import self_ms_per_step

#: the spans read, by name
SPANS = ("engine.drain",)


def read(run):
    return self_ms_per_step(run, SPANS)
