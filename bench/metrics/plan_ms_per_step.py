"""Engine loop, scheduling: host milliseconds per fused step spent
planning the step (``engine.plan``), revalidating its rows and binding
admissions (``engine.rows``), packing sampling parameters
(``engine.pack``) and packing and dispatching the step
(``runner.dispatch``): the self time of those spans inside the traced
window over the number of ``engine.step`` spans in it.  None without a
trace, or where the program records no such spans."""
from bench.spans import self_ms_per_step

#: the spans read, by name
SPANS = ("engine.plan", "engine.rows", "engine.pack", "runner.dispatch")


def read(run):
    return self_ms_per_step(run, SPANS)
