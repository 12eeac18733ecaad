"""The engine's own host spans in a traced window, and their self time.

The program records its phases as ``jax.profiler`` spans on the engine
loop's thread (docs/ARCHITECTURE.md, "Observability").  A phase's self
time is its duration less the part that the phases nested inside it on
the same thread cover; the markers ``engine.admit`` and
``engine.finish`` count in their parents."""
import bisect
import itertools

#: the engine's phase spans, the only ones taken out of a parent's time
PHASES = ("engine.step", "engine.plan", "engine.rows", "engine.pack",
          "runner.dispatch", "engine.drain", "runner.materialize",
          "engine.idle")
#: per-request markers, carrying the request id
MARKERS = ("engine.admit", "engine.finish")


def clipped(run, names):
    """The spans of ``names`` that overlap the window, clipped to it, as
    ``(start, end, thread, name)`` in order of start."""
    a, b = run.trace.window
    return sorted((max(s, a), min(e, b), th, n)
                  for n, s, e, th in run.trace.host
                  if n in names and e > a and s < b)


def _self_ns(span, inner) -> float:
    """``span``'s duration less the union of the spans of ``inner``
    (sorted by start) nested inside it on its thread."""
    s, e, th, _ = span
    covered, t = 0.0, s
    first = bisect.bisect_left(inner, (s,))
    for kid in itertools.islice(inner, first, None):
        cs, ce, cth, _ = kid
        if cs > e:
            break
        if cth != th or ce > e or kid == span:
            continue
        if ce > t:
            covered += ce - max(cs, t)
            t = ce
    return (e - s) - covered


def self_ms_per_step(run, names):
    """Summed self time of the spans of ``names`` inside the window, in
    ms, over the number of ``engine.step`` spans in it.  None without a
    trace, or where the program records no steps."""
    if run.trace is None:
        return None
    steps = len(clipped(run, ("engine.step",)))
    if steps == 0:
        return None
    inner = clipped(run, PHASES)
    return sum(_self_ns(sp, inner) for sp in clipped(run, names)) / (
        1e6 * steps)
