"""Arithmetic shared by the metric readers in ``bench/metrics/``.

A reader returns None where its run holds nothing for it to read (no
trace, no step of the program in the window), and the harness then
leaves the metric out of the result line."""
from __future__ import annotations

from typing import Optional, Sequence


def output_tok_s(run) -> float:
    """Output tokens streamed inside the window over its seconds."""
    n = sum(k for r in run.records for t, k in r.chunks
            if run.t_open <= t < run.t_close)
    return n / (run.t_close - run.t_open)


def delta(a: dict, b: dict, *path) -> float:
    """Change of the counter at ``path`` from stats ``a`` to stats ``b``."""
    for p in path:
        a, b = a[p], b[p]
    return b - a


def _delta(run, *path) -> float:
    return delta(run.stats_open, run.stats_close, *path)


def host_ms_per_step(run) -> Optional[float]:
    """Host milliseconds per engine step not hidden behind the device."""
    def host_ms(st):
        e = st["engine"]
        return e["host_ms_per_step"] * e["exec_steps"]
    steps = _delta(run, "engine", "exec_steps")
    if steps <= 0:
        return None
    return (host_ms(run.stats_close) - host_ms(run.stats_open)) / steps


def decode_rows_per_step(run) -> Optional[float]:
    steps = _delta(run, "runner", "ragged_steps")
    if steps <= 0:
        return None
    return _delta(run, "runner", "decode_tokens") / steps


def _step_runs(run, programs: Sequence[str]):
    """Device seconds of each run of the step program, or None."""
    if run.trace is None:
        return None
    return run.trace.module_runs(programs) or None


def step_device_ms(run, programs: Sequence[str]) -> Optional[float]:
    """Mean device milliseconds of one run of the step program."""
    runs = _step_runs(run, programs)
    return None if runs is None else 1000 * sum(runs) / len(runs)


def idle_share(run) -> Optional[float]:
    if run.trace is None or not run.trace.ops:
        return None
    return 100 * (1 - run.trace.busy_s() / run.trace.window_s)


def kernel_roofline(run, kernel: Sequence[str]) -> Optional[float]:
    """Least time the chip needs for the attention that the window's
    tokens required (the larger of its flops over the peak flop rate and
    its bytes over the peak bandwidth, as the configuration's reference
    counts them) over the kernel's device time in the traced window,
    in %."""
    if run.trace is None:
        return None
    rows = run.window_rows()
    secs, calls = run.trace.op_time_s(kernel)
    if calls == 0 or not rows:
        return None
    s, p, c = run.sizes, run.peak, run.ref
    need = max(c.attention_flops(s, rows) / p["bf16_flops_per_s"],
               c.attention_bytes(s, rows) / p["hbm_bytes_per_s"])
    return 100 * need / secs


def mfu(run, programs: Sequence[str]) -> Optional[float]:
    """Model flops that the window's tokens required (as the
    configuration's reference counts them) over the device time
    of the step program's runs in the traced window at the chip's peak,
    in %."""
    runs = _step_runs(run, programs)
    rows = run.window_rows()
    if runs is None or not rows:
        return None
    f = run.ref.model_flops(run.sizes, rows)
    return 100 * f / (sum(runs) * run.peak["bf16_flops_per_s"])
