"""The readers of the engine's own counters and spans (pad share, queue
wait, KV occupancy, host time per step), on stats and a trace made by
hand; and what they give for a program that counts and records none of
them (None, not an error)."""
import pytest

from bench import harness
from bench.trace import Trace

MS = 1e6                                        # ns
COUNTERS = ("pad_share", "queue_wait_ms", "kv_live_share")
SPANS = ("plan_ms_per_step", "consume_ms_per_step")


def _stats(prefill, decode, bucket, ragged, wait_s, waits, live):
    return {"engine": {"exec_steps": ragged, "host_ms_per_step": 1.0},
            "scheduler": {"queue_wait_s": wait_s, "queue_waits": waits},
            "runner": {"prefill_tokens": prefill, "decode_tokens": decode,
                       "bucket_tokens": bucket, "ragged_steps": ragged,
                       "pages": {"num_pages": 50, "page_size": 4,
                                 "live_token_steps": live}}}


def _old_stats():
    """A program without the counters: the keys are absent."""
    return {"engine": {"exec_steps": 10, "host_ms_per_step": 1.0},
            "scheduler": {"admitted": 3},
            "runner": {"prefill_tokens": 0, "decode_tokens": 0,
                       "ragged_steps": 10, "pages": {"used_pages": 5}}}


def _run(open_, close, trace=None):
    return harness.Run(cell=None, sizes=None, records=[], t_open=0.0,
                       t_close=1.0, stats_open=open_, stats_close=close,
                       setup_s=1.0, peak={}, ref=None, trace=trace)


def test_counter_readers():
    r = _run(_stats(100, 40, 1000, 10, 1.0, 3, 0),
             _stats(600, 140, 3000, 20, 1.6, 6, 1000))
    # 600 real tokens in 2000 bucket slots
    assert harness.reader("pad_share")(r) == pytest.approx(70.0)
    # 0.6 s over 3 requests first admitted in the window
    assert harness.reader("queue_wait_ms")(r) == pytest.approx(200.0)
    # 1000 token-steps over 10 steps of a 50 x 4 token pool
    assert harness.reader("kv_live_share")(r) == pytest.approx(50.0)


def test_counter_readers_say_nothing_without_work():
    s = _stats(100, 40, 1000, 10, 1.0, 3, 0)
    r = _run(s, s)
    for name in COUNTERS:
        assert harness.reader(name)(r) is None


def _loop(name, s, e):
    return [name, s * MS, e * MS, "loop"]


def _trace():
    host = [
        _loop("engine.plan", -5, 3),             # clipped to 0-3
        _loop("engine.rows", 5, 10),
        _loop("engine.admit", 6, 7),             # a marker: rows' own time
        _loop("engine.step", 10, 40),
        _loop("engine.pack", 10, 12),
        _loop("runner.dispatch", 12, 17),
        _loop("PjitFunction(step)", 13, 16),     # the runtime's, not a phase
        _loop("engine.drain", 17, 35),
        _loop("runner.materialize", 18, 30),
        _loop("engine.finish", 31, 32),          # a marker: drain's own time
        ["runner.materialize", 20 * MS, 22 * MS, "other"],  # another thread
        _loop("engine.plan", 35, 38),
        _loop("engine.rows", 40, 42),
        _loop("engine.step", 42, 70),
        _loop("engine.pack", 42, 43),
        _loop("runner.dispatch", 43, 47),
        _loop("engine.drain", 47, 65),
        _loop("runner.materialize", 48, 60),
        _loop("engine.plan", 65, 69),
        _loop("engine.idle", 80, 90),
        _loop("engine.step", 95, 110),           # half inside
        _loop("runner.dispatch", 96, 99),
        _loop("engine.plan", 105, 108),          # past the window
    ]
    return Trace([], [], host, (0.0, 100 * MS))


def test_span_readers():
    s = _stats(0, 0, 0, 0, 0.0, 0, 0)
    r = _run(s, s, _trace())
    # plans 3 + 3 + 4, rows 5 + 2, packs 2 + 1, dispatches 5 + 4 + 3 ms
    # over 3 steps
    assert harness.reader("plan_ms_per_step")(r) == pytest.approx(32 / 3)
    # drains of 18 ms less 12 ms of blocking pulls, twice, over 3 steps
    assert harness.reader("consume_ms_per_step")(r) == pytest.approx(4.0)


def test_span_readers_say_nothing_without_spans():
    s = _stats(0, 0, 0, 0, 0.0, 0, 0)
    assert all(harness.reader(n)(_run(s, s)) is None for n in SPANS)
    runtime_only = Trace([], [], [["PjitFunction(step)", 0, MS, "loop"]],
                         (0.0, 10 * MS))
    assert all(harness.reader(n)(_run(s, s, runtime_only)) is None
               for n in SPANS)


def test_a_program_without_the_counters_and_spans():
    r = _run(_old_stats(), _old_stats(),
             Trace([], [], [["_np.asarray", 0, MS, "python3"]],
                   (0.0, 10 * MS)))
    for name in COUNTERS + SPANS:
        assert harness.reader(name)(r) is None
