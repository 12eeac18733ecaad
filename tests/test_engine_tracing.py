"""The engine's spans in a profiler trace, and the counters that the
benchmark's per-layer metrics read (pad share, queue wait, KV
occupancy).

Spans are recorded with ``jax.profiler`` into the same trace as the
device's operations; ``bench.trace.load`` reads them back the way the
benchmark does."""
import threading
import time

import jax
import pytest

from bench import spans as bench_spans
from bench import trace as bench_trace
from repro.configs import get_config
from repro.core import MLCEngine
from repro.core.paged_runner import PagedModelRunner
from repro.core.scheduler import Scheduler
from repro.models import model
from repro.models.pdef import init_params

CFG = get_config("llama-3.1-8b", reduced=True)
#: every span the benchmark's readers take by name
SPANS = bench_spans.PHASES + bench_spans.MARKERS


@pytest.fixture(scope="module")
def params():
    return init_params(model.params_def(CFG), jax.random.PRNGKey(0))


def _engine(params):
    eng = MLCEngine()
    eng.load_model("m", CFG, params=params, backend="paged", max_slots=3,
                   max_context=96, page_size=4, prefill_chunk_size=6,
                   seed=0, enable_prefix_cache=False, pipeline_depth=2)
    return eng


def _chat(eng, text, n):
    return eng.chat_completions_create({
        "model": "m", "max_tokens": n, "temperature": 0.0,
        "messages": [{"role": "user", "content": text}]})


def _serve(eng, prompts):
    """Serve ``prompts`` at once, one caller thread each, and return
    once all have been answered."""
    out = [None] * len(prompts)

    def call(i):
        out[i] = _chat(eng, prompts[i], 6)
    threads = [threading.Thread(target=call, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(300)
    assert all(not t.is_alive() for t in threads)
    return out


def _inside(a, b):
    """Span ``a`` lies within span ``b`` on the same thread."""
    return a[3] == b[3] and b[1] <= a[1] and a[2] <= b[2]


def test_engine_spans_in_a_profiler_trace(params, tmp_path):
    eng = _engine(params)
    _chat(eng, "warm the step programs", 4)         # compiles untraced
    st0 = eng.stats("m")
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(bench_trace.WINDOW):
            res = _serve(eng, ["a long prompt that prefills in chunks",
                               "hello", "another prompt of a few words"])
            # the loop goes idle, then stops, so every span has closed
            time.sleep(0.3)
            st1 = eng.stats("m")
            eng.shutdown()
            for th in threading.enumerate():
                if th.name == "repro-engine-loop":
                    th.join(60)
                    assert not th.is_alive()
    finally:
        jax.profiler.stop_trace()
    assert all(r.choices[0].finish_reason == "length" for r in res)
    t = bench_trace.load(str(tmp_path))
    by = {n: [ev for ev in t.host if ev[0] == n] for n in SPANS}
    assert all(by[n] for n in SPANS), {n: len(v) for n, v in by.items()}
    steps = st1["engine"]["exec_steps"] - st0["engine"]["exec_steps"]
    assert len(by["engine.step"]) == steps > 0
    assert len(by["engine.admit"]) == len(by["engine.finish"]) == 3
    # every blocking pull sits in a drain; at depth 2 a step drains the
    # step before it while the device computes
    for m in by["runner.materialize"]:
        assert any(_inside(m, d) for d in by["engine.drain"])
    assert any(_inside(m, d) and _inside(d, s)
               for m in by["runner.materialize"] for d in by["engine.drain"]
               for s in by["engine.step"])
    for name in ("engine.pack", "runner.dispatch"):
        assert all(any(_inside(x, s) for s in by["engine.step"])
                   for x in by[name])
    # runner.materialize times what host_block_s counts (the wait and
    # the token pull), so the span metrics and host_ms_per_step share
    # one split of the loop's time
    blocked = (st1["runner"]["host_block_s"]
               - st0["runner"]["host_block_s"]) * 1e9
    spans = sum(e - s for _, s, e, _ in by["runner.materialize"])
    assert blocked <= spans <= blocked + 2e6 * len(
        by["runner.materialize"])


def test_pad_and_live_token_counters(params):
    pr = PagedModelRunner(CFG, params, num_pages=16, page_size=4,
                          max_slots=2, pages_per_seq=6)
    a = pr.begin_seq([1, 2, 3, 4, 5])
    b = pr.begin_seq([6, 7, 8])
    # rows of 5 and 3 tokens: a (2, 8) bucket of 16 slots
    pr.run_step([(a, [1, 2, 3, 4, 5], "prefill"), (b, [6, 7, 8], "prefill")],
                return_logits=False)
    # one decode row of a: a (1, 1) bucket; a holds 6 tokens, b 3
    pr.run_step([(a, [9], "decode")], return_logits=False)
    st = pr.stats()
    assert st["ragged_steps"] == 2
    assert st["bucket_tokens"] == 2 * 8 + 1 * 1
    assert st["prefill_tokens"] + st["decode_tokens"] == 9
    assert st["pages"]["live_token_steps"] == (5 + 3) + (6 + 3)
    assert (st["pages"]["num_pages"], st["pages"]["page_size"]) == (16, 4)


def test_queue_wait_counter():
    s = Scheduler(max_slots=2, max_context=32)
    s.count_queue_wait(0.25)
    s.count_queue_wait(0.5)
    st = s.stats()
    assert st["queue_wait_s"] == pytest.approx(0.75)
    assert st["queue_waits"] == 2


def test_queue_waits_counted_once_per_request(params):
    eng = _engine(params)
    try:
        t0 = time.time()
        _serve(eng, ["one", "two words", "three words here", "four"])
        took = time.time() - t0
        st = eng.stats("m")["scheduler"]
        # four requests, three slots: each counted once, at its first
        # admission, and no wait outlasts the run
        assert st["queue_waits"] == 4 == st["admitted"]
        assert 0 < st["queue_wait_s"] < 4 * took
    finally:
        eng.shutdown()
