"""The per-layer readers' arithmetic on a run and a trace made by hand."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import flops, harness
from bench.serve import Record
from bench.traffic import Request
from bench.references.gqa_decoder import Sizes
from bench.trace import Trace

S = Sizes(layers=2, d=64, heads=4, kv_heads=2, head_dim=16, d_ff=128,
          vocab=512, rope_theta=1e4, eps=1e-5)
PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
MS = 1e6
ROOT = Path(__file__).resolve().parents[2]


def _stats(steps, host_ms, decode, ragged):
    return {"engine": {"exec_steps": steps, "host_ms_per_step": host_ms},
            "runner": {"decode_tokens": decode, "ragged_steps": ragged}}


def _record(prompt, t_submit, chunks):
    req = Request(client=0, index=0, prompt=np.zeros(prompt, np.int32),
                  max_tokens=len(chunks), temperature=0.0, top_p=1.0, seed=0)
    return Record(req, t_submit=t_submit, chunks=[(t, 1) for t in chunks])


#: the window is 0.5-3.0 s: a request decoding across it (tokens 1 and 2
#: in it), one prefilled and first decoded in it, and one whose prefill
#: (2.0 s to its first token at 4.0 s) is half inside
RECORDS = [_record(100, -5.0, [-1.0, 1.0, 2.0, 5.0]),
           _record(16, 1.0, [2.0, 2.5]),
           _record(40, 2.0, [4.0])]
ROWS = [(100, 1), (101, 1), (0, 16), (16, 1), (0, 20)]


def _run(trace=None):
    return harness.Run(
        cell=None, sizes=S, records=RECORDS, t_open=0.5, t_close=3.0,
        stats_open=_stats(10, 2.0, 40, 10),
        stats_close=_stats(20, 3.0, 60, 20), setup_s=7.0, peak=PEAK,
        ref=flops, trace=trace)


def test_window_rows_from_the_requests():
    assert _run().window_rows() == ROWS


def _trace():
    # two step runs of 4 ms, each with one kernel call per layer of 1 ms
    ops = [["paged_ragged_attention.1", 0, 1 * MS, ""],
           ["paged_ragged_attention.2", 2 * MS, 3 * MS, ""],
           ["fusion.7", 3 * MS, 4 * MS, ""],
           ["paged_ragged_attention.1", 5 * MS, 6 * MS, ""],
           ["paged_ragged_attention.2", 6 * MS, 7 * MS, ""]]
    modules = [["jit__ragged_sample_step(123)", 0, 4 * MS, ""],
               ["jit__ragged_sample_step(123)", 5 * MS, 9 * MS, ""]]
    return Trace(ops, modules, [], (0.0, 10 * MS))


def test_counter_readers():
    r = _run()
    # host ms: 20 * 3.0 - 10 * 2.0 = 40 over 10 steps
    assert harness.reader("host_ms_per_step.decode")(r) == pytest.approx(4)
    assert harness.reader("decode_rows_per_step.decode")(r) == 2.0
    assert harness.reader("setup_s")(r) == 7.0


def test_trace_readers_say_nothing_without_a_trace():
    r = _run()
    for name in ("step_device_ms.decode", "paged_attention_roofline.decode",
                 "idle_share.decode", "mfu.decode"):
        assert harness.reader(name)(r) is None


def test_trace_readers():
    r = _run(_trace())
    assert harness.reader("step_device_ms.decode")(r) == pytest.approx(4.0)
    # ops cover 0-1, 2-4 and 5-7 ms of the 10 ms window
    assert harness.reader("idle_share.decode")(r) == pytest.approx(50.0)
    need = max(flops.attention_flops(S, ROWS) / 1e12,
               flops.attention_bytes(S, ROWS) / 1e9)
    # 4 kernel calls of 1 ms in the window
    assert harness.reader("paged_attention_roofline.decode")(r) == (
        pytest.approx(100 * need / 4e-3))
    # two step runs of 4 ms
    assert harness.reader("mfu.decode")(r) == pytest.approx(
        100 * flops.model_flops(S, ROWS) / (8e-3 * 1e12))


def test_trace_readers_count_with_the_configurations_reference():
    conf = json.loads((ROOT / "bench/configs/phi-3.5-mini.json").read_text())
    r = _run(_trace())
    r.ref = harness.reference(conf)
    for name in ("paged_attention_roofline.decode", "mfu.decode"):
        got = harness.reader(name)(r)
        assert got is not None
        assert got == harness.reader(name)(_run(_trace()))
