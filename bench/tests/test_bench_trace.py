"""The trace reduction, on events worked by hand and on an excerpt
recorded from a TPU v5e run of ``phi35.long-decode`` (32 layers, six
decode rows; 240 device ops of the window)."""
import json
from pathlib import Path

import pytest

from bench.trace import Trace, op_kind, op_name

DATA = Path(__file__).resolve().parent / "data"
MS = 1e6                                        # ns


def _hand():
    ops = [["fusion.1", 0 * MS, 4 * MS, ""],
           ["paged_ragged_attention.3", 3 * MS, 6 * MS, ""],  # overlaps
           ["fusion.2", 8 * MS, 9 * MS, ""],
           ["paged_ragged_attention.9", 9.5 * MS, 12 * MS, ""],  # past end
           ["early.1", -5 * MS, -1 * MS, ""]]                    # before
    modules = [["jit_step(77)", 0, 6 * MS, ""],
               ["jit_step(77)", 8 * MS, 12 * MS, ""]]
    host = [["PjitFunction(step)", 5.5 * MS, 8.5 * MS, "engine-loop"],
            ["outer", 0, 11 * MS, "engine-loop"]]
    return Trace(ops, modules, host, (0.0, 10 * MS))


def test_busy_is_the_union_inside_the_window():
    t = _hand()
    assert t.busy_intervals() == [(0, 6 * MS), (8 * MS, 9 * MS),
                                  (9.5 * MS, 10 * MS)]
    assert t.busy_s() == pytest.approx(7.5e-3)
    assert t.window_s == pytest.approx(10e-3)


def test_idle_gaps_longest_first_with_the_host_event_in_them():
    gaps = _hand().idle_gaps()
    assert gaps[0] == ("engine-loop: PjitFunction(step)",
                       pytest.approx(2e-3))
    assert gaps[1] == ("engine-loop: outer", pytest.approx(0.5e-3))
    assert len(gaps) == 2


def test_kernel_time_top_ops_and_module_runs():
    t = _hand()
    secs, n = t.op_time_s(["paged_ragged_attention"])
    assert n == 2 and secs == pytest.approx(3e-3 + 0.5e-3)
    top = dict(t.top_ops())
    assert top["fusion"] == pytest.approx(5e-3)
    assert "early" not in top
    assert t.module_runs(["jit_step"]) == [pytest.approx(6e-3)]


def test_op_names_from_hlo_text():
    text = ("%paged_ragged_attention.41 = bf16[8,32,1,128]{3,2,1,0} "
            "custom-call(s32[8,128]{1,0} %copy-done.51)")
    assert op_name(text) == "paged_ragged_attention.41"
    assert op_kind("paged_ragged_attention.41") == "paged_ragged_attention"
    assert op_kind("copy-start") == "copy-start"
    assert op_kind("slice-done.7") == "slice-done"


def test_recorded_excerpt():
    t = Trace.from_json(json.loads(
        (DATA / "trace_excerpt.json").read_text()))
    assert len(t.ops) == 240
    # the device never rests inside this slice of a decode-bound window
    assert t.busy_s() == pytest.approx(t.window_s, rel=1e-4)
    secs, n = t.op_time_s(["paged_ragged_attention"])
    assert n == 6                         # one kernel call per layer
    assert secs / t.busy_s() == pytest.approx(0.9054, abs=1e-3)
    assert t.top_ops(1)[0][0] == "paged_ragged_attention"
    assert all(g < 1e-6 for _, g in t.idle_gaps())


def test_json_round_trip():
    t = _hand()
    u = Trace.from_json(json.loads(json.dumps(t.to_json())))
    assert u.busy_s() == t.busy_s() and u.idle_gaps() == t.idle_gaps()
