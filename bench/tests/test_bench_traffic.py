"""The traffic generator: deterministic by seed, lengths in range, the
same multiset of lengths on every seed."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench.traffic import closed_loop, spread

MIX = json.loads((Path(__file__).resolve().parents[1] / "traffic"
                  / "long-decode.json").read_text())
BIG = 2**31 + 12345                 # seeds are wider than 32 bits


def _flat(plans):
    return [r for p in plans for r in p]


def test_same_seed_same_requests():
    a, b = closed_loop(MIX, 32064, BIG), closed_loop(MIX, 32064, BIG)
    for x, y in zip(_flat(a), _flat(b)):
        assert x.rid == y.rid and x.max_tokens == y.max_tokens
        assert x.seed == y.seed and x.temperature == y.temperature
        np.testing.assert_array_equal(x.prompt, y.prompt)


def test_lengths_in_range_and_ids_in_vocab():
    plans = closed_loop(MIX, 32064, 7)
    assert [len(p) for p in plans] == [MIX["rounds"]] * MIX["clients"]
    later = [r for p in plans for r in p[1:]]
    lo, hi = MIX["prompt_tokens"]
    assert all(lo <= len(r.prompt) <= hi for r in _flat(plans))
    lo, hi = MIX["output_tokens"]
    assert all(lo <= r.max_tokens <= hi for r in later)
    reqs = _flat(plans)
    assert all(r.prompt.min() >= 0 and r.prompt.max() < 32064 for r in reqs)


def test_every_seed_holds_the_same_lengths_and_its_own_ids():
    a, b = closed_loop(MIX, 32064, 1), closed_loop(MIX, 32064, BIG)
    shape = lambda p: [[(len(r.prompt), r.max_tokens) for r in c] for c in p]
    assert shape(a) == shape(b)
    assert not np.array_equal(a[0][1].prompt, b[0][1].prompt)
    ramp = [(len(c[0].prompt), c[0].max_tokens) for c in a]
    assert ramp == list(zip(MIX["ramp_prompt_tokens"],
                            MIX["ramp_output_tokens"]))


def test_each_round_after_the_ramp_holds_the_spread_lengths():
    plans = closed_loop(MIX, 32064, 5)
    c = MIX["clients"]
    want_p = sorted(spread(*MIX["prompt_tokens"], c))
    want_o = sorted(spread(*MIX["output_tokens"], c))
    for r in range(1, MIX["rounds"]):
        assert sorted(len(p[r].prompt) for p in plans) == want_p
        assert sorted(p[r].max_tokens for p in plans) == want_o


def test_greedy_clients_as_listed():
    plans = closed_loop(MIX, 32064, 3)
    for c, p in enumerate(plans):
        want = c in MIX["greedy_clients"]
        assert all(r.greedy == want for r in p)
        assert all(r.top_p == (1.0 if want else MIX["top_p"]) for r in p)


@pytest.mark.parametrize("key, bad", [("ramp_prompt_tokens", [64] * 6),
                                      ("ramp_output_tokens", [0] * 6),
                                      ("ramp_output_tokens", [600] * 6),
                                      ("ramp_prompt_tokens", [1280] * 5)])
def test_ramp_out_of_range_is_refused(key, bad):
    with pytest.raises(ValueError):
        closed_loop(dict(MIX, **{key: bad}), 32064, 1)


@pytest.mark.parametrize("lo, hi, n", [(1024, 1536, 288), (256, 512, 7),
                                       (5, 5, 3)])
def test_spread_covers_range_evenly(lo, hi, n):
    v = spread(lo, hi, n)
    assert len(v) == n and v.min() >= lo and v.max() <= hi
    assert np.all(np.diff(v) >= 0)
    assert abs(v.mean() - (lo + hi) / 2) <= 1
