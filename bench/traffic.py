"""The one traffic generator: a mix's data file in, requests out.

Every seed gets the same prompt and answer lengths and its own token ids
and sampling seeds: seeds change what is computed, not how much.

Kinds of mix (the file's ``kind``):

``closed_loop``
    ``clients`` callers, each sending its next request when the previous
    one has ended, ``rounds`` requests each.  Round 0 (the ramp) gives
    client ``i`` ``ramp_prompt_tokens[i]`` and ``ramp_output_tokens[i]``:
    the ramp's answers are staggered so that the callers run out of
    phase, as in a loop that has turned over for a while, and request
    ends and new prompts fall inside the window.  In every later round
    the clients share the same ``clients`` prompt lengths and answer
    lengths, spread evenly over ``prompt_tokens`` and ``output_tokens``
    (inclusive ranges) and handed round by round: client ``i`` takes
    the ``(i + r) mod clients``-th prompt length and the
    ``(i + 2r) mod clients``-th answer length.  Lengths are the same on
    every seed, so every seed holds the same work; the seed draws the
    token ids and the sampling seeds.  The clients listed in
    ``greedy_clients`` decode greedily (their answers are what the check
    compares); the others sample at ``temperature`` and ``top_p``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List

import numpy as np


@dataclass
class Request:
    client: int
    index: int
    prompt: np.ndarray            # int32 token ids
    max_tokens: int
    temperature: float
    top_p: float
    seed: int

    @property
    def rid(self) -> str:
        return f"c{self.client}-{self.index}"

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0


def spread(lo: int, hi: int, n: int) -> np.ndarray:
    """``n`` whole numbers spread evenly over ``lo..hi``: the midpoints
    of ``n`` equal slices of the range."""
    return np.floor(lo + (np.arange(n) + 0.5) * (hi - lo + 1) / n).astype(
        np.int64)


def closed_loop(t: dict, vocab: int, seed: int) -> List[List[Request]]:
    """Each client's requests, in the order it sends them."""
    if t["kind"] != "closed_loop":
        raise ValueError(f"not a closed loop: {t['kind']!r}")
    c, rounds = t["clients"], t["rounds"]
    lo, hi = t["prompt_tokens"]
    ramp_p = np.asarray(t["ramp_prompt_tokens"], np.int64)
    if len(ramp_p) != c or ramp_p.min() < lo or ramp_p.max() > hi:
        raise ValueError(f"ramp_prompt_tokens needs {c} lengths in "
                         f"{lo}..{hi}")
    ramp_o = np.asarray(t["ramp_output_tokens"], np.int64)
    if len(ramp_o) != c or ramp_o.min() < 1 or ramp_o.max() > t[
            "output_tokens"][1]:
        raise ValueError(f"ramp_output_tokens needs {c} lengths in "
                         f"1..{t['output_tokens'][1]}")
    p_len, o_len = spread(lo, hi, c), spread(*t["output_tokens"], c)
    rng = np.random.default_rng(seed)
    greedy = set(t["greedy_clients"])
    plans: List[List[Request]] = [[] for _ in range(c)]
    for r in range(rounds):
        for i in range(c):
            n_p = ramp_p[i] if r == 0 else p_len[(i + r) % c]
            n_o = ramp_o[i] if r == 0 else o_len[(i + 2 * r) % c]
            g = i in greedy
            plans[i].append(Request(
                client=i, index=r,
                prompt=rng.integers(0, vocab, int(n_p), dtype=np.int32),
                max_tokens=int(n_o),
                temperature=0.0 if g else float(t["temperature"]),
                top_p=1.0 if g else float(t["top_p"]),
                seed=int(rng.integers(0, 2**31 - 1))))
    return plans
