"""Operations and bytes the algorithm needs, from the shapes alone.

What a step *requires*, not what a kernel moves: real rows only, each
row's real context, the unpadded head dim, bf16 operands.  A row is
``(start, n)``: ``n`` new tokens at positions ``start .. start + n - 1``
of one sequence, each attending causally to every earlier position and
to itself.
"""
from __future__ import annotations

from typing import Iterable, Tuple

BF16 = 2


def _context_sum(start: int, n: int) -> int:
    """Sum over the row's tokens of the positions each attends to."""
    return n * start + n * (n + 1) // 2


def attention_flops(s, rows: Iterable[Tuple[int, int]]) -> int:
    """QK^T and PV for every query head and layer: 2 matmuls x 2 flops x
    heads x head_dim per (query, key) pair."""
    pairs = sum(_context_sum(a, n) for a, n in rows)
    return 4 * s.layers * s.heads * s.head_dim * pairs


def attention_bytes(s, rows: Iterable[Tuple[int, int]]) -> int:
    """Each row reads K and V of its whole context once and its queries
    once, and writes its outputs once, in every layer."""
    total = 0
    for a, n in rows:
        kv = 2 * (a + n) * s.kv_heads * s.head_dim
        qo = 2 * n * s.heads * s.head_dim
        total += (kv + qo) * BF16
    return s.layers * total


def layer_params(s) -> int:
    """Weights every token multiplies: projections and MLP of all layers
    (the embedding is a lookup)."""
    q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
    return s.layers * (s.d * q + 2 * s.d * kv + q * s.d + 3 * s.d * s.d_ff)


def model_flops(s, rows: Iterable[Tuple[int, int]]) -> int:
    """Forward flops of the rows: 2 per layer weight per real token, the
    output head once per row (a row is sampled at its last token), and
    attention."""
    rows = list(rows)
    tokens = sum(n for _, n in rows)
    return (2 * layer_params(s) * tokens + 2 * s.d * s.vocab * len(rows)
            + attention_flops(s, rows))
