"""jit'd public wrappers for the Pallas kernels.

On CPU hosts the kernels execute with ``interpret=True`` (Pallas runs the
kernel body in Python) — the TPU path compiles the same kernels natively.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.paged_attention import paged_attention as _paged
from repro.kernels.paged_attention import (paged_prefill_attention
                                           as _paged_prefill)
from repro.kernels.paged_attention import (paged_ragged_attention
                                           as _paged_ragged)
from repro.kernels.rmsnorm import rmsnorm as _rmsnorm
from repro.kernels.sampling import batched_accept as _batched_accept
from repro.kernels.sampling import batched_sample as _batched_sample
from repro.kernels.w4a16_gemm import w4a16_gemm as _w4a16


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: Optional[float] = None, block_q: int = 128,
                    block_k: int = 128, interpret: Optional[bool] = None):
    return _flash(q, k, v, causal=causal, window=window, scale=scale,
                  block_q=block_q, block_k=block_k, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_attention(q, k_pages, v_pages, page_table, context_lens, *,
                    k_scales=None, v_scales=None,
                    scale: Optional[float] = None,
                    interpret: Optional[bool] = None):
    return _paged(q, k_pages, v_pages, page_table, context_lens,
                  k_scales=k_scales, v_scales=v_scales,
                  scale=scale, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_prefill_attention(q, k_pages, v_pages, page_table, context,
                            start, *, k_scales=None, v_scales=None,
                            scale: Optional[float] = None,
                            interpret: Optional[bool] = None):
    return _paged_prefill(q, k_pages, v_pages, page_table, context, start,
                          k_scales=k_scales, v_scales=v_scales,
                          scale=scale, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_ragged_attention(q, k_pages, v_pages, page_tables, contexts,
                           starts, *, k_scales=None, v_scales=None,
                           scale: Optional[float] = None,
                           interpret: Optional[bool] = None):
    """One fused ragged attention step: q [B, C, H, D] mixed decode +
    prefill-chunk rows, each against its own page-table row.  Jit
    variants are keyed by the (B, C) shape — callers bucket both to
    powers of two so the variant count stays bounded (see
    ``PagedModelRunner.run_step``).  Passing ``k_scales``/``v_scales``
    ([P, page_size, Kv]) selects the quantized-pool variant with dequant
    fused into the page loop."""
    return _paged_ragged(q, k_pages, v_pages, page_tables, contexts,
                         starts, k_scales=k_scales, v_scales=v_scales,
                         scale=scale, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("n_top", "use_planes",
                                             "all_greedy",
                                             "need_logprobs"))
def batched_sample(logits, seeds, counters, temperature, top_k, top_p,
                   min_p, typical_p, freq_pen, pres_pen, rep_pen, bias,
                   counts, mask_bits, *, n_top: int = 0,
                   use_planes: bool = True, all_greedy: bool = False,
                   need_logprobs: bool = True):
    """One fused logits→token sampling op over ``[S, V]`` rows (bias,
    penalties, grammar bitmask, temperature/top-k/top-p/min-p/typical-p,
    counter-based Gumbel-max draw, optional top-``n_top`` logprobs
    gather).  The engine path chains the same function INSIDE the fused
    ragged step jit (``PagedModelRunner.run_step``) so sampling adds no
    dispatch; this standalone wrapper serves tests.  Jit
    variants are keyed by ``(S, V, n_top)`` — callers bucket S."""
    return _batched_sample(logits, seeds, counters, temperature, top_k,
                           top_p, min_p, typical_p, freq_pen, pres_pen,
                           rep_pen, bias, counts, mask_bits, n_top=n_top,
                           use_planes=use_planes, all_greedy=all_greedy,
                           need_logprobs=need_logprobs)


@jax.jit
def batched_accept(tokens, drafts, win_off):
    """Batched speculative acceptance over the step's sampling rows:
    ``emit[s]`` is True iff every earlier row of row ``s``'s verify
    window resampled exactly its draft token (``win_off`` gives each
    row's offset inside its window; ``drafts == -1`` means nothing to
    check).  The engine path runs the same function INSIDE the fused
    step jit; this wrapper serves tests."""
    return _batched_accept(tokens, drafts, win_off)


@functools.partial(jax.jit, static_argnames=("group", "block_m", "block_n",
                                             "interpret"))
def w4a16_gemm(x, w_packed, scales, *, group: int = 64, block_m: int = 128,
               block_n: int = 128, interpret: Optional[bool] = None):
    return _w4a16(x, w_packed, scales, group=group, block_m=block_m,
                  block_n=block_n, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows",
                                             "interpret"))
def rmsnorm(x, scale, *, eps: float = 1e-5, residual=None,
            block_rows: int = 256, interpret: Optional[bool] = None):
    return _rmsnorm(x, scale, eps=eps, residual=residual,
                    block_rows=block_rows, interpret=interpret)
