"""Paged attention — one Pallas TPU kernel for decode, prefill, mixed steps.

The TPU rethink of WebLLM's PagedAttention WebGPU kernel: the per-sequence
page table is SCALAR-PREFETCHED (``PrefetchScalarGridSpec``) so the
``BlockSpec`` index maps can route each grid step's HBM->VMEM DMA to the
right physical page — the gather never materializes in HBM.  Online
softmax (flash-decode) accumulates across the sequential page grid
dimension in VMEM scratch.

Page pools are HEAD-MAJOR, ``[P, Kv, page_size, Dp]``: a grid step DMAs
the ``(page_size, Dp)`` tile of one kv head of one physical page, a block
whose last two dims are the array's own, which Mosaic lowers for any
``Kv`` and ``Dp``.  (Token-major ``[P, page_size, Kv, D]`` pools would
need a block that slices one head out of the second-minor dim, which
Mosaic refuses.)  ``Dp`` may exceed the query head dim ``D`` with zero
lanes: a pool padded to a multiple of 128 lanes keeps the TPU's default
row-major layout, where an unaligned ``D`` lets XLA pick a permuted
layout and copy the whole pool to and from it around every kernel call.
Int8 pools carry per-(token, kv-head) scales ``[P, page_size, Kv]``: a
grid step loads one page's whole ``[page_size, Kv]`` plane, picks its
head's column as a ``[1, page_size]`` lane vector, and applies it to the
score columns (K) and to the probabilities before the PV dot (V), so the
int8 tile reaches the dot unscaled.  Read row-major, that plane pads its
Kv lanes to 128 (4x for Kv = 32); the ``[P, Kv, 1, page_size]`` plane a
per-head block would need pads 16x.

``paged_ragged_attention`` is the kernel — one fused call for a whole
engine step: B ragged rows, each a chunk of up to C consecutive tokens
of its OWN sequence (a decode token is a length-1 row):
    q            [B, C, H, D]   (row b: queries at starts[b] ..)
    k_pages      [P, Kv, page_size, Dp]  (physical page pool, Dp >= D)
    v_pages      [P, Kv, page_size, Dp]
    page_tables  [B, pages_per_seq] int32
    contexts     [B] int32      (per-seq valid tokens incl. this chunk)
    starts       [B] int32      (per-seq global position of q row 0)
    Grid: (B, Kv, pages_per_seq); all C*G query rows of a (b, kv) pair
    ride in one [C*G, D] tile (G = H // Kv query heads per kv head) and
    row r attends to keys t < contexts[b] with t <= starts[b] + r//G.
    Pad rows inside a chunk (positions >= contexts[b]) produce garbage;
    fully padded batch rows (contexts[b] == 0) skip every page and
    output zeros.  The caller's pad K/V writes go to a trash page,
    never read here.

``paged_attention`` (decode: one token per sequence) and
``paged_prefill_attention`` (one chunk of one sequence) are the same
kernel with C = 1 and B = 1 respectively.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _head_row(s_ref, kv):
    """Column ``kv`` of one page's ``[page_size, Kv]`` scale plane as a
    ``[1, page_size]`` f32 lane vector: a one-hot contraction over Kv,
    exact because every product is a bf16 scale times 1 or 0."""
    s = s_ref[0].astype(jnp.float32)                  # [page_size, Kv]
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (1, s.shape[1]), 1)
              == kv).astype(jnp.float32)
    return jax.lax.dot_general(onehot, s, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _ragged_kernel(page_tables_ref, contexts_ref, starts_ref,   # prefetch
                   q_ref, k_ref, v_ref, *rest,    # blocks (+scales), out
                   scale: float, page_size: int, n_group: int,
                   quantized: bool = False):
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    b = pl.program_id(0)
    kv = pl.program_id(1)
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    ctx = contexts_ref[b]              # keys at t >= ctx are invalid
    start = starts_ref[b]              # global position of row b's token 0
    page_start = pi * page_size

    @pl.when(page_start < ctx)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)       # [C*G, D]
        k = k_ref[0, 0].astype(jnp.float32)       # [page_size, D]
        v = v_ref[0, 0].astype(jnp.float32)       # [page_size, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [C*G, page]
        if quantized:
            # fused dequant: q . (k * ks[t]) == (q . k) * ks[t]
            s = s * _head_row(ks_ref, kv)
        # per-row causal mask against THIS sequence's cursor: query row
        # r (chunk token r // G) sits at global position start + r//G
        qpos = start + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0) // n_group
        tpos = page_start + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where((tpos < ctx) & (tpos <= qpos), s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, -1, keepdims=True)
        if quantized:
            # sum_t p[t] * (v[t] * vs[t]) == (p * vs) . v
            p = p * _head_row(vs_ref, kv)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(pi == pl.num_programs(2) - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_ragged_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, page_tables: jax.Array,
                           contexts: jax.Array, starts: jax.Array, *,
                           k_scales: Optional[jax.Array] = None,
                           v_scales: Optional[jax.Array] = None,
                           scale: Optional[float] = None,
                           interpret: Optional[bool] = None) -> jax.Array:
    """Ragged multi-sequence paged attention: one kernel invocation for a
    whole engine step's mixed decode + prefill-chunk batch.

    Row ``b`` of ``q`` ([B, C, H, D]) holds up to C consecutive query
    tokens of one sequence, starting at that sequence's global position
    ``starts[b]``; a decode token is simply a length-1 row.  Each row
    attends only to its own scalar-prefetched ``page_tables[b]`` with
    keys masked to ``t < contexts[b]`` and the per-row causal constraint
    ``t <= starts[b] + c``.  Returns [B, C, H, D].

    Padding contract: chunk pad rows (``starts[b] + c >= contexts[b]``)
    produce garbage output the caller must ignore; fully padded batch
    rows signal themselves with ``contexts[b] == 0`` and output zeros.
    The caller must have scattered all B rows' K/V (pads into a trash
    page outside every page table) before invoking.

    With ``k_scales``/``v_scales`` ([P, page_size, Kv]) the pools hold
    quantized (int8) values, dequantized inside the page loop.
    ``interpret`` defaults to native lowering on TPU and to the Pallas
    interpreter elsewhere.
    """
    B, C, H, Dq = q.shape
    _, Kv, page_size, D = k_pages.shape
    pages_per_seq = page_tables.shape[1]
    G = H // Kv
    scale = Dq ** -0.5 if scale is None else scale
    quantized = k_scales is not None
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    # zero lanes up to the pools' padded head dim leave q . k unchanged
    q = jnp.pad(q, ((0, 0),) * 3 + ((0, D - Dq),))
    # row r = c*G + g of a (b, kv) tile is chunk token c, group head g
    qg = (q.reshape(B, C, Kv, G, D).transpose(0, 2, 1, 3, 4)
          .reshape(B, Kv, C * G, D))

    grid = (B, Kv, pages_per_seq)

    def q_map(b, kv, pi, pt, ctx, st):
        return (b, kv, 0, 0)

    def kv_map(b, kv, pi, pt, ctx, st):
        # scalar-prefetched page-table ROW b routes the DMA to the
        # physical page backing this sequence's pi-th logical page
        return (pt[b, pi], kv, 0, 0)

    def scales_map(b, kv, pi, pt, ctx, st):
        return (pt[b, pi], 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, C * G, D), q_map),
        pl.BlockSpec((1, 1, page_size, D), kv_map),
        pl.BlockSpec((1, 1, page_size, D), kv_map),
    ]
    operands = [page_tables, contexts, starts, qg, k_pages, v_pages]
    if quantized:
        in_specs += [pl.BlockSpec((1, page_size, Kv), scales_map)] * 2
        operands += [k_scales, v_scales]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, C * G, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((C * G, 1), jnp.float32),
            pltpu.VMEM((C * G, 1), jnp.float32),
            pltpu.VMEM((C * G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_ragged_kernel, scale=scale,
                          page_size=page_size, n_group=G,
                          quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Kv, C * G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(*operands)
    return (out.reshape(B, Kv, C, G, D).transpose(0, 2, 1, 3, 4)
            .reshape(B, C, H, D)[..., :Dq])


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    page_table: jax.Array, context_lens: jax.Array, *,
                    k_scales: Optional[jax.Array] = None,
                    v_scales: Optional[jax.Array] = None,
                    scale: Optional[float] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Decode: q [B, H, D], one new token per sequence at position
    ``context_lens[b] - 1`` (the lens include it).  Returns [B, H, D]."""
    lens = jnp.asarray(context_lens, jnp.int32)
    out = paged_ragged_attention(
        q[:, None], k_pages, v_pages, page_table, lens, lens - 1,
        k_scales=k_scales, v_scales=v_scales, scale=scale,
        interpret=interpret)
    return out[:, 0]


def paged_prefill_attention(q: jax.Array, k_pages: jax.Array,
                            v_pages: jax.Array, page_table: jax.Array,
                            context: jax.Array, start: jax.Array, *,
                            k_scales: Optional[jax.Array] = None,
                            v_scales: Optional[jax.Array] = None,
                            scale: Optional[float] = None,
                            interpret: Optional[bool] = None) -> jax.Array:
    """Chunked prefill: C query tokens of one sequence attend to its page
    table with causal masking inside the chunk.  Returns [C, H, D].

    ``context`` counts the valid tokens in the pages (including this
    chunk's valid tokens — the caller scatters the chunk's K/V before
    calling); ``start`` is the global position of query row 0.  Rows of
    a padded final chunk (positions >= context) produce garbage output.
    """
    out = paged_ragged_attention(
        q[None], k_pages, v_pages, page_table[None],
        jnp.asarray(context, jnp.int32).reshape(1),
        jnp.asarray(start, jnp.int32).reshape(1),
        k_scales=k_scales, v_scales=v_scales, scale=scale,
        interpret=interpret)
    return out[0]
