"""Fused int4-dequant GEMM (w4a16) — Pallas TPU kernel.

WebLLM serves q4f16 models: weights live packed (two int4 nibbles per
int8) with bf16 group scales, and the dequant is fused into the GEMM so
the packed form is what crosses HBM.  TPU adaptation: MXU-aligned
(128-multiple) M/N/K tiles, nibble unpack + scale in VREGs right before
the ``dot``, fp32 VMEM accumulator across the sequential K grid dim.

    x        [M, K]   bf16
    w_packed [K/2, N] int8   (low nibble = even k, high = odd k)
    scales   [K/G, N] bf16   (per-(group, column) symmetric scales)
    out      [M, N]   bf16

Grid: (M/bm, N/bn, K/bk) — K innermost/sequential.  ``bk`` is a multiple
of the quant group size so each K-tile sees whole groups.  On TPU every
2-D quantized matmul of the serving path runs here (``int4.qdot``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(xe_ref, xo_ref, w_ref, s_ref, o_ref, acc_scr, *,
            half_group: int, dot_dtype):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # unpack in int32: Mosaic has no int8 shifts.  Row i of the packed
    # tile holds k = 2i (low nibble) and k = 2i + 1 (high nibble); the
    # wrapper split x into its even and odd columns to match, so no
    # sublane interleave is needed
    packed = w_ref[...].astype(jnp.int32)             # [bk/2, bn]
    low = jnp.right_shift(jnp.left_shift(packed, 28), 28)
    high = jnp.right_shift(packed, 4)
    scales = s_ref[...].astype(jnp.float32)           # [bk/G, bn]

    def dequant(q):                                   # [bk/2, bn]
        rows = q.shape[0]
        w = (q.astype(jnp.float32).reshape(rows // half_group, half_group,
                                           -1) * scales[:, None, :])
        return w.reshape(rows, -1).astype(jnp.bfloat16).astype(dot_dtype)

    def dot(x_ref, w):
        return jax.lax.dot_general(
            x_ref[...].astype(dot_dtype), w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    acc_scr[...] += dot(xe_ref, dequant(low)) + dot(xo_ref, dequant(high))

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finish():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


def w4a16_gemm(x: jax.Array, w_packed: jax.Array, scales: jax.Array, *,
               group: int = 64, block_m: int = 128, block_n: int = 128,
               interpret: Optional[bool] = None) -> jax.Array:
    """``x @ dequant(w_packed, scales)`` for any row count M.

    Rows are padded up to a whole number of ``block_m`` tiles (a
    multiple of 16 when M is small).  ``block_k`` is ``max(8 * group,
    256)`` when that divides K, else all of K, so the scale block
    ``(block_k // group, block_n)`` keeps its second-minor dim a multiple
    of 8 and the half-K blocks of x stay whole 128-lane tiles — the
    tiling Mosaic lowers.  N must be a multiple of ``block_n`` or at
    most it."""
    M, K = x.shape
    K2, N = w_packed.shape
    assert K == 2 * K2 and K % group == 0 and group % 2 == 0, \
        (K, K2, group)
    block_m = min(block_m, -(-M // 16) * 16)
    block_n = min(block_n, N)
    block_k = max(8 * group, 256)
    if K % block_k:
        block_k = K
    assert N % block_n == 0, (N, block_n)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    Mp = -(-M // block_m) * block_m
    if Mp != M:
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))
    x_even, x_odd = x[:, 0::2], x[:, 1::2]            # [Mp, K/2] each

    grid = (Mp // block_m, N // block_n, K // block_k)
    x_spec = pl.BlockSpec((block_m, block_k // 2),
                          lambda mi, ni, ki: (mi, ki))
    out = pl.pallas_call(
        # XLA:CPU cannot run every bf16 x bf16 -> f32 dot the Pallas
        # interpreter emits; bf16 products are exact in f32, so the
        # interpreter's f32 dot computes the same numbers
        functools.partial(_kernel, half_group=group // 2,
                          dot_dtype=jnp.float32 if interpret
                          else jnp.bfloat16),
        grid=grid,
        in_specs=[
            x_spec, x_spec,
            pl.BlockSpec((block_k // 2, block_n),
                         lambda mi, ni, ki: (ki, ni)),
            pl.BlockSpec((block_k // group, block_n),
                         lambda mi, ni, ki: (ki, ni)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((Mp, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x_even, x_odd, w_packed, scales)
    return out[:M]
