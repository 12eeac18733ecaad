"""Causal flash attention (prefill) — Pallas TPU kernel.

TPU adaptation of the WebGPU FlashAttention kernels WebLLM compiles via
MLC/TVM: HBM->VMEM pipelining is expressed with ``BlockSpec`` index maps,
tiles are MXU-aligned (128-multiples), and the online-softmax running
state (m, l, acc) lives in VMEM scratch across the (sequential) kv-block
grid dimension.

Grid: (B * Kv * G, Sq / block_q, Sk / block_k)  — last dim "arbitrary"
(sequential) so scratch carries across kv blocks.  Supports GQA (the
q head index maps onto its kv head) and sliding windows (block skipping
via masking; fully-masked blocks are cheap early-outs).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, block_q: int, block_k: int, seq_len: int,
            causal: bool, window: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    run = True
    if causal:
        # skip blocks strictly above the diagonal / beyond the window
        run = k_start <= q_start + block_q - 1
        if window:
            run = jnp.logical_and(
                run, k_start + block_k - 1 > q_start - window)

    @pl.when(run if causal else True)
    def _body():
        q = q_ref[0].astype(jnp.float32)            # [bq, d]
        k = k_ref[0].astype(jnp.float32)            # [bk, d]
        v = v_ref[0].astype(jnp.float32)            # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = rows >= cols
            if window:
                mask &= (rows - cols) < window
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                          # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                       # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)              # [bq, 1]
        l_new = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new
        acc_scr[...] = acc

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """q: [B,S,H,D]; k,v: [B,S,Kv,D] -> [B,S,H,D]."""
    B, S, H, D = q.shape
    Kv = k.shape[2]
    G = H // Kv
    scale = D ** -0.5 if scale is None else scale
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    # [B,S,H,D] -> [B*H, S, D] with h = kv*G + g
    qt = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kt = k.transpose(0, 2, 1, 3).reshape(B * Kv, S, D)
    vt = v.transpose(0, 2, 1, 3).reshape(B * Kv, S, D)

    grid = (B * H, S // block_q, S // block_k)

    def q_map(h, qi, ki):
        return (h, qi, 0)

    def kv_map(h, qi, ki):
        return (h // G, ki, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, seq_len=S, causal=causal,
                          window=window),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), q_map),
            pl.BlockSpec((1, block_k, D), kv_map),
            pl.BlockSpec((1, block_k, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), q_map),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),     # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),     # running sum l
            pltpu.VMEM((block_q, D), jnp.float32),     # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)
