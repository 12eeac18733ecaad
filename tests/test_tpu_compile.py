"""Ahead-of-time compiles of the serving path's Pallas kernels for a
described TPU v5e, at phi-3.5-mini width (Kv = H = 32, head_dim 96).

No chip is needed: the TPU compiler is installed and compiles for a
device that is described, not attached.  Interpret-mode tests cannot see
what these catch — blocks the chip's tiling refuses, too much VMEM.
Each case asserts the kernel really lowered (``tpu_custom_call``).
The topology is described inside a fixture, never at import, so only
the test worker that runs this file loads the TPU library.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels.paged_attention import (paged_attention,
                                           paged_prefill_attention,
                                           paged_ragged_attention)
from repro.kernels.w4a16_gemm import w4a16_gemm

CFG = get_config("phi-3.5-mini")
KV, H, D = CFG.n_kv_heads, CFG.n_heads, CFG.head_dim
PAGE, PAGES, PPS = 16, 385, 64          # 4 slots x 1024 tokens + headroom
DP = 128                                # pools pad head_dim to 128 lanes


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:        # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile would be written to the persistent
    # cache but can never be read back without a chip
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _pools(sh, dtype):
    pool = _spec(sh, (PAGES, KV, PAGE, DP), dtype)
    if dtype != jnp.int8:
        return pool, pool, None
    return pool, pool, _spec(sh, (PAGES, PAGE, KV), jnp.bfloat16)


@pytest.mark.parametrize("B,C,dtype", [
    (4, 1, jnp.bfloat16),         # pure decode step
    (4, 16, jnp.bfloat16),        # mixed decode + prefill-chunk step
    (4, 16, jnp.int8),            # quantized pool, fused dequant
], ids=["ragged-bf16-decode", "ragged-bf16-mixed", "ragged-int8"])
def test_paged_ragged_attention_lowers(one_chip, B, C, dtype):
    k, v, s = _pools(one_chip, dtype)
    i32 = jnp.int32

    def step(q, k, v, pt, ctx, st, s):
        return paged_ragged_attention(q, k, v, pt, ctx, st, k_scales=s,
                                      v_scales=s, interpret=False)

    _compile(step, _spec(one_chip, (B, C, H, D), jnp.bfloat16), k, v,
             _spec(one_chip, (B, PPS), i32), _spec(one_chip, (B,), i32),
             _spec(one_chip, (B,), i32), s)


def test_paged_decode_attention_lowers(one_chip):
    k, v, _ = _pools(one_chip, jnp.bfloat16)
    _compile(lambda q, k, v, pt, n: paged_attention(
        q, k, v, pt, n, interpret=False),
        _spec(one_chip, (4, H, D), jnp.bfloat16), k, v,
        _spec(one_chip, (4, PPS), jnp.int32), _spec(one_chip, (4,),
                                                    jnp.int32))


def test_paged_prefill_attention_lowers(one_chip):
    k, v, _ = _pools(one_chip, jnp.bfloat16)
    _compile(lambda q, k, v, pt, c, s: paged_prefill_attention(
        q, k, v, pt, c, s, interpret=False),
        _spec(one_chip, (16, H, D), jnp.bfloat16), k, v,
        _spec(one_chip, (PPS,), jnp.int32), _spec(one_chip, (), jnp.int32),
        _spec(one_chip, (), jnp.int32))


def test_w4a16_gemm_lowers(one_chip):
    # the MLP down projection: K = d_ff, group 64, a decode-sized M
    M, K, N, G = 8, CFG.d_ff, CFG.d_model, 64
    _compile(lambda x, w, s: w4a16_gemm(x, w, s, group=G, interpret=False),
             _spec(one_chip, (M, K), jnp.bfloat16),
             _spec(one_chip, (K // 2, N), jnp.int8),
             _spec(one_chip, (K // G, N), jnp.bfloat16))
