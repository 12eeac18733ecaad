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

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels.paged_attention import (head_block, paged_attention,
                                           paged_prefill_attention,
                                           paged_ragged_attention)
from repro.kernels.sampling import batched_sample
from repro.kernels.w4a16_gemm import w4a16_gemm

CFG = get_config("phi-3.5-mini")
KV, H, D = CFG.n_kv_heads, CFG.n_heads, CFG.head_dim
PAGE, PAGES, PPS = 16, 385, 64          # 4 slots x 1024 tokens + headroom
DP = 128                                # pools pad head_dim to 128 lanes
CELL_PAGES, CELL_PPS = 769, 128         # the benchmark cells' pools


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


def _pools(sh, dtype, pages=PAGES):
    pool = _spec(sh, (pages, KV, PAGE, DP), dtype)
    if dtype != jnp.int8:
        return pool, pool, None
    return pool, pool, _spec(sh, (pages, PAGE, KV), jnp.bfloat16)


@pytest.mark.parametrize("B,C,dtype,pages,pps", [
    (4, 1, jnp.bfloat16, PAGES, PPS),       # pure decode step
    (4, 16, jnp.bfloat16, PAGES, PPS),      # mixed decode + chunk step
    (4, 16, jnp.int8, PAGES, PPS),          # quantized pool, fused dequant
    # the cells' widest chunk buckets: the tallest query tiles, where
    # the head block must shrink to fit VMEM
    (8, 256, jnp.bfloat16, CELL_PAGES, CELL_PPS),
    (16, 256, jnp.bfloat16, CELL_PAGES, CELL_PPS),
    (8, 256, jnp.int8, CELL_PAGES, CELL_PPS),
    (16, 256, jnp.int8, CELL_PAGES, CELL_PPS),
], ids=["ragged-bf16-decode", "ragged-bf16-mixed", "ragged-int8",
        "ragged-bf16-8x256", "ragged-bf16-16x256", "ragged-int8-8x256",
        "ragged-int8-16x256"])
def test_paged_ragged_attention_lowers(one_chip, B, C, dtype, pages, pps):
    k, v, s = _pools(one_chip, dtype, pages)
    i32 = jnp.int32

    def step(q, k, v, pt, ctx, st, s):
        return paged_ragged_attention(q, k, v, pt, ctx, st, k_scales=s,
                                      v_scales=s, interpret=False)

    _compile(step, _spec(one_chip, (B, C, H, D), jnp.bfloat16), k, v,
             _spec(one_chip, (B, pps), i32), _spec(one_chip, (B,), i32),
             _spec(one_chip, (B,), i32), s)


def test_head_block_fits_the_tile():
    """Every kv head of a page in one grid step for a decode tile; a
    proper divisor of Kv for the tallest chunk tile; a GQA tile of 256
    tokens x 4 query heads (the kernel sweep's GQA case) splits its 8
    kv heads."""
    bf16 = jnp.bfloat16
    assert head_block(1, PAGE, DP, KV, bf16, bf16) == KV
    assert head_block(1, PAGE, DP, KV, bf16, jnp.int8) == KV
    for kv_dtype in (bf16, jnp.int8):
        hb = head_block(256, PAGE, DP, KV, bf16, kv_dtype)
        assert KV % hb == 0 and 1 <= hb < KV
    hb = head_block(256 * 4, PAGE, DP, 8, bf16, bf16)
    assert 8 % hb == 0 and hb < 8


def test_paged_ragged_attention_keeps_its_name(one_chip):
    """The kernel's operation is named ``paged_ragged_attention`` in the
    compiled program whatever jitted function calls it: a profiler trace
    names device ops after it, and the benchmark finds the kernel's
    device time by that name."""
    k, v, _ = _pools(one_chip, jnp.bfloat16)
    i32 = jnp.int32

    def serving_step(q, k, v, pt, ctx, st):
        return 2 * paged_ragged_attention(q, k, v, pt, ctx, st,
                                          interpret=False)

    text = _compile(serving_step, _spec(one_chip, (4, 1, H, D), jnp.bfloat16),
                    k, v, _spec(one_chip, (4, PPS), i32),
                    _spec(one_chip, (4,), i32),
                    _spec(one_chip, (4,), i32)).as_text()
    ops = [line.split(" = ")[0].strip().lstrip("%")
           for line in text.splitlines()
           if "tpu_custom_call" in line and " = " in line]
    assert ops and all(op.rsplit(".", 1)[0] == "paged_ragged_attention"
                       for op in ops), ops


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


def _hlo_outside_conds(text: str) -> list:
    """Instruction lines of an HLO module that run on every call: those
    of computations reachable from the entry without entering a
    ``conditional``'s branches."""
    comps, cur, entry = {}, None, None
    for line in text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            words = line.split()
            cur = words[1 if words[0] == "ENTRY" else 0].lstrip("%")
            entry = cur if words[0] == "ENTRY" else entry
            comps[cur] = []
        elif cur and line.startswith("  "):
            comps[cur].append(line)
    callee = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
    seen, todo, out = set(), [entry], []
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        out += comps[name]
        for line in comps[name]:
            todo += callee.findall(line)
    return out


def _out_elems(line: str, op: str) -> int:
    """Elements of the largest array an HLO instruction outputs."""
    shape = line.split(" = ", 1)[1].split(f" {op}(", 1)[0]
    return max((math.prod(int(d) for d in dims.split(",") if d)
                for dims in re.findall(r"\[([\d,]*)\]", shape)), default=0)


def test_sampler_sorts_once_and_gathers_no_vocab_plane(one_chip):
    """The stochastic sampler at the benchmark cells' shape (16 rows of
    phi-3.5-mini's 32064-token vocabulary, no planes, no logprobs) holds
    at most one full-vocabulary sort outside its runtime-conditional
    stages, and no gather or scatter whose output is a whole ``[S, V]``
    plane anywhere."""
    S, V = 16, CFG.vocab_size
    f32, i32, u32 = jnp.float32, jnp.int32, jnp.uint32
    row = lambda dt: _spec(one_chip, (S,), dt)  # noqa: E731
    args = [_spec(one_chip, (S, V), jnp.bfloat16), row(u32), row(i32),
            row(f32), row(i32), row(f32), row(f32), row(f32), row(f32),
            row(f32), row(f32), _spec(one_chip, (S, 1), f32),
            _spec(one_chip, (S, 1), f32),
            _spec(one_chip, (S, -(-V // 32)), u32)]
    text = jax.jit(lambda *a: batched_sample(
        *a, n_top=0, use_planes=False, all_greedy=False,
        need_logprobs=False)).lower(*args).compile().as_text()
    ins = [line for line in text.splitlines() if " = " in line]
    sorts = [line for line in ins if " sort(" in line
             and _out_elems(line, "sort") >= S * V]
    assert sorts, "no full-vocabulary sort found: the HLO parse is stale"
    always = [line for line in _hlo_outside_conds(text) if " sort(" in line
              and _out_elems(line, "sort") >= S * V]
    assert len(always) <= 1, always
    planes = [line.strip()[:160] for line in ins for op in ("gather",
                                                             "scatter")
              if f" {op}(" in line and _out_elems(line, op) >= S * V]
    assert not planes, planes
