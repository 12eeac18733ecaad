"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.quant.int4 import quantize_array


def _rand(key, shape, dtype, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


@pytest.mark.parametrize("B,S,H,Kv,D", [
    (2, 256, 4, 2, 64),
    (1, 128, 8, 8, 128),
    (2, 512, 4, 1, 64),
    (1, 256, 6, 2, 128),
])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_attention_sweep(B, S, H, Kv, D, dtype, rng_key):
    ks = jax.random.split(rng_key, 3)
    q = _rand(ks[0], (B, S, H, D), dtype)
    k = _rand(ks[1], (B, S, Kv, D), dtype)
    v = _rand(ks[2], (B, S, Kv, D), dtype)
    out = ops.flash_attention(q, k, v, interpret=True)
    expect = ref.flash_attention_ref(q, k, v)
    tol = 0.06 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=tol)


@pytest.mark.parametrize("window", [64, 128])
def test_flash_attention_sliding(window, rng_key):
    ks = jax.random.split(rng_key, 3)
    B, S, H, Kv, D = 1, 512, 4, 2, 64
    q = _rand(ks[0], (B, S, H, D), jnp.bfloat16)
    k = _rand(ks[1], (B, S, Kv, D), jnp.bfloat16)
    v = _rand(ks[2], (B, S, Kv, D), jnp.bfloat16)
    out = ops.flash_attention(q, k, v, window=window, interpret=True)
    expect = ref.flash_attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=0.06)


@pytest.mark.parametrize("B,H,Kv,D,pages,psz,pps", [
    (2, 8, 2, 64, 16, 16, 4),
    (3, 4, 4, 128, 32, 8, 6),
    (1, 16, 2, 64, 64, 32, 8),
    (4, 2, 1, 128, 8, 16, 2),
])
def test_paged_attention_sweep(B, H, Kv, D, pages, psz, pps, rng_key):
    ks = jax.random.split(rng_key, 5)
    q = _rand(ks[0], (B, H, D), jnp.bfloat16)
    kp = _rand(ks[1], (pages, Kv, psz, D), jnp.bfloat16)
    vp = _rand(ks[2], (pages, Kv, psz, D), jnp.bfloat16)
    pt = jax.random.randint(ks[3], (B, pps), 0, pages)
    lens = jax.random.randint(ks[4], (B,), 1, pps * psz + 1)
    out = ops.paged_attention(q, kp, vp, pt, lens, interpret=True)
    expect = ref.paged_attention_ref(q, kp, vp, pt, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=0.06)


@pytest.mark.parametrize("C,H,Kv,D,pages,psz,pps", [
    (8, 8, 2, 64, 16, 16, 4),
    (16, 4, 4, 128, 32, 8, 6),
    (4, 2, 1, 64, 8, 16, 2),
])
@pytest.mark.parametrize("start_frac", [0.0, 0.5])
def test_paged_prefill_attention_sweep(C, H, Kv, D, pages, psz, pps,
                                       start_frac, rng_key):
    """Chunked prefill kernel vs oracle, incl. mid-sequence chunks and a
    padded final chunk (only the valid rows are compared)."""
    ks = jax.random.split(rng_key, 4)
    q = _rand(ks[0], (C, H, D), jnp.bfloat16)
    kp = _rand(ks[1], (pages, Kv, psz, D), jnp.bfloat16)
    vp = _rand(ks[2], (pages, Kv, psz, D), jnp.bfloat16)
    pt = jax.random.randint(ks[3], (pps,), 0, pages)
    start = int(start_frac * (pps * psz - C))
    for valid in (C, max(1, C // 2)):      # full chunk + padded chunk
        ctx = start + valid
        out = ops.paged_prefill_attention(q, kp, vp, pt, ctx, start,
                                          interpret=True)
        expect = ref.paged_prefill_attention_ref(q, kp, vp, pt, ctx, start)
        np.testing.assert_allclose(
            np.asarray(out, np.float32)[:valid],
            np.asarray(expect, np.float32)[:valid], atol=0.06)


def _ragged_pools(key, pages, Kv, psz, D):
    """Head-major pools; an unaligned head dim (phi-3.5's 96) rides in
    zero-padded 128-lane pools, as the runner's do."""
    Dp = -(-D // 128) * 128 if D % 64 else D
    kk, kv = jax.random.split(key)
    pad = ((0, 0),) * 3 + ((0, Dp - D),)
    return (jnp.pad(_rand(kk, (pages, Kv, psz, D), jnp.bfloat16), pad),
            jnp.pad(_rand(kv, (pages, Kv, psz, D), jnp.bfloat16), pad))


def _ragged_rows(key, B, C, psz, pps):
    """Row kinds cycle decode, full chunk, padded partial chunk, batch
    pad (context 0); the first decode row fills its whole page table
    and the first full chunk ends mid-page.  Returns (lengths, contexts,
    starts)."""
    lengths = [(1, C, max(1, C // 2), 0)[b % 4] for b in range(B)]
    starts = np.array(jax.random.randint(
        key, (B,), 0, pps * psz - C + 1), np.int32)
    starts[np.asarray(lengths) == 0] = 0
    starts[0] = pps * psz - 1
    if B > 1:
        starts[1] = pps * psz - C - psz // 2
    contexts = (starts + np.asarray(lengths)).astype(np.int32)
    return lengths, jnp.asarray(contexts), jnp.asarray(starts)


@pytest.mark.parametrize("B,C,H,Kv,D,pages,psz,pps", [
    (4, 8, 8, 2, 64, 16, 16, 4),
    (2, 16, 4, 4, 128, 32, 8, 6),
    (8, 4, 2, 1, 64, 16, 16, 2),
    (8, 16, 32, 32, 96, 80, 16, 8),       # phi-3.5 width: MHA, D 96
    (4, 256, 32, 8, 64, 48, 16, 18),      # GQA tile of 1024 rows: hb < Kv
])
def test_paged_ragged_attention_sweep(B, C, H, Kv, D, pages, psz, pps,
                                      rng_key):
    """Fused ragged kernel vs BOTH oracles: every row must equal the
    single-sequence chunk oracle over its own page table — for a mixed
    batch of decode rows (length 1), full chunks, padded partial chunks,
    and one fully padded batch row (context 0 -> zeros)."""
    ks = jax.random.split(rng_key, 4)
    q = _rand(ks[0], (B, C, H, D), jnp.bfloat16)
    kp, vp = _ragged_pools(ks[1], pages, Kv, psz, D)
    pt = jax.random.randint(ks[2], (B, pps), 0, pages)
    lengths, contexts, starts = _ragged_rows(ks[3], B, C, psz, pps)
    out = ops.paged_ragged_attention(q, kp, vp, pt, contexts, starts,
                                     interpret=True)
    batched = ref.paged_ragged_attention_ref(q, kp, vp, pt, contexts,
                                             starts)
    for b, L in enumerate(lengths):
        got = np.asarray(out[b], np.float32)
        if L == 0:
            np.testing.assert_allclose(got, 0.0)       # batch pad row
            continue
        perseq = ref.paged_prefill_attention_ref(
            q[b], kp, vp, pt[b], int(contexts[b]), int(starts[b]))
        np.testing.assert_allclose(
            got[:L], np.asarray(perseq, np.float32)[:L], atol=0.06)
        np.testing.assert_allclose(
            got[:L], np.asarray(batched[b], np.float32)[:L], atol=0.06)


# ---------------------------------------------------------------------------
# quantized KV pages: kernels with dequant FUSED into the page loop vs
# (a) the quantized oracle (same math, tight tolerance) and (b) the
# unquantized oracle on the original pools (bounded quantization noise).
# ---------------------------------------------------------------------------

def _quant_pools(kp, vp):
    """Per-(token, kv-head) symmetric int8, exactly the runner's scheme:
    head-major int8 pools, token-major [P, page_size, Kv] scales."""
    from repro.core.paged_runner import PagedModelRunner
    kq, ks = PagedModelRunner._page_quant(kp)
    vq, vs = PagedModelRunner._page_quant(vp)
    return kq, ks.swapaxes(1, 2), vq, vs.swapaxes(1, 2)


@pytest.mark.parametrize("B,H,Kv,D,pages,psz,pps", [
    (2, 8, 2, 64, 16, 16, 4),
    (3, 4, 4, 128, 32, 8, 6),
])
def test_paged_attention_quantized(B, H, Kv, D, pages, psz, pps, rng_key):
    ks_ = jax.random.split(rng_key, 5)
    q = _rand(ks_[0], (B, H, D), jnp.bfloat16)
    kp = _rand(ks_[1], (pages, Kv, psz, D), jnp.bfloat16)
    vp = _rand(ks_[2], (pages, Kv, psz, D), jnp.bfloat16)
    pt = jax.random.randint(ks_[3], (B, pps), 0, pages)
    lens = jax.random.randint(ks_[4], (B,), 1, pps * psz + 1)
    kq, kscale, vq, vscale = _quant_pools(kp, vp)
    out = ops.paged_attention(q, kq, vq, pt, lens, k_scales=kscale,
                              v_scales=vscale, interpret=True)
    oracle = ref.paged_attention_ref(q, kq, vq, pt, lens, k_scales=kscale,
                                     v_scales=vscale)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(oracle, np.float32), atol=0.06)
    dense = ref.paged_attention_ref(q, kp, vp, pt, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(dense, np.float32), atol=0.12)


@pytest.mark.parametrize("C,H,Kv,D,pages,psz,pps", [
    (8, 8, 2, 64, 16, 16, 4),
    (16, 4, 4, 128, 32, 8, 6),
])
def test_paged_prefill_attention_quantized(C, H, Kv, D, pages, psz, pps,
                                           rng_key):
    ks_ = jax.random.split(rng_key, 4)
    q = _rand(ks_[0], (C, H, D), jnp.bfloat16)
    kp = _rand(ks_[1], (pages, Kv, psz, D), jnp.bfloat16)
    vp = _rand(ks_[2], (pages, Kv, psz, D), jnp.bfloat16)
    pt = jax.random.randint(ks_[3], (pps,), 0, pages)
    start = (pps * psz - C) // 2
    ctx = start + C
    kq, kscale, vq, vscale = _quant_pools(kp, vp)
    out = ops.paged_prefill_attention(q, kq, vq, pt, ctx, start,
                                      k_scales=kscale, v_scales=vscale,
                                      interpret=True)
    oracle = ref.paged_prefill_attention_ref(q, kq, vq, pt, ctx, start,
                                             k_scales=kscale,
                                             v_scales=vscale)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(oracle, np.float32), atol=0.06)
    dense = ref.paged_prefill_attention_ref(q, kp, vp, pt, ctx, start)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(dense, np.float32), atol=0.12)


@pytest.mark.parametrize("B,C,H,Kv,D,pages,psz,pps", [
    (4, 8, 8, 2, 64, 16, 16, 4),
    (2, 16, 4, 4, 128, 32, 8, 6),
    (8, 16, 32, 32, 96, 80, 16, 8),       # phi-3.5 width: MHA, D 96
    (4, 256, 32, 8, 64, 48, 16, 18),      # GQA tile of 1024 rows: hb < Kv
])
def test_paged_ragged_attention_quantized(B, C, H, Kv, D, pages, psz, pps,
                                          rng_key):
    """The serving kernel: mixed decode/chunk/pad rows over int8 pools,
    scale-multiply inside the page loop (no materialized f32 copy)."""
    ks_ = jax.random.split(rng_key, 4)
    q = _rand(ks_[0], (B, C, H, D), jnp.bfloat16)
    kp, vp = _ragged_pools(ks_[1], pages, Kv, psz, D)
    pt = jax.random.randint(ks_[2], (B, pps), 0, pages)
    lengths, contexts, starts = _ragged_rows(ks_[3], B, C, psz, pps)
    kq, kscale, vq, vscale = _quant_pools(kp, vp)
    out = ops.paged_ragged_attention(
        q, kq, vq, pt, contexts, starts, k_scales=kscale, v_scales=vscale,
        interpret=True)
    oracle = ref.paged_ragged_attention_ref(
        q, kq, vq, pt, contexts, starts, k_scales=kscale, v_scales=vscale)
    dense = ref.paged_ragged_attention_ref(q, kp, vp, pt, contexts, starts)
    for b, L in enumerate(lengths):
        got = np.asarray(out[b], np.float32)
        if L == 0:
            np.testing.assert_allclose(got, 0.0)       # batch pad row
            continue
        np.testing.assert_allclose(
            got[:L], np.asarray(oracle[b], np.float32)[:L], atol=0.06)
        np.testing.assert_allclose(
            got[:L], np.asarray(dense[b], np.float32)[:L], atol=0.12)


def test_paged_attention_single_token_context(rng_key):
    ks = jax.random.split(rng_key, 3)
    q = _rand(ks[0], (1, 4, 64), jnp.bfloat16)
    kp = _rand(ks[1], (4, 2, 8, 64), jnp.bfloat16)
    vp = _rand(ks[2], (4, 2, 8, 64), jnp.bfloat16)
    pt = jnp.zeros((1, 2), jnp.int32)
    lens = jnp.ones((1,), jnp.int32)
    out = ops.paged_attention(q, kp, vp, pt, lens, interpret=True)
    expect = ref.paged_attention_ref(q, kp, vp, pt, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=0.06)


@pytest.mark.parametrize("M,K,N,G", [
    (128, 256, 128, 64),
    (256, 512, 256, 64),
    (128, 128, 384, 32),
    (64, 1024, 128, 128),
    (5, 512, 256, 64),            # decode-sized M: rows padded in-kernel
])
def test_w4a16_gemm_sweep(M, K, N, G, rng_key):
    ks = jax.random.split(rng_key, 2)
    x = _rand(ks[0], (M, K), jnp.bfloat16, 0.1)
    w = _rand(ks[1], (K, N), jnp.bfloat16, 0.05)
    qt = quantize_array(w, G)
    out = ops.w4a16_gemm(x, qt.data, qt.scales, group=G, interpret=True)
    expect = ref.w4a16_gemm_ref(x, qt.data, qt.scales, G)
    scale = float(jnp.max(jnp.abs(expect.astype(jnp.float32)))) + 1e-6
    np.testing.assert_allclose(np.asarray(out, np.float32) / scale,
                               np.asarray(expect, np.float32) / scale,
                               atol=0.02)


def test_w4a16_matches_dequant_matmul(rng_key):
    """Kernel == dequantize-then-matmul (the model's XLA fallback path)."""
    ks = jax.random.split(rng_key, 2)
    x = _rand(ks[0], (128, 256), jnp.bfloat16, 0.1)
    w = _rand(ks[1], (256, 128), jnp.bfloat16, 0.05)
    qt = quantize_array(w, 64)
    a = ops.w4a16_gemm(x, qt.data, qt.scales, group=64, interpret=True)
    b = x @ qt.dequant()
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=0.05)


@pytest.mark.parametrize("shape", [(4, 64, 512), (2, 128, 256), (1, 8, 896)])
@pytest.mark.parametrize("with_residual", [False, True])
def test_rmsnorm_sweep(shape, with_residual, rng_key):
    ks = jax.random.split(rng_key, 3)
    x = _rand(ks[0], shape, jnp.bfloat16)
    s = _rand(ks[1], shape[-1:], jnp.float32) + 1.0
    r = _rand(ks[2], shape, jnp.bfloat16) if with_residual else None
    out = ops.rmsnorm(x, s, residual=r, interpret=True)
    expect = ref.rmsnorm_ref(x, s, residual=r)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=0.05, rtol=0.02)   # bf16 output ulp


def test_flash_attention_used_like_model(rng_key):
    """Kernel output matches the model's attention math (GQA reshape)."""
    from repro.configs import get_config
    cfg = get_config("yi-6b", reduced=True)
    B, S = 1, 128
    ks = jax.random.split(rng_key, 3)
    q = _rand(ks[0], (B, S, cfg.n_heads, cfg.head_dim), jnp.bfloat16)
    k = _rand(ks[1], (B, S, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16)
    v = _rand(ks[2], (B, S, cfg.n_kv_heads, cfg.head_dim), jnp.bfloat16)
    out = ops.flash_attention(q, k, v, interpret=True)
    expect = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=0.06)
