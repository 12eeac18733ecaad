"""Per-kernel microbenchmarks (one per WebLLM WebGPU kernel class).

On this CPU host the Pallas kernels execute in interpret mode, so the
timings benchmark the *oracle-equivalent jnp path* (what XLA:CPU runs)
and verify the harness; on a TPU host the same calls time the compiled
kernels.  Derived column reports achieved GFLOP/s or GB/s.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sampler import RequestSampler
from repro.kernels import ref
from repro.kernels.ops import batched_sample
from repro.quant.int4 import quantize_array


def _time(fn, *args, iters=5) -> float:
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6      # us


def run(smoke: bool = False) -> list:
    key = jax.random.PRNGKey(0)
    rows = []
    iters = 2 if smoke else 5

    # flash attention (prefill class)
    B, S, H, Kv, D = (1, 128, 4, 2, 32) if smoke else (1, 1024, 8, 2, 64)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, Kv, D), jnp.float32).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, Kv, D), jnp.float32).astype(jnp.bfloat16)
    f = jax.jit(lambda q, k, v: ref.flash_attention_ref(q, k, v))
    us = _time(f, q, k, v, iters=iters)
    flops = 2 * 2 * B * H * S * S / 2 * D
    rows.append((f"kernel/flash_attention_{S}", us,
                 f"{flops/us/1e3:.1f}GFLOP/s(xla-cpu)"))

    # paged attention (decode class)
    P_, psz, pps = (32, 8, 8) if smoke else (128, 16, 16)
    q2 = jax.random.normal(ks[0], (8, H, D), jnp.float32).astype(jnp.bfloat16)
    kp = jax.random.normal(ks[1], (P_, Kv, psz, D), jnp.float32).astype(jnp.bfloat16)
    vp = jax.random.normal(ks[2], (P_, Kv, psz, D), jnp.float32).astype(jnp.bfloat16)
    pt = jax.random.randint(key, (8, pps), 0, P_)
    lens = jnp.full((8,), pps * psz, jnp.int32)
    f2 = jax.jit(lambda *a: ref.paged_attention_ref(*a))
    us = _time(f2, q2, kp, vp, pt, lens, iters=iters)
    byts = 2 * 8 * pps * psz * Kv * D * 2
    rows.append((f"kernel/paged_attention_{pps*psz}ctx", us,
                 f"{byts/us/1e3:.2f}GB/s(xla-cpu)"))

    # chunked paged prefill (multi-token prefill class)
    C = 8 if smoke else 16
    qc = jax.random.normal(ks[0], (C, H, D), jnp.float32).astype(jnp.bfloat16)
    pt1 = jax.random.randint(key, (pps,), 0, P_)
    f2b = jax.jit(lambda *a: ref.paged_prefill_attention_ref(
        *a, pps * psz, pps * psz - C))
    us = _time(f2b, qc, kp, vp, pt1, iters=iters)
    flops = 2 * 2 * C * H * pps * psz * D
    rows.append((f"kernel/paged_prefill_chunk{C}", us,
                 f"{flops/us/1e3:.1f}GFLOP/s(xla-cpu)"))

    # ragged multi-sequence step (fused mixed decode+prefill class):
    # Bq rows — half decode (length 1), half chunks of C — in ONE call
    Bq = 4 if smoke else 8
    qr = jax.random.normal(ks[0], (Bq, C, H, D),
                           jnp.float32).astype(jnp.bfloat16)
    ptB = jax.random.randint(key, (Bq, pps), 0, P_)
    starts = jnp.asarray([(pps * psz - C) if b % 2 else (pps * psz - 1)
                          for b in range(Bq)], jnp.int32)
    ctxs = jnp.asarray([pps * psz] * Bq, jnp.int32)
    f2c = jax.jit(lambda *a: ref.paged_ragged_attention_ref(*a))
    us = _time(f2c, qr, kp, vp, ptB, ctxs, starts, iters=iters)
    flops = 2 * 2 * Bq * C * H * pps * psz * D
    rows.append((f"kernel/paged_ragged_{Bq}x{C}", us,
                 f"{flops/us/1e3:.1f}GFLOP/s(xla-cpu)"))

    # quantized ragged decode (int8 KV pages, dequant fused into the
    # gather).  Long-context decode is where quantized pages pay: the
    # step is KV-bandwidth-bound, and int8 pools halve the bytes pulled
    # per token.  The shape is fixed (not scaled down in smoke) because
    # short contexts are compute-bound and the scale-multiply then LOSES
    # — a smoke-scaled row would report the wrong sign.  Derived column
    # is the speedup vs the bf16-pool baseline at the same shape.
    Bq2, H2, Kv2, D2 = 1, 4, 4, 64
    P2, psz2, pps2 = 1024, 16, 512         # ctx = 8192 tokens
    q8 = jax.random.normal(ks[0], (Bq2, 1, H2, D2),
                           jnp.float32).astype(jnp.bfloat16)
    kb = jax.random.normal(ks[1], (P2, Kv2, psz2, D2),
                           jnp.float32).astype(jnp.bfloat16)
    vb = jax.random.normal(ks[2], (P2, Kv2, psz2, D2),
                           jnp.float32).astype(jnp.bfloat16)
    k8 = jax.random.randint(ks[1], (P2, Kv2, psz2, D2), -127, 128, jnp.int8)
    v8 = jax.random.randint(ks[2], (P2, Kv2, psz2, D2), -127, 128, jnp.int8)
    s8 = (jax.random.uniform(ks[0], (P2, psz2, Kv2)) * 0.02
          ).astype(jnp.bfloat16)
    pt8 = jax.random.randint(key, (Bq2, pps2), 0, P2)
    ctx8 = jnp.full((Bq2,), pps2 * psz2, jnp.int32)
    st8 = jnp.full((Bq2,), pps2 * psz2 - 1, jnp.int32)
    f2d = jax.jit(lambda *a: ref.paged_ragged_attention_ref(*a))
    f2e = jax.jit(lambda q, k, v, ks_, vs_, pt, cx, st:
                  ref.paged_ragged_attention_ref(
                      q, k, v, pt, cx, st, k_scales=ks_, v_scales=vs_))
    # fixed iters + best-of-2 even in smoke: the row gates a speedup
    # RATIO, and 2-iteration timings of a ~7 ms op swing more than the
    # margin under ambient host load
    us_bf16 = min(_time(f2d, q8, kb, vb, pt8, ctx8, st8, iters=8)
                  for _ in range(3))
    us_int8 = min(_time(f2e, q8, k8, v8, s8, s8, pt8, ctx8, st8, iters=8)
                  for _ in range(3))
    rows.append((f"kernel/paged_ragged_int8_{pps2*psz2}ctx", us_int8,
                 f"{us_bf16/us_int8:.2f}x_vs_bf16_pages"))

    # w4a16 gemm (quantized matmul class)
    M, K, N = (32, 256, 256) if smoke else (128, 2048, 2048)
    x = (jax.random.normal(ks[0], (M, K), jnp.float32) * 0.1).astype(jnp.bfloat16)
    w = (jax.random.normal(ks[1], (K, N), jnp.float32) * 0.05).astype(jnp.bfloat16)
    qt = quantize_array(w, 64)
    f3 = jax.jit(lambda x, d, s: ref.w4a16_gemm_ref(x, d, s, 64))
    us = _time(f3, x, qt.data, qt.scales, iters=iters)
    rows.append((f"kernel/w4a16_gemm_{M}x{K}x{N}", us,
                 f"{2*M*K*N/us/1e3:.1f}GFLOP/s(xla-cpu)"))

    # batched on-device sampling (logits→token class): the fused
    # bias/penalties/mask/temp/top-k/top-p/min-p/Gumbel pipeline vs the
    # per-sequence host loop it replaced.  Two fixes make this row's
    # trajectory trustworthy (it used to read 0.7x at smoke scale):
    # * the device op is timed in the ENGINE's static configuration
    #   (plane-less, no logprobs) — the old call paid a dense [S, V]
    #   penalty stage and an [S, V] log-softmax the mixed workload
    #   never executes;
    # * the host loop is timed INCLUDING the [S, V] device→host logits
    #   pull it cannot run without — that transfer (plus the per-token
    #   host sync it forces) is precisely what the fused op eliminates,
    #   so a host loop timed on pre-pulled numpy rows undercounts.
    Sb, Vv = (4, 256) if smoke else (8, 512)
    lg = jax.random.normal(ks[0], (Sb, Vv), jnp.float32) * 3
    seeds = jnp.arange(Sb, dtype=jnp.uint32)
    ctr = jnp.zeros(Sb, jnp.int32)
    temp = jnp.full(Sb, 0.9, jnp.float32)
    topk = jnp.full(Sb, 40, jnp.int32)
    topp = jnp.full(Sb, 0.95, jnp.float32)
    zf = jnp.zeros(Sb, jnp.float32)
    ones = jnp.ones(Sb, jnp.float32)
    bias1 = jnp.zeros((Sb, 1), jnp.float32)      # plane-less placeholders
    cnts1 = jnp.zeros((Sb, 1), jnp.float32)
    maskb = jnp.full((Sb, -(-Vv // 32)), 0xFFFFFFFF, jnp.uint32)
    f5 = (lambda *a: batched_sample(*a, use_planes=False,
                                    need_logprobs=False)[0])
    us = _time(f5, lg, seeds, ctr, temp, topk, topp, zf, ones, zf, zf,
               ones, bias1, cnts1, maskb, iters=iters)
    host = [RequestSampler(temperature=0.9, top_k=40, top_p=0.95, seed=i)
            for i in range(Sb)]
    t0 = time.perf_counter()
    for _ in range(iters):
        lg_np = np.asarray(lg)       # the device→host pull the op avoids
        for i, s in enumerate(host):
            s.sample(lg_np[i])
    host_us = (time.perf_counter() - t0) / iters * 1e6
    rows.append((f"kernel/batched_sample_{Sb}x{Vv}", us,
                 f"{host_us/us:.1f}x_vs_host_loop+transfer"))

    # rmsnorm (fusion class)
    R = (2, 64, 256) if smoke else (8, 512, 1024)
    xn = jax.random.normal(key, R, jnp.float32).astype(jnp.bfloat16)
    s = jnp.ones((R[-1],), jnp.float32)
    f4 = jax.jit(lambda x, s: ref.rmsnorm_ref(x, s))
    us = _time(f4, xn, s, iters=iters)
    rows.append((f"kernel/rmsnorm_{R[0]}x{R[1]}x{R[2]}", us,
                 f"{2*xn.size*2/us/1e3:.2f}GB/s(xla-cpu)"))
    return rows


if __name__ == "__main__":
    for r in run():
        print(",".join(map(str, r)))
