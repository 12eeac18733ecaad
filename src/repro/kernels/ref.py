"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

These are small, obviously-correct implementations used by the kernel
tests (``tests/test_kernels.py`` sweeps shapes/dtypes and asserts
``assert_allclose`` against these).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _gather(pages, table, d):
    """Gather head-major ``pages[table]`` -> [..., pps, Kv, page_size, d]
    in f32, dropping pool lane padding past the head dim ``d``.  Token t
    of the sequence is (page t // page_size, slot t % page_size); the
    einsums keep the two apart, so pages are never transposed into token
    order."""
    return pages[table][..., :d].astype(jnp.float32)


def _token_scales(scales, table, lead: int):
    """Per-(token, kv-head) int8 scales ([P, page_size, Kv]) of the
    gathered pages, laid out to multiply scores/probabilities
    [*lead, Kv, G, pps, page_size] (``lead`` leading batch dims): scaling
    q . k by the key's scale equals dequantizing k first, and scaling
    p by the value's scale equals dequantizing v."""
    s = scales[table].astype(jnp.float32)          # [*lead, pps, ps, Kv]
    n = s.ndim
    s = jnp.moveaxis(s, n - 1, lead)               # [*lead, Kv, pps, ps]
    return jnp.expand_dims(s, lead + 1)            # [*lead, Kv, 1, pps, ps]


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True,
                        window: int = 0,
                        scale: Optional[float] = None) -> jax.Array:
    """q: [B,S,H,D]; k,v: [B,S,Kv,D] (GQA: H = Kv*G).  fp32 softmax."""
    B, S, H, D = q.shape
    Kv = k.shape[2]
    G = H // Kv
    scale = D ** -0.5 if scale is None else scale
    qf = q.reshape(B, S, Kv, G, D).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    scores = jnp.einsum("bskgd,btkd->bkgst", qf, kf) * scale
    if causal:
        i = jnp.arange(S)[:, None]
        j = jnp.arange(S)[None, :]
        mask = i >= j
        if window:
            mask &= (i - j) < window
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", p, v.astype(jnp.float32))
    return out.reshape(B, S, H, D).astype(q.dtype)


def paged_attention_ref(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                        page_table: jax.Array, context_lens: jax.Array,
                        *, k_scales: Optional[jax.Array] = None,
                        v_scales: Optional[jax.Array] = None,
                        scale: Optional[float] = None) -> jax.Array:
    """Decode attention over a paged KV cache.

    q: [B, H, D]; k_pages/v_pages: [P, Kv, page_size, Dp >= D];
    page_table: [B, pages_per_seq] int32; context_lens: [B] int32.
    Optional k_scales/v_scales ([P, page_size, Kv]) dequantize int8 pools.
    """
    B, H, D = q.shape
    P, Kv, page_size, _ = k_pages.shape
    pages_per_seq = page_table.shape[1]
    G = H // Kv
    scale = D ** -0.5 if scale is None else scale

    # each sequence's pages: [B, pages_per_seq, Kv, page_size, D]
    flat = pages_per_seq * page_size
    k = _gather(k_pages, page_table, D)
    v = _gather(v_pages, page_table, D)
    qf = q.reshape(B, Kv, G, D).astype(jnp.float32)
    scores = jnp.einsum("bkgd,bpksd->bkgps", qf, k) * scale
    if k_scales is not None:
        scores = scores * _token_scales(k_scales, page_table, 1)
    scores = scores.reshape(B, Kv, G, flat)
    t = jnp.arange(flat)[None, :]
    valid = t < context_lens[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).reshape(B, Kv, G, pages_per_seq,
                                                page_size)
    if v_scales is not None:
        p = p * _token_scales(v_scales, page_table, 1)
    out = jnp.einsum("bkgps,bpksd->bkgd", p, v)
    return out.reshape(B, H, D).astype(q.dtype)


def paged_prefill_attention_ref(q: jax.Array, k_pages: jax.Array,
                                v_pages: jax.Array, page_table: jax.Array,
                                context, start, *,
                                k_scales: Optional[jax.Array] = None,
                                v_scales: Optional[jax.Array] = None,
                                scale: Optional[float] = None) -> jax.Array:
    """Chunked prefill attention over one sequence's paged KV cache.

    q: [C, H, D] (chunk of queries at positions start..start+C-1);
    k_pages/v_pages: [P, Kv, page_size, Dp >= D];
    page_table: [pages_per_seq].
    Keys at t >= context are masked; query row i sees keys t <= start+i.
    Optional k_scales/v_scales ([P, page_size, Kv]) dequantize int8 pools.
    """
    C, H, D = q.shape
    P, Kv, page_size, _ = k_pages.shape
    pages_per_seq = page_table.shape[0]
    G = H // Kv
    scale = D ** -0.5 if scale is None else scale

    flat = pages_per_seq * page_size
    k = _gather(k_pages, page_table, D)
    v = _gather(v_pages, page_table, D)
    qf = q.reshape(C, Kv, G, D).astype(jnp.float32)
    scores = jnp.einsum("ckgd,pksd->ckgps", qf, k) * scale
    if k_scales is not None:
        scores = scores * _token_scales(k_scales, page_table, 0)
    scores = scores.reshape(C, Kv, G, flat)
    t = jnp.arange(flat)[None, :]
    qpos = start + jnp.arange(C)[:, None]
    mask = (t < context) & (t <= qpos)
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).reshape(C, Kv, G, pages_per_seq,
                                                page_size)
    if v_scales is not None:
        p = p * _token_scales(v_scales, page_table, 0)
    out = jnp.einsum("ckgps,pksd->ckgd", p, v)
    return out.reshape(C, H, D).astype(q.dtype)


def paged_ragged_attention_ref(q: jax.Array, k_pages: jax.Array,
                               v_pages: jax.Array, page_tables: jax.Array,
                               contexts: jax.Array, starts: jax.Array, *,
                               k_scales: Optional[jax.Array] = None,
                               v_scales: Optional[jax.Array] = None,
                               scale: Optional[float] = None) -> jax.Array:
    """Ragged multi-sequence chunk attention (one fused engine step).

    q: [B, C, H, D] — row b is a chunk of up to C consecutive tokens of
    one sequence at positions starts[b]..; a decode token is a length-1
    row.  page_tables: [B, pages_per_seq]; contexts/starts: [B].  Row b
    masks keys to ``t < contexts[b]`` and ``t <= starts[b] + c`` — i.e.
    each row is exactly ``paged_prefill_attention_ref`` over its own
    page-table row (the per-sequence oracle the kernel must match).
    Rows with ``contexts[b] == 0`` (batch padding) return zeros.
    Optional k_scales/v_scales ([P, page_size, Kv]) dequantize int8 pools.
    """
    B, C, H, D = q.shape
    P, Kv, page_size, _ = k_pages.shape
    pages_per_seq = page_tables.shape[1]
    G = H // Kv
    scale = D ** -0.5 if scale is None else scale

    flat = pages_per_seq * page_size
    k = _gather(k_pages, page_tables, D)
    v = _gather(v_pages, page_tables, D)
    qf = q.reshape(B, C, Kv, G, D).astype(jnp.float32)
    scores = jnp.einsum("bckgd,bpksd->bckgps", qf, k) * scale
    if k_scales is not None:
        scores = scores * _token_scales(k_scales, page_tables, 1)[:, None]
    scores = scores.reshape(B, C, Kv, G, flat)
    t = jnp.arange(flat)[None, None, :]
    qpos = starts[:, None] + jnp.arange(C)[None, :]         # [B, C]
    mask = (t < contexts[:, None, None]) & (t <= qpos[..., None])
    scores = jnp.where(mask[:, :, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    # fully masked rows (batch padding, contexts == 0) -> zeros, not the
    # uniform distribution softmax degenerates to
    p = jnp.where(jnp.any(mask, -1)[:, :, None, None, None], p, 0.0)
    p = p.reshape(B, C, Kv, G, pages_per_seq, page_size)
    if v_scales is not None:
        p = p * _token_scales(v_scales, page_tables, 1)[:, None]
    out = jnp.einsum("bckgps,bpksd->bckgd", p, v)
    return out.reshape(B, C, H, D).astype(q.dtype)


def batched_sample_ref(logits, seeds, counters, temperature, top_k,
                       top_p, min_p, typical_p, freq_pen, pres_pen,
                       rep_pen, bias, counts, mask_bits, *,
                       n_top: int = 0):
    """Row-at-a-time oracle for ``kernels.sampling.batched_sample``.

    Mirrors the host ``RequestSampler`` pipeline order (bias →
    frequency/presence/repetition penalties → grammar mask →
    temperature → top-k → top-p/min-p/typical-p) one row at a time with no
    batched tricks, then draws the same counter-based Gumbel noise —
    the batched op must match token-for-token.
    """
    import numpy as np

    from repro.kernels.sampling import ALLOWED_FLOOR, FILTERED, MASKED

    logits = np.asarray(logits, np.float32)
    S, V = logits.shape
    tokens = np.zeros(S, np.int32)
    for s in range(S):
        x = logits[s] + np.asarray(bias[s], np.float32)
        cnt = np.asarray(counts[s], np.float32)
        seen = cnt > 0
        x = x - float(freq_pen[s]) * cnt
        x = np.where(seen, x - float(pres_pen[s]), x)
        rep = float(rep_pen[s])
        x = np.where(seen, np.where(x > 0, x / rep, x * rep), x)
        words = np.asarray(mask_bits[s], np.uint32)
        allowed = ((words[np.arange(V) // 32]
                    >> (np.arange(V) % 32).astype(np.uint32)) & 1) \
            .astype(bool)
        x = np.where(allowed, np.maximum(x, ALLOWED_FLOOR), MASKED)
        if float(temperature[s]) == 0.0:
            tokens[s] = int(np.argmax(x))
            continue
        z = x / float(temperature[s])
        k = int(top_k[s])
        if k > 0:
            kth = np.sort(z)[::-1][min(k, V) - 1]
            z = np.where(z < kth, FILTERED, z)
        tp, mp = float(top_p[s]), float(min_p[s])
        ty = float(typical_p[s])
        # top_p >= 1 / min_p <= 0 / typical_p >= 1: filters disabled
        if tp < 1.0 or mp > 0.0 or ty < 1.0:
            e = np.exp(z - z.max())
            p = e / e.sum()
            keep = np.ones(V, bool)
            if tp < 1.0:
                order = np.argsort(-p, kind="stable")
                csum = np.cumsum(p[order])
                keep_sorted = (csum - p[order]) < tp
                keep[:] = False
                keep[order] = keep_sorted
            if mp > 0.0:              # min-p on the same pre-filter probs
                keep &= p >= mp * p.max()
            if ty < 1.0:              # typical-p, deviation-ascending
                surp = -np.log(np.where(p > 0, p, 1.0))
                ent = np.float32((p * surp).sum())
                dev = np.where(p > 0, np.abs(surp - ent), np.inf)
                dorder = np.argsort(dev, kind="stable")
                tkeep_sorted = (np.cumsum(p[dorder]) - p[dorder]) < ty
                tkeep_sorted[0] = True    # most-typical token survives
                tk = np.zeros(V, bool)
                tk[dorder] = tkeep_sorted
                keep &= tk
            keep[int(np.argmax(p))] = True  # host keeps >= 1 token (top-1)
            z = np.where(keep, z, FILTERED)
        key = jax.random.fold_in(jax.random.PRNGKey(int(seeds[s])),
                                 int(counters[s]))
        g = np.asarray(jax.random.gumbel(key, (V,), jnp.float32))
        tokens[s] = int(np.argmax(z + g))
    ls = np.asarray(jax.nn.log_softmax(jnp.asarray(logits), axis=-1))
    lp = ls[np.arange(S), tokens]
    if n_top > 0:
        top_ids = np.argsort(-ls, axis=-1, kind="stable")[:, :n_top]
        top_lps = np.take_along_axis(ls, top_ids, axis=-1)
    else:
        top_ids = np.zeros((S, 0), np.int32)
        top_lps = np.zeros((S, 0), np.float32)
    return tokens, lp, top_ids.astype(np.int32), top_lps


def batched_accept_ref(tokens, drafts, win_off):
    """Row-at-a-time oracle for ``kernels.sampling.batched_accept``:
    walk each window left to right and emit rows until (and including)
    the first one whose preceding row rejected its draft."""
    import numpy as np

    tokens = np.asarray(tokens)
    drafts = np.asarray(drafts)
    win_off = np.asarray(win_off)
    S = tokens.shape[0]
    emit = np.zeros(S, bool)
    for s in range(S):
        start = s - int(win_off[s])
        ok = True
        for j in range(start, s):
            if drafts[j] >= 0 and tokens[j] != drafts[j]:
                ok = False
                break
        emit[s] = ok
    return emit


def w4a16_gemm_ref(x: jax.Array, w_packed: jax.Array, scales: jax.Array,
                   group: int) -> jax.Array:
    """x: [M,K] bf16; w_packed: [K//2, N] int8 (2 nibbles along K);
    scales: [K//group, N] bf16.  Returns [M,N] bf16."""
    K2, N = w_packed.shape
    K = K2 * 2
    low = jnp.right_shift(jnp.left_shift(w_packed, 4), 4)
    high = jnp.right_shift(w_packed, 4)
    wq = jnp.stack([low, high], axis=1).reshape(K, N)      # int8 in [-8,7]
    w = (wq.astype(jnp.float32).reshape(K // group, group, N)
         * scales.astype(jnp.float32)[:, None, :]).reshape(K, N)
    return (x.astype(jnp.float32) @ w).astype(x.dtype)


def rmsnorm_ref(x: jax.Array, scale: jax.Array, *, eps: float = 1e-5,
                residual: Optional[jax.Array] = None) -> jax.Array:
    """Fused (residual-add +) RMSNorm: y = rms(x + residual) * scale."""
    if residual is not None:
        x = x + residual
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return y.astype(x.dtype)
