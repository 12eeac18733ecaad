"""PagedModelRunner: ragged fused steps through the paged KV cache.

The TPU-native serving path (WebLLM's PagedAttention analogue): every
attention layer keeps its own head-major physical page pool ``[P, Kv,
page_size, Dh_pad]`` (``head_dim`` padded to 128 lanes).  The pools are
a list of per-layer arrays, so a layer's pool reaches its kernel as a
whole buffer in the TPU's default layout: no per-layer slice copy and no
relayout copy around the scatter or the kernel.  EVERY token
— prompt or completion, cold or cache-hit — flows through the same paged
machinery, and a whole engine step dispatches as ONE kernel call:

* ``run_step(rows)``: the fused ragged step.  Each row is a chunk of
  consecutive tokens of one sequence — a decode token is a length-1 row,
  a prefill chunk up to ``chunk_size`` (or more, budget permitting)
  tokens.  All rows' K/V are scattered into their sequences' pages and
  attention runs via the multi-sequence ``kernels.paged_ragged_attention``
  kernel (per-row causal masks against each sequence's own cursor) in
  one jitted step.  Rows are padded to a (B, C) bucket so the jit
  variant count stays bounded; pad K/V writes land in a dedicated trash
  page.  This is what collapses the former one-kernel-call-per-sequence
  dispatch into one call per engine step.
* ``prefill_chunk(sid, tokens)`` / ``decode(seq_tokens)``: the per-kind
  single calls (one sequence's chunk / one batched decode token per
  sequence) — kept as the reference path for tests and non-interleaving
  callers; ``run_step`` subsumes both on the engine path.

There is no dense-prefill-then-scatter path anymore and no decode-per-
suffix-token replay: ``begin_seq`` adopts the longest prefix already in
the :class:`repro.core.prefix_cache.PrefixCache` (sharing full pages
zero-copy, forking a partial tail page copy-on-write) and the uncached
suffix runs through ragged rows / ``prefill_chunk``.  ``prefill_seq`` is
a thin loop over chunks for callers that want the whole prompt at once.

Page bookkeeping lives in :class:`repro.core.paged_cache.PageManager`.
:class:`PagedEngineBackend` wraps the runner in the slot-keyed unified
runner interface ``MLCEngine`` drives, adding the chunked-prefill calls
(``begin_prefill``/``run_step``) the step-plan scheduler uses.
"""
from __future__ import annotations

import functools
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.paged_cache import OutOfPages, PageManager
from repro.core.prefix_cache import PrefixCache
from repro.core.sampler import SampleResult, SamplingParamsBatch
from repro.kernels.ops import (paged_attention, paged_prefill_attention,
                               paged_ragged_attention)
from repro.kernels.sampling import (batched_accept, batched_sample,
                                    optional_stages)
from repro.models import model
from repro.models.attention import _project, _qk_norm
from repro.models.layers import apply_rope, mlp, rmsnorm
from repro.models.pdef import init_params
from repro.quant.int4 import qdot


def paged_supported(cfg: ModelConfig) -> bool:
    return (not cfg.is_encdec
            and all(s.mixer == "attn" and s.ffn == "dense"
                    for s in cfg.layer_pattern))


@dataclass
class StepHandle:
    """One dispatched-but-not-materialized fused step (the pipelined
    engine's unit of in-flight work).

    Holds the ON-DEVICE ``SampleResult`` arrays the fused jit returned —
    JAX async dispatch means the computation may still be running; no
    ``np.asarray`` has happened and the host has not blocked.  The next
    step's decode inputs can be fed device-to-device straight from
    ``tokens`` (``run_step(prev=handle, decode_srcs=...)``), so the host
    never needs these values to keep the device busy.  ``materialize()``
    blocks until the step is done, pulls the arrays across (accounted to
    the runner's ``t_block_s``/``host_sync_bytes``), backfills the token
    placeholders of device-fed rows into ``seq_tokens``, and caches the
    result (idempotent)."""
    tokens: object            # jax.Array [Sb] int32, on device
    logprob: object           # jax.Array [Sb] f32
    top_ids: object           # jax.Array [Sb, K] int32
    top_lps: object           # jax.Array [Sb, K] f32
    n_rows: int               # valid sampling rows (<= Sb)
    runner: "PagedModelRunner"
    #: jax.Array [Sb] bool — per-row speculative acceptance (all-True
    #: when the step carried no draft windows)
    emit: object = None
    #: (sid, index into seq_tokens[sid], sampling row) placeholders
    #: written by device-fed decode rows of the NEXT step, which
    #: consume THIS handle's tokens — resolved at materialize
    backfills: List[Tuple[int, int, int]] = field(default_factory=list)
    result: Optional[SampleResult] = None

    def backfill(self, sid: int, pos: int, src: int):
        """Register that ``seq_tokens[sid][pos]`` holds a placeholder
        for this handle's sampling row ``src`` (a device-fed decode
        input); resolves immediately when already materialized."""
        if self.result is not None:
            toks = self.runner.seq_tokens.get(sid)
            if toks is not None and pos < len(toks):
                toks[pos] = int(self.result.tokens[src])
        else:
            self.backfills.append((sid, pos, src))

    def materialize(self) -> SampleResult:
        if self.result is not None:
            return self.result
        r = self.runner
        # the span times what t_block_s counts: the wait for the step
        # and the token pull; the other pulls are the caller's own time
        with jax.profiler.TraceAnnotation("runner.materialize"):
            t0 = time.perf_counter()
            self.tokens.block_until_ready()
            tok = np.asarray(self.tokens)
            r.t_block_s += time.perf_counter() - t0
        res = SampleResult(
            tokens=tok[:self.n_rows],
            logprob=np.asarray(self.logprob)[:self.n_rows],
            top_ids=np.asarray(self.top_ids)[:self.n_rows],
            top_lps=np.asarray(self.top_lps)[:self.n_rows],
            emit=(np.asarray(self.emit)[:self.n_rows]
                  if self.emit is not None
                  else np.ones(self.n_rows, bool)))
        r.host_sync_bytes += (res.tokens.nbytes + res.logprob.nbytes
                              + res.top_ids.nbytes + res.top_lps.nbytes
                              + res.emit.nbytes)
        for sid, pos, src in self.backfills:
            toks = r.seq_tokens.get(sid)
            if toks is not None and pos < len(toks):
                toks[pos] = int(tok[src])
        self.result = res
        return res


class PagedModelRunner:
    """Chunked-prefill + decode paged runner (everything runs in pages)."""

    def __init__(self, cfg: ModelConfig, params=None, *, num_pages: int = 64,
                 page_size: int = 16, max_slots: int = 4,
                 pages_per_seq: int = 8, seed: int = 0,
                 enable_prefix_cache: bool = True,
                 chunk_size: int = 16,
                 max_cached_pages: Optional[int] = None,
                 max_cached_bytes: Optional[int] = None,
                 kv_dtype: str = "f32",
                 weight_quant: str = "off"):
        assert paged_supported(cfg), f"{cfg.name}: paged path needs pure GQA"
        assert chunk_size >= 1
        assert kv_dtype in ("f32", "int8"), kv_dtype
        assert weight_quant in ("off", "w4a16"), weight_quant
        self.cfg = cfg
        self.page_size = page_size
        self.pages_per_seq = pages_per_seq
        self.max_slots = max_slots
        self.chunk_size = chunk_size
        #: Python-static quantization switch: every traced step function
        #: branches on it at TRACE time, so the f32 default compiles to
        #: exactly the pre-quantization program
        self.kv_quant = kv_dtype == "int8"
        self.kv_dtype = kv_dtype
        self.weight_quant = weight_quant
        self.pm = PageManager(num_pages, page_size, max_slots, pages_per_seq)
        # K + V planes across every layer — what one physical page of
        # THIS model actually costs, so a byte cap can govern several
        # loaded models with one number.  Derived from the actual pool
        # dtypes: bf16 K/V vectors by default; int8 vectors plus one
        # bf16 scale per (token, kv-head) when the pool is quantized.
        # Pools pad head_dim with zero lanes to a multiple of 128: the
        # kernel then reads them in the TPU's default layout, with no
        # relayout copy of the pool around each call (see
        # kernels/paged_attention.py), and the padded lanes count here.
        self.pool_dh = -(-cfg.head_dim // 128) * 128
        kv_elem = 1 if self.kv_quant else jnp.dtype(jnp.bfloat16).itemsize
        scale_bytes = jnp.dtype(jnp.bfloat16).itemsize if self.kv_quant \
            else 0
        self.page_bytes = (2 * cfg.n_layers * page_size * cfg.n_kv_heads
                           * (self.pool_dh * kv_elem + scale_bytes))
        self.prefix_cache = (
            PrefixCache(self.pm, max_cached_pages=max_cached_pages,
                        max_cached_bytes=max_cached_bytes,
                        page_bytes=self.page_bytes)
            if enable_prefix_cache else None)
        self.seq_tokens: Dict[int, List[int]] = {}   # tokens whose KV is paged
        self.last_prefill_info: Dict[str, int] = {"prefix_cached_tokens": 0}
        self.n_prefills = 0               # prompt prefills (not forks)
        self.n_forks = 0                  # CoW sequence forks
        self.n_prefill_chunks = 0         # chunked prefill kernel steps
        self.n_prefill_tokens = 0         # real (non-pad) tokens prefilled
        self.n_decode_steps = 0           # batched decode steps
        self.n_decode_tokens = 0          # tokens decoded across the batch
        self.n_ragged_steps = 0           # fused ragged kernel steps
        #: token slots of the padded (B, C) buckets of those steps
        self.n_bucket_tokens = 0
        self.n_sampled_tokens = 0         # tokens sampled ON DEVICE
        #: stochastic steps that ran each conditional sampler stage
        #: (``kernels.sampling.optional_stages``)
        self.sample_branch_steps = {"top_k": 0, "typical_p": 0}
        #: logit ROWS ([V] float vectors) pulled device→host — 0 on the
        #: fused engine path, where only sampled token ids cross back
        self.host_logit_rows = 0
        self.host_sync_bytes = 0          # device→host payload bytes
        self.t_block_s = 0.0              # host seconds blocked on device
        #: distinct fused-sampled jit variants dispatched so far, keyed
        #: by their full static signature (surfaced as ``jit_buckets``)
        self._seen_buckets: set = set()
        self.n_warmup_compiles = 0        # variants compiled by warmup()
        self.n_rewinds = 0                # lag-1 finish rewinds applied
        #: sampling rows are ALWAYS padded to this fixed bucket — it
        #: keeps one step's on-device token array shape-stable, so a
        #: pipelined step can gather its decode inputs straight from the
        #: previous StepHandle without a reshape or an extra variant
        self._s_rows = self._bucket(max(1, max_slots))
        #: device-resident penalty count planes ``[max_slots + 1, V]``
        #: (row ``max_slots`` is the trash row pad sampling rows
        #: scatter into) — allocated lazily at the engine's vocab,
        #: donated through every fused step, gathered by ``slot_ids``
        #: before sampling and scatter-incremented with each sampled
        #: token after it, replacing per-step dense [S, V] uploads
        self.count_planes = jnp.zeros((1, 1), jnp.float32)
        self._plane_vocab: Optional[int] = None
        #: double-buffered host staging for the sampling uploads (the
        #: SHARK-Engine fenced TransferBufferPool idiom): consecutive
        #: steps alternate buffer sets, so overwriting a buffer for step
        #: N+2 can never race the (possibly still-pending) transfer of
        #: step N — depth-2 pipelining guarantees step N has drained by
        #: then
        self._staging = ({}, {})
        self._staging_i = 0
        #: bounded trace of jitted steps, for liveness assertions/tests:
        #: ("decode", batch_size) | ("chunk", n_valid_tokens) |
        #: ("ragged", n_decode_rows, n_prefill_tokens)
        self.step_log: Deque[Tuple] = deque(maxlen=4096)
        if params is None:
            params = init_params(model.params_def(cfg),
                                 jax.random.PRNGKey(seed))
        if weight_quant == "w4a16":
            from repro.quant.int4 import quantize_tree
            params = quantize_tree(params, model.params_def(cfg))
        self.params = params
        L, Kv, Dh = cfg.n_layers, cfg.n_kv_heads, self.pool_dh
        # one extra physical page (index num_pages) absorbs the K/V
        # writes of a padded final chunk's pad rows — never in any
        # page table, never read
        self.trash_page = num_pages
        pool_dtype = jnp.int8 if self.kv_quant else jnp.bfloat16
        pool_shape = (num_pages + 1, Kv, page_size, Dh)
        self.k_pages = [jnp.zeros(pool_shape, pool_dtype) for _ in range(L)]
        self.v_pages = [jnp.zeros(pool_shape, pool_dtype) for _ in range(L)]
        # per-(token, kv-head) dequant scale planes [P, page_size, Kv],
        # paged like the pools so the page table routes them too.  In
        # f32 mode they are tiny placeholders: every jit signature
        # carries them (donated + rebound like the pools) so both modes
        # share one call protocol, but no traced op ever touches them.
        scale_shape = ((num_pages + 1, page_size, Kv)
                       if self.kv_quant else (1, 1, 1))
        self.k_scales = [jnp.zeros(scale_shape, jnp.bfloat16)
                         for _ in range(L)]
        self.v_scales = [jnp.zeros(scale_shape, jnp.bfloat16)
                         for _ in range(L)]
        self._step = jax.jit(self._decode_step, donate_argnums=(1, 2, 3, 4))
        self._chunk_step = jax.jit(self._prefill_chunk_step,
                                   donate_argnums=(1, 2, 3, 4))
        # one jit object: variants are cached per traced (B, C) bucket;
        # run_step pads both to powers of two so the count stays bounded
        # at O(log(max_slots) * log(max chunk tokens))
        self._ragged_jit = jax.jit(self._ragged_step,
                                   donate_argnums=(1, 2, 3, 4))
        # the fused logits→token variant the engine drives: sampling is
        # chained after ragged attention INSIDE the same jitted step, so
        # a whole engine step stays one dispatch and only token ids (not
        # [B, V] logits) come back; variants add (S, n_top) buckets.
        # The count planes (arg 5) ride donated through every step like
        # the page pools and scale planes, so penalty bookkeeping stays
        # device-resident.
        self._ragged_sample_jit = jax.jit(
            self._ragged_sample_step, donate_argnums=(1, 2, 3, 4, 5),
            static_argnames=("vocab", "n_top", "use_planes",
                             "all_greedy", "need_logprobs", "use_counts"))

        def _copy(k, v, ks, vs, src, dst):
            def page(pools):
                return [x.at[dst].set(x[src]) for x in pools]
            if self.kv_quant:    # placeholders have no page dim to copy
                ks, vs = page(ks), page(vs)
            return page(k), page(v), ks, vs

        # donated so XLA updates the pools in place instead of copying
        # the whole K/V buffers per CoW fork
        self._copy_jit = jax.jit(_copy, donate_argnums=(0, 1, 2, 3))
        # donated single-row overwrite: re-seeds one count-plane row
        # from the host oracle at slot bind/resume
        self._seed_plane_jit = jax.jit(
            lambda pl, vals, row: pl.at[row].set(vals),
            donate_argnums=(0,))
        # persistent all-zero "previous tokens" per length, for steps
        # with no pipelined predecessor (avoids a per-step upload)
        self._zero_prev: Dict[int, object] = {}

    # ------------------------------------------------------------------
    def _layer_params(self):
        """Unstack the scanned block params into per-layer trees."""
        g = self.cfg.grouped_pattern()
        layers = list(self.params["decoder"]["prefix"])
        if g.n_blocks:
            stacked = self.params["decoder"]["blocks"]
            for i in range(g.n_blocks):
                for j in range(len(g.block)):
                    layers.append(jax.tree.map(lambda x: x[i], stacked[j]))
        layers += list(self.params["decoder"]["suffix"])
        return layers

    @staticmethod
    def _page_quant(x):
        """Symmetric per-(token, kv-head) int8 quantization of K/V rows:
        ``x [..., Kv, Dh] -> (int8 values, bf16 scales [..., Kv])``.
        Dequant is ``values * scale`` — exactly the multiply the paged
        kernels fuse into their page loop."""
        xf = x.astype(jnp.float32)
        amax = jnp.max(jnp.abs(xf), axis=-1)
        scale = jnp.maximum(amax / 127.0, 1e-8)
        q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127)
        return q.astype(jnp.int8), scale.astype(jnp.bfloat16)

    @staticmethod
    def _scatter_rows(pool, page_idx, page_off, x):
        """Write row n of ``x`` ([N, Kv, Dh], zero-padded to the pool's
        lane width) to token slot ``page_off[n]`` of physical page
        ``page_idx[n]`` of a head-major ``pool`` ([P, Kv, page_size,
        pool_dh]), for every kv head.

        The pool is viewed as ``[P * Kv, page_size, pool_dh]`` (a free
        reshape of the leading dims), so the scatter indexes the two
        major dims and writes whole lane rows: the form the TPU scatter
        runs in place on the pool's own layout.  Indexing ``[page, :,
        offset]`` directly makes XLA relayout (copy) the whole pool
        around every scatter."""
        P, Kv, ps, dh = pool.shape
        n = page_idx.shape[0]
        heads = (page_idx[:, None] * Kv
                 + jnp.arange(Kv, dtype=page_idx.dtype)[None, :])
        offs = jnp.broadcast_to(page_off[:, None], (n, Kv))
        rows = jnp.pad(x.reshape(n * Kv, -1).astype(pool.dtype),
                       ((0, 0), (0, dh - x.shape[-1])))
        flat = pool.reshape(P * Kv, ps, dh).at[
            heads.reshape(-1), offs.reshape(-1)].set(rows)
        return flat.reshape(pool.shape)

    def _scatter_kv(self, pools, li, page_idx, page_off, k, v):
        """Scatter layer ``li``'s new K/V rows ([N, Kv, Dh]) into its
        page pool — quantizing at scatter time (values + scales) when the
        pool is int8.  ``pools`` is the ``(k_pages, v_pages, k_scales,
        v_scales)`` tuple of per-layer lists, updated in place.  Returns
        the layer's kernel operands ``(k_pool, v_pool, k_scales,
        v_scales)``, the scales ``None`` (the unquantized kernel variant)
        unless the pool is int8.  The branch is on a Python flag, so
        each mode traces to a single-path program."""
        kp, vp, ks, vs = pools
        put = functools.partial(self._scatter_rows, page_idx=page_idx,
                                page_off=page_off)
        if self.kv_quant:
            kq, kscale = self._page_quant(k)
            vq, vscale = self._page_quant(v)
            kp[li], vp[li] = put(kp[li], x=kq), put(vp[li], x=vq)
            # scale planes are token-major [P, page_size, Kv]: one
            # [Kv] row per token
            ks[li] = ks[li].at[page_idx, page_off].set(kscale)
            vs[li] = vs[li].at[page_idx, page_off].set(vscale)
            return kp[li], vp[li], ks[li], vs[li]
        kp[li], vp[li] = put(kp[li], x=k), put(vp[li], x=v)
        return kp[li], vp[li], None, None

    def _decode_step(self, params, k_pages, v_pages, k_scales, v_scales,
                     token, pos, page_table, lens, page_idx, page_off):
        """token [B,1], pos [B], page_table [B,pps], lens [B] (incl. the
        new token), page_idx/page_off [B]: physical write location."""
        cfg = self.cfg
        B = token.shape[0]
        x = jnp.take(params["embed"], token, axis=0)           # [B,1,D]
        layers = self._layer_params_traced(params)
        pools = tuple(map(list, (k_pages, v_pages, k_scales, v_scales)))
        for li, p in enumerate(layers):
            h = rmsnorm(x, p["mixer_norm"], cfg.norm_eps)
            q = _project(cfg, p["attn"], h, "q", cfg.n_heads)  # [B,1,H,Dh]
            k = _project(cfg, p["attn"], h, "k", cfg.n_kv_heads)
            v = _project(cfg, p["attn"], h, "v", cfg.n_kv_heads)
            q, k = _qk_norm(cfg, p["attn"], q, k)
            q = apply_rope(q, pos[:, None], cfg.rope_theta)
            k = apply_rope(k, pos[:, None], cfg.rope_theta)
            # scatter the new K/V into each sequence's current page
            kp, vp, ks, vs = self._scatter_kv(pools, li, page_idx,
                                              page_off, k[:, 0], v[:, 0])
            att = paged_attention(q[:, 0], kp, vp, page_table, lens,
                                  k_scales=ks, v_scales=vs)   # [B,H,Dh]
            y = qdot(att.reshape(B, 1, -1), p["attn"]["wo"])
            x = x + y
            h = rmsnorm(x, p["ffn_norm"], cfg.norm_eps)
            x = x + mlp(h, p["ffn"], cfg.act)
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        if cfg.tie_embeddings:
            logits = x @ params["embed"].T
        else:
            logits = x @ params["lm_head"]
        return (logits,) + pools

    def _prefill_chunk_step(self, params, k_pages, v_pages, k_scales,
                            v_scales, tokens, pos, page_table, ctx, start,
                            page_idx, page_off):
        """One chunked-prefill step for a single sequence.

        tokens/pos/page_idx/page_off [C] (C = chunk_size, padded);
        page_table [pps]; ctx scalar (tokens in pages incl. this chunk's
        valid suffix); start scalar (global position of chunk row 0).
        K/V for all C rows are scattered into pages (pad rows land in
        the trash page) and the chunk attends to the page table with
        causal masking inside the chunk.  Returns logits [C, V]."""
        cfg = self.cfg
        C = tokens.shape[0]
        x = jnp.take(params["embed"], tokens[None], axis=0)    # [1,C,D]
        layers = self._layer_params_traced(params)
        pools = tuple(map(list, (k_pages, v_pages, k_scales, v_scales)))
        for li, p in enumerate(layers):
            h = rmsnorm(x, p["mixer_norm"], cfg.norm_eps)
            q = _project(cfg, p["attn"], h, "q", cfg.n_heads)  # [1,C,H,Dh]
            k = _project(cfg, p["attn"], h, "k", cfg.n_kv_heads)
            v = _project(cfg, p["attn"], h, "v", cfg.n_kv_heads)
            q, k = _qk_norm(cfg, p["attn"], q, k)
            q = apply_rope(q, pos[None, :], cfg.rope_theta)
            k = apply_rope(k, pos[None, :], cfg.rope_theta)
            kp, vp, ks, vs = self._scatter_kv(pools, li, page_idx,
                                              page_off, k[0], v[0])
            att = paged_prefill_attention(q[0], kp, vp, page_table, ctx,
                                          start, k_scales=ks,
                                          v_scales=vs)         # [C,H,Dh]
            y = qdot(att.reshape(1, C, -1), p["attn"]["wo"])
            x = x + y
            h = rmsnorm(x, p["ffn_norm"], cfg.norm_eps)
            x = x + mlp(h, p["ffn"], cfg.act)
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        if cfg.tie_embeddings:
            logits = x @ params["embed"].T
        else:
            logits = x @ params["lm_head"]
        return (logits[0],) + pools

    def _ragged_logits(self, params, k_pages, v_pages, k_scales, v_scales,
                       tokens, pos, page_tables, contexts, starts, lengths,
                       page_idx, page_off):
        """One fused ragged step over B packed rows of C slots each.

        tokens/pos/page_idx/page_off [B*C] (row b occupies the slice
        ``b*C : (b+1)*C``; slots past the row's valid length are pads);
        page_tables [B, pps]; contexts/starts/lengths [B].  K/V for all
        B*C slots are scattered into pages (pads land in the trash page)
        and every row attends to its OWN page-table row with per-row
        causal masking — one attention kernel invocation per layer for
        the whole step.  Returns each row's FULL per-slot logits
        [B, C, V]: speculative verify windows sample several offsets of
        one row, so the reduce to one position per row happens in the
        caller (``_ragged_step`` keeps the last-valid-slot [B, V]
        semantics for the legacy logits path)."""
        cfg = self.cfg
        B = page_tables.shape[0]
        N = tokens.shape[0]
        C = N // B
        x = jnp.take(params["embed"], tokens[None], axis=0)    # [1,N,D]
        layers = self._layer_params_traced(params)
        pools = tuple(map(list, (k_pages, v_pages, k_scales, v_scales)))
        for li, p in enumerate(layers):
            h = rmsnorm(x, p["mixer_norm"], cfg.norm_eps)
            q = _project(cfg, p["attn"], h, "q", cfg.n_heads)  # [1,N,H,Dh]
            k = _project(cfg, p["attn"], h, "k", cfg.n_kv_heads)
            v = _project(cfg, p["attn"], h, "v", cfg.n_kv_heads)
            q, k = _qk_norm(cfg, p["attn"], q, k)
            q = apply_rope(q, pos[None, :], cfg.rope_theta)
            k = apply_rope(k, pos[None, :], cfg.rope_theta)
            kp, vp, ks, vs = self._scatter_kv(pools, li, page_idx,
                                              page_off, k[0], v[0])
            att = paged_ragged_attention(
                q[0].reshape(B, C, cfg.n_heads, cfg.head_dim), kp, vp,
                page_tables, contexts, starts, k_scales=ks,
                v_scales=vs)                                   # [B,C,H,Dh]
            y = qdot(att.reshape(1, N, -1), p["attn"]["wo"])
            x = x + y
            h = rmsnorm(x, p["ffn_norm"], cfg.norm_eps)
            x = x + mlp(h, p["ffn"], cfg.act)
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        if cfg.tie_embeddings:
            logits = x @ params["embed"].T
        else:
            logits = x @ params["lm_head"]
        return (logits[0].reshape(B, C, -1),) + pools

    def _ragged_step(self, params, k_pages, v_pages, k_scales, v_scales,
                     tokens, pos, page_tables, contexts, starts, lengths,
                     page_idx, page_off):
        """Legacy logits-path reduce over :meth:`_ragged_logits`: each
        row's last-valid-slot logits [B, V]."""
        logits, k_pages, v_pages, k_scales, v_scales = self._ragged_logits(
            params, k_pages, v_pages, k_scales, v_scales, tokens, pos,
            page_tables, contexts, starts, lengths, page_idx, page_off)
        C = logits.shape[1]
        last = jnp.clip(lengths - 1, 0, C - 1)
        out = jnp.take_along_axis(logits, last[:, None, None], axis=1)[:, 0]
        return out, k_pages, v_pages, k_scales, v_scales

    def _ragged_sample_step(self, params, k_pages, v_pages, k_scales,
                            v_scales, count_planes,
                            tokens, pos, page_tables, contexts, starts,
                            lengths, page_idx, page_off, prev_tokens,
                            tok_src, parent, offsets, seeds, counters,
                            temperature, top_k, top_p, min_p, typical_p,
                            freq_pen, pres_pen, rep_pen, bias, counts,
                            slot_rows, mask_bits, draft_toks, win_off,
                            *, vocab: int, n_top: int,
                            use_planes: bool, all_greedy: bool,
                            need_logprobs: bool, use_counts: bool):
        """The fused logits→token step: ragged attention, then batched
        sampling over the rows' last-valid-token logits, in ONE jit.

        ``parent [S]`` maps each sampling row to the attention row whose
        logits it draws from (several sampling rows may share a parent —
        ``n``-way siblings sampling one freshly prefilled prompt, or the
        ``k+1`` positions of a speculative verify window) and ``offsets
        [S]`` selects the slot WITHIN that row (ordinary rows: the last
        valid slot; verify windows: ``0..k``); the remaining per-row
        arrays are the :class:`SamplingParamsBatch` fields.
        ``draft_toks``/``win_off`` feed ``batched_accept``: the returned
        ``emit [S]`` marks the rows whose (seed, counter) draw saw
        exactly the sequential path's logits — i.e. every earlier row of
        the same window resampled its own draft — so the engine retires
        ``1..k+1`` tokens per window and rewinds the rest.  Two
        device-to-device indirections keep the pipelined engine off the
        host:

        * ``tok_src [B*C]`` — slots with ``tok_src >= 0`` take their
          input token from ``prev_tokens[tok_src]`` (the PREVIOUS step's
          on-device sampled tokens) instead of the host-packed
          ``tokens``, so a decode step can be dispatched before the
          token it consumes has ever been materialized on the host.
        * ``slot_rows [S]`` + ``count_planes`` — with ``use_counts`` the
          freq/presence/repetition counts are gathered from the
          device-resident planes (and the sampled tokens scattered back
          in), so no dense ``[S, V]`` host plane is ever uploaded.

        Returns ``(token [S], logprob [S], top_ids [S, n_top], top_lps
        [S, n_top])`` plus the updated page pools and count planes —
        ``[B, V]`` logits never leave the device."""
        tokens = jnp.where(tok_src >= 0,
                           prev_tokens[jnp.clip(tok_src, 0)], tokens)
        logits, k_pages, v_pages, k_scales, v_scales = self._ragged_logits(
            params, k_pages, v_pages, k_scales, v_scales, tokens, pos,
            page_tables, contexts, starts, lengths, page_idx, page_off)
        rows = logits[parent, offsets][:, :vocab]
        if use_counts:
            counts = count_planes[slot_rows]
        out = batched_sample(rows, seeds, counters, temperature, top_k,
                             top_p, min_p, typical_p, freq_pen,
                             pres_pen, rep_pen,
                             bias, counts, mask_bits, n_top=n_top,
                             use_planes=use_planes or use_counts,
                             all_greedy=all_greedy,
                             need_logprobs=need_logprobs)
        emit = batched_accept(out[0], draft_toks, win_off)
        if use_counts:
            # pad rows carry slot_rows == max_slots (the trash row), so
            # their greedy throwaway tokens never touch a live plane.
            # Verify-window rows scatter unconditionally too — penalty-
            # bearing rows never draft (the engine flushes them to
            # k=0), so a rejected draw only ever lands in a plane row
            # whose penalties are all zero, where counts have no effect
            # and the next penalty-bearing bind re-seeds anyway
            count_planes = count_planes.at[slot_rows, out[0]].add(1.0)
        return (out + (emit,), k_pages, v_pages, k_scales, v_scales,
                count_planes)

    def _layer_params_traced(self, params):
        g = self.cfg.grouped_pattern()
        layers = list(params["decoder"]["prefix"])
        if g.n_blocks:
            stacked = params["decoder"]["blocks"]
            for i in range(g.n_blocks):
                for j in range(len(g.block)):
                    layers.append(jax.tree.map(lambda x: x[i], stacked[j]))
        layers += list(params["decoder"]["suffix"])
        return layers

    # -- host-side API ---------------------------------------------------
    def begin_seq(self, prompt_ids: List[int]) -> int:
        """Open a new sequence for chunked prefill of ``prompt_ids``.

        The longest prefix already present in the prefix cache is adopted
        (full pages shared in place, a partial tail page forked
        copy-on-write); ``seq_len(sid)`` afterwards reports how many
        leading tokens are already in pages — the caller feeds the rest
        through ``prefill_chunk``.  At least one suffix token is always
        left uncached so the final chunk yields logits.  Returns seq_id.
        """
        prompt_ids = [int(t) for t in prompt_ids]
        self.n_prefills += 1
        alloc = self.pm.new_seq()
        sid = alloc.seq_id
        cached = 0
        if self.prefix_cache is not None and len(prompt_ids) > 1:
            # always leave >= 1 suffix token so prefill yields logits
            full_pages, tail = self.prefix_cache.match(prompt_ids[:-1])
            try:
                if full_pages:
                    self.pm.share_pages(sid, full_pages,
                                        len(full_pages) * self.page_size)
                if tail is not None:
                    src, n_tok = tail
                    dst = self.pm.fork_page(sid, n_tok)
                    self._copy_page(src, dst)
            except Exception:
                self.pm.free_seq(sid)
                raise
            cached = alloc.length
        self.last_prefill_info = {"prefix_cached_tokens": cached}
        self.seq_tokens[sid] = prompt_ids[:cached]
        return sid

    def seq_len(self, sid: int) -> int:
        """Tokens currently stored in the sequence's pages."""
        return self.pm.seqs[sid].length

    def prefill_chunk(self, sid: int, tokens: List[int]) -> np.ndarray:
        """Prefill up to ``chunk_size`` consecutive prompt tokens.

        K/V for every token are scattered into the sequence's pages and
        the chunk attends to the full page table (causal inside the
        chunk) in ONE jitted step; a partial final chunk is padded to
        ``chunk_size`` (pad rows write to the trash page).  Raises
        :class:`OutOfPages` *before* mutating sequence state when the
        pool cannot back the chunk.  Returns the last valid token's
        logits [V]."""
        tokens = [int(t) for t in tokens]
        T = len(tokens)
        C = self.chunk_size
        assert 0 < T <= C, (T, C)
        alloc = self.pm.seqs[sid]
        start = alloc.length
        need_pages = -(-(start + T) // self.page_size)
        if need_pages > self.pm.pages_per_seq:
            raise OutOfPages(f"seq {sid} at pages_per_seq cap")
        self.pm.require_pages(max(0, need_pages - len(alloc.pages)))
        self.pm.append_tokens(sid, T)
        pages = alloc.pages
        pos = (start + np.arange(C)).astype(np.int32)
        page_idx = np.full(C, self.trash_page, np.int32)
        page_idx[:T] = [pages[p // self.page_size] for p in pos[:T]]
        page_off = (pos % self.page_size).astype(np.int32)
        tok = np.zeros(C, np.int32)
        tok[:T] = tokens
        table = self.pm.page_table([sid])[0]
        logits, self.k_pages, self.v_pages, self.k_scales, self.v_scales = \
            self._chunk_step(
                self.params, self.k_pages, self.v_pages, self.k_scales,
                self.v_scales, jnp.asarray(tok),
                jnp.asarray(pos), jnp.asarray(table), np.int32(start + T),
                np.int32(start), jnp.asarray(page_idx),
                jnp.asarray(page_off))
        self.seq_tokens[sid].extend(tokens)
        self.n_prefill_chunks += 1
        self.n_prefill_tokens += T
        self.step_log.append(("chunk", T))
        out = np.asarray(logits[T - 1].astype(jnp.float32))
        self.host_logit_rows += 1
        self.host_sync_bytes += out.nbytes
        self._last_logits_np = out
        return out

    def prefill_seq(self, prompt_ids: List[int]) -> int:
        """Prefill a whole prompt: ``begin_seq`` (prefix-cache adoption)
        then a loop of ``prefill_chunk`` over the uncached suffix.
        Returns seq_id; ``last_prefill_logits()`` has the final logits."""
        prompt_ids = [int(t) for t in prompt_ids]
        sid = self.begin_seq(prompt_ids)
        done = self.seq_len(sid)
        try:
            while done < len(prompt_ids):
                n = min(self.chunk_size, len(prompt_ids) - done)
                self.prefill_chunk(sid, prompt_ids[done:done + n])
                done += n
        except Exception:
            self.free(sid)
            raise
        return sid

    @staticmethod
    def _bucket(n: int) -> int:
        """Next power of two — pads ragged (B, C) to a bounded set of
        jit variants instead of one trace per exact shape."""
        b = 1
        while b < n:
            b *= 2
        return b

    @functools.partial(jax.profiler.annotate_function,
                       name="runner.dispatch")
    def run_step(self, rows: List[Tuple[int, List[int], str]],
                 sampling: Optional[SamplingParamsBatch] = None,
                 n_top: int = 0, return_logits: bool = True,
                 materialize: bool = True,
                 prev: Optional[StepHandle] = None,
                 decode_srcs: Optional[Dict[int, int]] = None):
        """Execute one fused ragged step: ONE attention kernel call for
        a whole engine step's mixed decode + prefill work.

        ``rows`` is the packed ragged layout: one ``(sid, tokens, kind)``
        entry per sequence, where ``tokens`` are the consecutive tokens
        to scatter-and-attend for that sequence this step — a decode row
        carries exactly its one pending token (``kind="decode"``), a
        prefill row carries the next chunk of its prompt
        (``kind="prefill"``).  A sequence may appear at most once.

        The batch is padded to a power-of-two ``(B, C)`` bucket (pad
        slots write K/V into the trash page; pad rows carry
        ``context=0`` and are skipped by the kernel), so the number of
        live jit variants stays O(log max_slots * log max chunk).

        Raises :class:`OutOfPages` BEFORE any sequence state mutates
        when the page pool cannot back every row (the engine preempts
        and replans).

        With ``sampling`` (a :class:`SamplingParamsBatch` whose
        ``parent`` entries index into ``rows``) the step is the fused
        logits→token pipeline: batched sampling chains after ragged
        attention inside the SAME jitted call and a
        :class:`SampleResult` (token ids + logprobs, ordered like the
        batch) returns — ``[B, V]`` logits never cross the device→host
        boundary.  Without it (the legacy/test path) each row's
        last-valid-token logits return as ``{sid: [V] float32}``,
        counted by ``host_logit_rows`` — unless ``return_logits=False``
        (a step that only advances mid-prompt prefill produces no token
        and must transfer nothing).

        The three pipelining kwargs (fused sampled path only):
        ``materialize=False`` skips the blocking device→host pull and
        returns a :class:`StepHandle` instead of a
        :class:`SampleResult` — JAX async dispatch means the host is
        free the moment the step is enqueued.  ``prev`` is the previous
        step's (possibly still-running) handle and ``decode_srcs`` maps
        a row index ``b`` of THIS step to the sampling row of ``prev``
        whose on-device token row ``b`` consumes: the row's packed
        token is a placeholder resolved inside the jit
        (device-to-device), and ``prev``'s eventual materialization
        backfills the real id into ``seq_tokens``.
        """
        assert rows, "run_step needs at least one row"
        sids = [sid for sid, _, _ in rows]
        assert len(set(sids)) == len(sids), \
            "one ragged row per sequence — merge chunks before calling"
        # atomic capacity pre-check: fail before touching any state so
        # the engine can preempt and retry without corrupted bookkeeping
        total_new = 0
        for sid, toks, _ in rows:
            alloc = self.pm.seqs[sid]
            n = len(toks)
            assert n >= 1, (sid, toks)
            need = -(-(alloc.length + n) // self.page_size)
            if need > self.pm.pages_per_seq:
                raise OutOfPages(f"seq {sid} at pages_per_seq cap")
            total_new += max(0, need - len(alloc.pages))
        self.pm.require_pages(total_new)

        B = len(rows)
        Bb = self._bucket(B)
        Cb = self._bucket(max(len(toks) for _, toks, _ in rows))
        N = Bb * Cb
        self.n_bucket_tokens += N
        tok = np.zeros(N, np.int32)
        tok_src = np.full(N, -1, np.int32)   # >= 0: take prev_tokens[src]
        pos = np.zeros(N, np.int32)
        page_idx = np.full(N, self.trash_page, np.int32)
        page_off = np.zeros(N, np.int32)
        page_tables = np.zeros((Bb, self.pm.pages_per_seq), np.int32)
        contexts = np.zeros(Bb, np.int32)    # pad rows: 0 -> kernel skips
        starts = np.zeros(Bb, np.int32)
        lengths = np.zeros(Bb, np.int32)
        for b, (sid, toks, _) in enumerate(rows):
            alloc = self.pm.seqs[sid]
            start = alloc.length
            n = len(toks)
            self.pm.append_tokens(sid, n)
            pages = alloc.pages
            rp = start + np.arange(Cb)
            o = b * Cb
            tok[o:o + n] = toks
            pos[o:o + Cb] = rp
            page_idx[o:o + n] = [pages[p // self.page_size]
                                 for p in rp[:n]]
            page_off[o:o + Cb] = rp % self.page_size
            page_tables[b, :len(pages)] = pages
            contexts[b] = start + n
            starts[b] = start
            lengths[b] = n
            if decode_srcs and b in decode_srcs:
                # device-fed rows carry their placeholder at offset 0;
                # a speculative verify row's draft tail (offsets 1..k)
                # is host-known and packed normally
                tok_src[o] = decode_srcs[b]
        self.pm.count_live_tokens()
        attn_args = (jnp.asarray(tok), jnp.asarray(pos),
                     jnp.asarray(page_tables), jnp.asarray(contexts),
                     jnp.asarray(starts), jnp.asarray(lengths),
                     jnp.asarray(page_idx), jnp.asarray(page_off))
        if sampling is not None:
            if sampling.offsets is None:
                # default: every sampling row draws from its parent
                # row's LAST valid slot (the non-speculative semantics;
                # verify windows set explicit offsets 0..k)
                row_last = np.array([len(t) - 1 for _, t, _ in rows],
                                    np.int32)
                sampling.offsets = row_last[sampling.parent]
            sampled = self._dispatch_sampled(sampling, n_top, attn_args,
                                             tok_src, prev)
        else:
            assert prev is None and not decode_srcs, \
                "device-fed tokens need the fused sampled path"
            logits, self.k_pages, self.v_pages, self.k_scales, \
                self.v_scales = self._ragged_jit(
                    self.params, self.k_pages, self.v_pages,
                    self.k_scales, self.v_scales, *attn_args)
            if return_logits:
                out = np.asarray(logits.astype(jnp.float32))
                self.host_logit_rows += B
                self.host_sync_bytes += out[:B].nbytes
        n_dec = n_pf = 0
        result: Dict[int, np.ndarray] = {}
        for b, (sid, toks, kind) in enumerate(rows):
            if sid in self.seq_tokens:
                if decode_srcs and b in decode_srcs:
                    prev.backfill(sid, len(self.seq_tokens[sid]),
                                  decode_srcs[b])
                self.seq_tokens[sid].extend(int(t) for t in toks)
            if kind == "decode":
                n_dec += 1
                self.n_decode_tokens += len(toks)
            else:
                n_pf += len(toks)
                self.n_prefill_tokens += len(toks)
            if sampling is None and return_logits:
                result[sid] = out[b]
        self.n_ragged_steps += 1
        self.step_log.append(("ragged", n_dec, n_pf))
        if sampling is not None:
            return sampled.materialize() if materialize else sampled
        return result

    def _dispatch_sampled(self, sampling: SamplingParamsBatch,
                          n_top: int, attn_args: tuple,
                          tok_src: np.ndarray,
                          prev: Optional[StepHandle] = None) -> StepHandle:
        """Dispatch the fused attention+sampling jit for one packed step
        WITHOUT blocking: returns a :class:`StepHandle` over the
        on-device outputs (JAX async dispatch frees the host
        immediately; ``run_step`` materializes it for legacy callers).

        Sampling rows are padded to at least the FIXED ``self._s_rows``
        bucket (pad rows sample greedily from attention row 0, scatter
        their count update into the trash plane row, and are dropped) so
        the on-device token array has one stable shape: the next step
        can gather its decode inputs from it (``tok_src``) without
        minting a new jit variant, and warmup covers steady state.

        Host staging buffers are pooled and double-buffered (alternating
        per call, reuse distance 2): by the time a buffer is repacked
        for step N+2, step N has drained, so even a zero-copy
        ``jnp.asarray`` of the buffer can never race a pending read —
        the SHARK-Engine fenced TransferBufferPool idiom."""
        S = len(sampling)
        assert S >= 1, "sampled step needs at least one sampling row"
        Sb = max(self._s_rows, self._bucket(S))
        stage = self._staging[self._staging_i]
        self._staging_i ^= 1

        def pad(name, a, fill=0):
            shape = (Sb,) + a.shape[1:]
            buf = stage.get((name,) + shape)
            if buf is None or buf.dtype != a.dtype:
                buf = stage[(name,) + shape] = np.empty(shape, a.dtype)
            buf[:S] = a
            buf[S:] = fill
            return jnp.asarray(buf)

        if sampling.use_counts:
            self._ensure_planes(sampling.vocab)
        if sampling.slot_ids is not None:
            slot_rows = np.where(sampling.slot_ids < 0, self.max_slots,
                                 sampling.slot_ids).astype(np.int32)
        else:
            slot_rows = np.zeros(S, np.int32)
        if prev is not None:
            prev_tok = prev.tokens
        else:
            prev_tok = self._zero_prev.get(self._s_rows)
            if prev_tok is None:
                prev_tok = self._zero_prev[self._s_rows] = jnp.zeros(
                    self._s_rows, jnp.int32)
        Bb = attn_args[2].shape[0]
        Cb = attn_args[0].shape[0] // Bb
        self._seen_buckets.add(
            (Bb, Cb, Sb, int(prev_tok.shape[0]), n_top,
             sampling.use_planes, sampling.use_counts,
             sampling.all_greedy, sampling.need_logprobs))
        (token, lp, top_ids, top_lps, emit), self.k_pages, self.v_pages, \
            self.k_scales, self.v_scales, self.count_planes = \
            self._ragged_sample_jit(
                self.params, self.k_pages, self.v_pages,
                self.k_scales, self.v_scales,
                self.count_planes, *attn_args,
                prev_tok, jnp.asarray(tok_src),
                pad("parent", sampling.parent),
                pad("offsets", sampling.offsets.astype(np.int32)),
                pad("seeds", sampling.seeds),
                pad("counters", sampling.counters),
                pad("temperature", sampling.temperature),
                pad("top_k", sampling.top_k),
                pad("top_p", sampling.top_p),
                pad("min_p", sampling.min_p),
                pad("typical_p", sampling.typical_p, 1),
                pad("freq_pen", sampling.freq_pen),
                pad("pres_pen", sampling.pres_pen),
                pad("rep_pen", sampling.rep_pen),
                pad("bias", sampling.bias),
                pad("counts", sampling.counts),
                pad("slot_rows", slot_rows, self.max_slots),
                pad("mask_bits", sampling.mask_bits, 0xFFFFFFFF),
                pad("draft_toks", sampling.draft_toks, -1),
                pad("win_off", sampling.win_off),
                vocab=sampling.vocab, n_top=n_top,
                use_planes=sampling.use_planes,
                all_greedy=sampling.all_greedy,
                need_logprobs=sampling.need_logprobs,
                use_counts=sampling.use_counts)
        self.n_sampled_tokens += S
        if not sampling.all_greedy:
            for stage, on in optional_stages(
                    sampling.temperature, sampling.top_k,
                    sampling.typical_p).items():
                self.sample_branch_steps[stage] += int(on)
        return StepHandle(tokens=token, logprob=lp, top_ids=top_ids,
                          top_lps=top_lps, n_rows=S, runner=self,
                          emit=emit)

    def fork_seq(self, src_sid: int) -> int:
        """Copy-on-write fork of a live sequence: the new sequence shares
        every *full* page of the source in place (+1 refcount, zero data
        movement) and gets a private copy of the partially filled tail
        page only.  This is what makes ``n``-way sampling nearly free on
        the paged backend — one shared prompt prefill, then n forked
        decode streams.  Returns the new seq_id."""
        src = self.pm.seqs[src_sid]
        alloc = self.pm.new_seq()
        sid = alloc.seq_id
        n_full = src.length // self.page_size
        tail = src.length - n_full * self.page_size
        try:
            if n_full:
                self.pm.share_pages(sid, src.pages[:n_full],
                                    n_full * self.page_size)
            if tail:
                dst = self.pm.fork_page(sid, tail)
                self._copy_page(src.pages[n_full], dst)
        except Exception:
            self.pm.free_seq(sid)
            raise
        self.seq_tokens[sid] = list(
            self.seq_tokens.get(src_sid, ()))[:src.length]
        self.n_forks += 1
        return sid

    def _copy_page(self, src: int, dst: int):
        """Copy one physical page's K/V payload (values AND dequant
        scales, when quantized) across every layer."""
        self.k_pages, self.v_pages, self.k_scales, self.v_scales = \
            self._copy_jit(self.k_pages, self.v_pages, self.k_scales,
                           self.v_scales, src, dst)

    def last_prefill_logits(self) -> np.ndarray:
        return self._last_logits_np

    def decode(self, seq_tokens: Dict[int, int]) -> Dict[int, np.ndarray]:
        """One batched decode step for {seq_id: token}."""
        sids = sorted(seq_tokens)
        B = len(sids)
        # capacity pre-check: fail *before* touching any sequence state so
        # the engine can preempt and retry without corrupted bookkeeping
        growing = sum(1 for s in sids
                      if self.pm.seqs[s].length % self.page_size == 0
                      and self.pm.seqs[s].length // self.page_size
                      == len(self.pm.seqs[s].pages))
        self.pm.require_pages(growing)
        for s in sids:
            if -(-(self.pm.seqs[s].length + 1) // self.page_size) \
                    > self.pm.pages_per_seq:
                raise OutOfPages(f"seq {s} at pages_per_seq cap")
        pos = self.pm.context_lens(sids)               # write position
        for sid in sids:
            self.pm.append_tokens(sid, 1)
        table = self.pm.page_table(sids)
        lens = self.pm.context_lens(sids)              # now includes new tok
        page_idx = np.array(
            [self.pm.seqs[s].pages[p // self.page_size]
             for s, p in zip(sids, pos)], np.int32)
        page_off = (pos % self.page_size).astype(np.int32)
        tok = np.array([[seq_tokens[s]] for s in sids], np.int32)
        logits, self.k_pages, self.v_pages, self.k_scales, self.v_scales = \
            self._step(
                self.params, self.k_pages, self.v_pages, self.k_scales,
                self.v_scales, jnp.asarray(tok),
                jnp.asarray(pos.astype(np.int32)), jnp.asarray(table),
                jnp.asarray(lens), jnp.asarray(page_idx),
                jnp.asarray(page_off))
        for s in sids:
            if s in self.seq_tokens:
                self.seq_tokens[s].append(int(seq_tokens[s]))
        self.n_decode_steps += 1
        self.n_decode_tokens += B
        self.step_log.append(("decode", B))
        out = np.asarray(logits[:, 0].astype(jnp.float32))
        self.host_logit_rows += B
        self.host_sync_bytes += out.nbytes
        return {s: out[i] for i, s in enumerate(sids)}

    def rewind_tokens(self, sid: int, n: int = 1):
        """Un-append the last ``n`` tokens of a live sequence.  Lag-1
        is the pipelined engine's finish rewind (a speculative decode
        row was dispatched for a sequence that turned out to have
        finished one step earlier); lag-k rolls back the rejected tail
        of a speculative verify window (the window's draft tokens were
        appended optimistically so their K/V lands in-step; acceptance
        then keeps a prefix and rewinds the rest).  Drops the tokens
        from ``seq_tokens`` and
        rolls the page cursor back, releasing a now-empty trailing page.
        The caller must have materialized every in-flight step that
        scatters into this sequence first: materialization blocks until
        the step's K/V writes have landed, so a released page can be
        reallocated without a stale write racing its new owner."""
        toks = self.seq_tokens.get(sid)
        if toks is not None and n:
            del toks[len(toks) - n:]
        self.pm.rewind_tokens(sid, n)
        self.n_rewinds += 1

    # -- device-resident penalty count planes ---------------------------
    def _ensure_planes(self, vocab: int):
        if self._plane_vocab != vocab:
            self.count_planes = jnp.zeros(
                (self.max_slots + 1, vocab), jnp.float32)
            self._plane_vocab = vocab

    def seed_counts(self, row: int, counts, vocab: int):
        """Overwrite count-plane row ``row`` from a host ``{token:
        count}`` mapping — called when a penalty-bearing request binds
        (or re-binds, after preemption) a slot, so the in-jit gathers
        see the sequence's true generated-token counts.  Rows of
        released slots are left as garbage: they are only ever read
        after the next penalty-bearing bind re-seeds them."""
        self._ensure_planes(vocab)
        vals = np.zeros(vocab, np.float32)
        for t, c in counts.items():
            if 0 <= t < vocab:
                vals[t] = c
        self.count_planes = self._seed_plane_jit(
            self.count_planes, jnp.asarray(vals), row)

    # -- jit-bucket warmup ----------------------------------------------
    def warmup(self, vocab: int, buckets=None,
               greedy=(False, True), draft_k: int = 0) -> int:
        """Precompile the fused sampled-step jit for the common ragged
        buckets so first-hit compiles stop dominating TTFT.

        Inputs are all-pad (contexts 0, K/V writes to the trash page,
        greedy throwaway samples), so no sequence state, page content,
        or runner step counter is touched.  Shapes and dtypes mirror
        ``_dispatch_sampled`` exactly — a warmed variant IS the steady-
        state variant.  Default buckets cover pure decode at 1 and
        ``max_slots`` rows plus chunked prefill at ``chunk_size``, each
        in both ``all_greedy`` flavors.  With ``draft_k > 0``
        (speculation enabled) the draft-row shapes are covered too:
        verify windows widen decode rows to ``1 + draft_k`` slots and
        multiply the sampling rows, so without these buckets a spec-on
        engine pays its first-hit compiles at serve time.  A bucket may
        be ``(B, C)``, ``(B, C, s_rows)``, or ``(B, C, s_rows,
        prev_rows)`` — the latter two pin the sampling-row count and the
        previous step's token-array length (default: the fixed
        ``_s_rows`` bucket for both, the non-speculative steady state).
        Returns the number of variants compiled (also accumulated in
        ``warmup_compiles``)."""
        ms = max(1, self.max_slots)
        sb = self._bucket(ms)
        if buckets is None:
            cb = self._bucket(max(1, self.chunk_size))
            buckets = [(1, 1), (sb, 1), (sb, cb), (1, cb)]
            if draft_k > 0:
                w = 1 + draft_k
                sd = self._bucket(ms * w)
                buckets += [
                    # all slots (and one slot) carrying verify windows,
                    # fed host-side (prev = the fixed zero array)
                    (sb, self._bucket(w), ms * w),
                    (1, self._bucket(w), w),
                    # plain decode chained AFTER a draft step's handle
                    (sb, 1, ms, sd),
                    (1, 1, 1, sd),
                ]
                # partial windows: the lookup often finds fewer than
                # draft_k tokens, so every power-of-two width below the
                # full window occurs in steady state.  Warm the
                # single-sequence ladder (the common low-traffic case);
                # multi-sequence partial mixes still compile on first
                # hit.
                wb = 2
                while wb < self._bucket(w):
                    buckets.append((1, wb, wb))
                    wb *= 2
        words = -(-vocab // 32)
        f32 = jnp.float32
        compiled = 0
        norm = [(bk[0], bk[1],
                 max(self._s_rows,
                     self._bucket(bk[2])) if len(bk) > 2 else self._s_rows,
                 max(self._s_rows,
                     self._bucket(bk[3])) if len(bk) > 3 else None)
                for bk in buckets]
        for Bb, Cb, Sb, Pb in dict.fromkeys(norm):
            if Pb is None:
                Pb = self._s_rows    # host-fed steps use _zero_prev
            N = Bb * Cb
            attn = (jnp.zeros(N, jnp.int32), jnp.zeros(N, jnp.int32),
                    jnp.zeros((Bb, self.pm.pages_per_seq), jnp.int32),
                    jnp.zeros(Bb, jnp.int32), jnp.zeros(Bb, jnp.int32),
                    jnp.zeros(Bb, jnp.int32),
                    jnp.full(N, self.trash_page, jnp.int32),
                    jnp.zeros(N, jnp.int32))
            for all_greedy in greedy:
                key = (Bb, Cb, Sb, Pb, 0, False, False,
                       bool(all_greedy), False)
                if key in self._seen_buckets:
                    continue
                _, self.k_pages, self.v_pages, self.k_scales, \
                    self.v_scales, self.count_planes = \
                    self._ragged_sample_jit(
                        self.params, self.k_pages, self.v_pages,
                        self.k_scales, self.v_scales,
                        self.count_planes, *attn,
                        jnp.zeros(Pb, jnp.int32),        # prev_tokens
                        jnp.full(N, -1, jnp.int32),      # tok_src
                        jnp.zeros(Sb, jnp.int32),        # parent
                        jnp.zeros(Sb, jnp.int32),        # offsets
                        jnp.zeros(Sb, jnp.uint32),       # seeds
                        jnp.zeros(Sb, jnp.int32),        # counters
                        jnp.zeros(Sb, f32),              # temperature
                        jnp.zeros(Sb, jnp.int32),        # top_k
                        jnp.zeros(Sb, f32),              # top_p
                        jnp.zeros(Sb, f32),              # min_p
                        jnp.ones(Sb, f32),               # typical_p
                        jnp.zeros(Sb, f32),              # freq_pen
                        jnp.zeros(Sb, f32),              # pres_pen
                        jnp.zeros(Sb, f32),              # rep_pen
                        jnp.zeros((Sb, 1), f32),         # bias
                        jnp.zeros((Sb, 1), f32),         # counts
                        jnp.full(Sb, self.max_slots, jnp.int32),
                        jnp.full((Sb, words), 0xFFFFFFFF, jnp.uint32),
                        jnp.full(Sb, -1, jnp.int32),     # draft_toks
                        jnp.zeros(Sb, jnp.int32),        # win_off
                        vocab=vocab, n_top=0, use_planes=False,
                        all_greedy=bool(all_greedy),
                        need_logprobs=False, use_counts=False)
                self._seen_buckets.add(key)
                compiled += 1
        jax.block_until_ready(self.k_pages)   # compiles charged to warmup
        self.n_warmup_compiles += compiled
        return compiled

    def free(self, seq_id: int, publish: bool = False):
        """Release a sequence.  With ``publish=True`` (and the prefix
        cache enabled) its pages are first inserted into the cache so a
        later request sharing the prefix can adopt them.  A sequence
        freed mid-prefill publishes exactly the chunks completed so far —
        this is what lets a preempted prefill resume from its cursor."""
        tokens = self.seq_tokens.pop(seq_id, None)
        if (publish and self.prefix_cache is not None and tokens
                and len(tokens) == self.pm.seqs[seq_id].length):
            self.prefix_cache.insert(tokens, self.pm.seqs[seq_id].pages)
        self.pm.free_seq(seq_id)

    def stats(self) -> dict:
        """Runner counters.  ``attn_kernel_calls`` is the total number of
        attention dispatches (fused ragged steps + legacy per-sequence
        chunk and per-batch decode calls) — the engine path issues
        exactly one per step, so ``attn_kernel_calls / engine exec
        steps`` should be 1.0 (surfaced by the mixed-traffic benchmark
        as ``kernel_calls_per_step``)."""
        out = {"pages": self.pm.stats(),
               "kv_dtype": self.kv_dtype,
               "weight_quant": self.weight_quant,
               "page_bytes": self.page_bytes,
               "prefills": self.n_prefills,
               "forks": self.n_forks,
               "chunk_size": self.chunk_size,
               "prefill_chunks": self.n_prefill_chunks,
               "prefill_tokens": self.n_prefill_tokens,
               "decode_steps": self.n_decode_steps,
               "decode_tokens": self.n_decode_tokens,
               "ragged_steps": self.n_ragged_steps,
               "bucket_tokens": self.n_bucket_tokens,
               "sampled_tokens": self.n_sampled_tokens,
               "sample_branch_steps": dict(self.sample_branch_steps),
               "host_logit_rows": self.host_logit_rows,
               "host_sync_bytes": self.host_sync_bytes,
               "host_block_s": self.t_block_s,
               "jit_buckets": len(self._seen_buckets),
               "warmup_compiles": self.n_warmup_compiles,
               "rewinds": self.n_rewinds,
               "attn_kernel_calls": (self.n_ragged_steps
                                     + self.n_prefill_chunks
                                     + self.n_decode_steps)}
        if self.prefix_cache is not None:
            out["prefix_cache"] = self.prefix_cache.stats()
        return out


class PagedEngineBackend:
    """Slot-keyed unified-runner facade over :class:`PagedModelRunner`.

    ``MLCEngine`` drives every backend through the same calls —
    ``prefill(slot, ids)``, ``decode(tokens_by_slot, pos_by_slot)``,
    ``release(slot)``, ``stats()`` — so the scheduler/engine code is
    backend-agnostic.  The paged backend additionally supports CHUNKED
    prefill (``supports_chunked_prefill``): ``begin_prefill(slot, ids)``
    opens the sequence and adopts the prefix-cache hit, then the engine
    streams the uncached suffix through ragged step rows across as many
    scheduler steps as the token budget allows — and FUSED execution
    (``supports_ragged_step``): ``run_step(rows)`` dispatches a whole
    step plan (every decode token + every prefill chunk) as one ragged
    attention kernel call.  This facade maps engine slots onto paged
    seq_ids, publishes finished (and preempted-mid-prefill) sequences
    into the prefix cache, and frees aborted ones without publishing.
    """

    supports_chunked_prefill = True
    supports_ragged_step = True

    def __init__(self, cfg: ModelConfig, params=None, *, max_slots: int = 4,
                 max_context: int = 256, page_size: int = 16,
                 num_pages: Optional[int] = None, seed: int = 0,
                 enable_prefix_cache: bool = True, chunk_size: int = 16,
                 max_cached_pages: Optional[int] = None,
                 max_cached_bytes: Optional[int] = None,
                 kv_dtype: str = "f32", weight_quant: str = "off"):
        pages_per_seq = -(-max_context // page_size)
        if num_pages is None:
            # room for every slot at full context plus cache headroom
            num_pages = (max_slots + 2) * pages_per_seq
        self.runner = PagedModelRunner(
            cfg, params, num_pages=num_pages, page_size=page_size,
            max_slots=max_slots, pages_per_seq=pages_per_seq, seed=seed,
            enable_prefix_cache=enable_prefix_cache, chunk_size=chunk_size,
            max_cached_pages=max_cached_pages,
            max_cached_bytes=max_cached_bytes,
            kv_dtype=kv_dtype, weight_quant=weight_quant)
        self.cfg = cfg
        self.max_context = max_context
        self.max_slots = max_slots
        self.chunk_size = chunk_size
        self.pm = self.runner.pm
        self.prefix_cache = self.runner.prefix_cache
        self._slot_seq: Dict[int, int] = {}

    @property
    def last_prefill_info(self) -> Dict[str, int]:
        return self.runner.last_prefill_info

    def prefill(self, slot: int, prompt_ids: List[int],
                embeds: Optional[np.ndarray] = None) -> np.ndarray:
        """Whole-prompt prefill (a loop of chunks) — kept for callers
        that don't interleave; the engine uses the chunked calls."""
        assert embeds is None, "paged backend: vision embeds unsupported"
        assert slot not in self._slot_seq, f"slot {slot} already bound"
        sid = self.runner.prefill_seq(prompt_ids)
        self._slot_seq[slot] = sid
        return self.runner.last_prefill_logits()

    def begin_prefill(self, slot: int, prompt_ids: List[int]) -> int:
        """Open ``slot`` for chunked prefill; adopts the longest cached
        prefix and returns how many leading tokens are already in pages
        (the chunk cursor's starting point)."""
        assert slot not in self._slot_seq, f"slot {slot} already bound"
        sid = self.runner.begin_seq(prompt_ids)
        self._slot_seq[slot] = sid
        return self.runner.seq_len(sid)

    def prefill_chunk(self, slot: int, tokens: List[int]) -> np.ndarray:
        """Append one chunk of prompt tokens to ``slot``'s sequence;
        returns the last token's logits."""
        return self.runner.prefill_chunk(self._slot_seq[slot], tokens)

    def run_step(self, rows: List[Tuple[int, List[int], str]],
                 sampling: Optional[SamplingParamsBatch] = None,
                 n_top: int = 0, return_logits: bool = True,
                 materialize: bool = True, prev=None,
                 decode_srcs: Optional[Dict[int, int]] = None):
        """Fused plan execution: ``rows`` are ``(slot, tokens, kind)``
        ragged rows (see :meth:`PagedModelRunner.run_step`); one
        attention kernel call covers them all.  With ``sampling``
        (``parent`` indexes into ``rows``) the step samples on device
        and returns a :class:`SampleResult` — or, with
        ``materialize=False``, a non-blocking :class:`StepHandle` (the
        pipelined engine path; ``prev``/``decode_srcs`` feed decode
        tokens device-to-device from the previous handle, keyed by row
        index, which is invariant under the slot→seq mapping).
        Otherwise per-slot last-valid-token logits return (the
        legacy/test path) — or nothing at all with
        ``return_logits=False``.  Raises :class:`OutOfPages` before any
        state mutates when the pool cannot back the whole step."""
        out = self.runner.run_step(
            [(self._slot_seq[slot], toks, kind)
             for slot, toks, kind in rows],
            sampling=sampling, n_top=n_top, return_logits=return_logits,
            materialize=materialize, prev=prev, decode_srcs=decode_srcs)
        if sampling is not None or not return_logits:
            return out
        return {slot: out[self._slot_seq[slot]] for slot, _, _ in rows}

    def seed_counts(self, slot: int, counts, vocab: int):
        """Seed the device count-plane row for ``slot`` (engine slots
        double as plane rows — both spaces are ``0..max_slots-1``) from
        the host sampler's generated-token counts."""
        self.runner.seed_counts(slot, counts, vocab)

    def rewind_token(self, slot: int, n: int = 1):
        """Lag-``n`` rewind: un-append ``slot``'s last ``n`` tokens
        (page cursors + recorded tokens) — lag-1 covers the pipelined
        finish rewind, lag-k the rejected tail of a speculative verify
        window; see :meth:`PagedModelRunner.rewind_tokens`."""
        self.runner.rewind_tokens(self._slot_seq[slot], n)

    def warmup(self, vocab: int, draft_k: int = 0) -> int:
        """Precompile the common fused-step jit buckets (see
        :meth:`PagedModelRunner.warmup`); ``draft_k > 0`` adds the
        speculative verify-window shapes.  Returns variants compiled."""
        return self.runner.warmup(vocab, draft_k=draft_k)

    def fork_slot(self, src_slot: int, dst_slot: int):
        """CoW-fork ``src_slot``'s sequence into ``dst_slot`` (shared
        prompt KV, private tail) — the n-way sampling fast path."""
        assert dst_slot not in self._slot_seq, \
            f"slot {dst_slot} already bound"
        self._slot_seq[dst_slot] = self.runner.fork_seq(
            self._slot_seq[src_slot])

    def decode(self, tokens_by_slot: Dict[int, int],
               pos_by_slot: Dict[int, int]) -> Dict[int, np.ndarray]:
        del pos_by_slot                    # positions tracked by PageManager
        seq_tok = {self._slot_seq[s]: t for s, t in tokens_by_slot.items()}
        out = self.runner.decode(seq_tok)
        return {s: out[self._slot_seq[s]] for s in tokens_by_slot}

    def release(self, slot: int, publish: bool = True):
        sid = self._slot_seq.pop(slot, None)
        if sid is not None:
            self.runner.free(sid, publish=publish)

    def stats(self) -> dict:
        return self.runner.stats()
