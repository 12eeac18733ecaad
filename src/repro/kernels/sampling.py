"""Batched on-device sampling: one fused logits→token op per step.

This is the device half of the WebLLM lesson that per-token host
round-trips dominate small-batch decode: instead of pulling ``[B, V]``
logits to the host and running a per-sequence numpy softmax/argsort/
``rng.choice`` loop, the whole step's sampling pipeline — logit bias,
frequency/presence/repetition penalties, grammar bitmasks, temperature,
top-k, top-p, and the random draw — runs as ONE compiled op over the
packed ``[S, V]`` logit rows and returns sampled token ids ``[S]``
(plus an optional batched top-logprobs gather), so only ``S`` ints (not
``S×V`` floats) cross the device→host boundary per emitted token.

Everything here is jnp: on the CPU host XLA fuses the pipeline the same
way it executes the interpret-mode Pallas attention kernels; on a TPU
host the op compiles natively and rides the same jitted step as the
fused ragged attention (``PagedModelRunner.run_step``), adding zero
extra dispatches.

Randomness is **counter-based**, not stateful: row ``s`` draws Gumbel
noise from ``fold_in(PRNGKey(seeds[s]), counters[s])`` where the seed is
``request.seed + choice_index`` and the counter is how many tokens that
sequence has sampled so far.  Seeded runs are therefore deterministic
regardless of batch composition, step boundaries, or preempt/resume —
and ``n`` sibling choices are bit-identical to ``n`` independent seeded
requests.  ``temperature == 0`` reduces exactly to argmax (no noise).

Masking uses large *finite* sentinels rather than ``-inf`` so degenerate
rows stay well-defined: grammar-disallowed tokens sit at ``MASKED``
(-1e38) strictly below the ``ALLOWED_FLOOR`` (-1e37) every allowed token
is clamped to, so even when every allowed logit underflows the argmax
still lands on an allowed token (mirroring the host sampler's fixed
degenerate fallback).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: grammar-disallowed tokens are pinned here — strictly below any
#: allowed token, which is clamped to ALLOWED_FLOOR at worst
MASKED = -1e38
#: the worst value an *allowed* token can take after bias/penalties
ALLOWED_FLOOR = -1e37
#: top-k / top-p filtered tokens (allowed by the grammar but cut from
#: the sampling support) — below the floor so Gumbel noise can never
#: resurrect them, but distinct from MASKED for debuggability
FILTERED = -3e37


def unpack_bitmask(mask_bits: jax.Array, vocab: int) -> jax.Array:
    """Unpack ``uint32 [S, ceil(V/32)]`` grammar bitmasks (bit ``v%32``
    of word ``v//32`` = token ``v`` allowed) into bool ``[S, V]`` by
    shifts and a reshape, with no gather."""
    bits = mask_bits[:, :, None] >> jnp.arange(32, dtype=jnp.uint32)
    return (bits & 1).astype(bool).reshape(mask_bits.shape[0], -1)[:, :vocab]


def optional_stages(temperature, top_k, typical_p) -> dict:
    """Whether a stochastic batch runs each conditional stage of
    :func:`batched_sample`: top-k when some sampled row sets ``top_k >
    0``, typical-p when some sampled row sets ``typical_p < 1``.  Works
    on numpy arrays (the runner's ``sample_branch_steps`` counter) and
    on traced ones (the ``lax.cond`` predicates) alike."""
    live = temperature > 0
    return {"top_k": (live & (top_k > 0)).any(),
            "typical_p": (live & (typical_p < 1.0)).any()}


def _prefix_keep(key, limit, mass=None):
    """Keep set of a cumulative-mass cut, in the original token order.

    Tokens are ranked by ``key`` ascending, ties by index (a stable
    order); the cut keeps the longest prefix whose mass BEFORE each
    token is below ``limit [S]`` (numpy searchsorted-left + 1, at least
    one token).  One sort of ``(key, index[, mass])`` gives the prefix's
    last token ``(kv, ki)``; a token is kept iff ``key < kv``, or ``key
    == kv`` and its index is ``<= ki`` — exact, since the stable order
    breaks ties by index, and no inverse permutation or ``[S, V]``
    gather is needed.  ``mass`` rides the sort as a payload; left out,
    it is ``-key`` (the top-p ranking) and the sort carries two operands.
    Returns bool ``keep [S, V]``."""
    idx = jax.lax.broadcasted_iota(jnp.int32, key.shape, 1)
    if mass is None:
        skey, order = jax.lax.sort((key, idx), num_keys=1,
                                   is_stable=True)
        smass = -skey
    else:
        skey, order, smass = jax.lax.sort((key, idx, mass), num_keys=1,
                                          is_stable=True)
    before = jnp.cumsum(smass, axis=-1) - smass
    n = jnp.sum(before < limit[:, None], axis=-1, keepdims=True)
    last = jnp.maximum(n, 1) - 1                            # [S, 1]
    kv = jnp.take_along_axis(skey, last, axis=-1)
    ki = jnp.take_along_axis(order, last, axis=-1)
    return (key < kv) | ((key == kv) & (idx <= ki))


def _penalized(logits, bias, counts, freq_pen, pres_pen, rep_pen,
               mask_bits, use_planes):
    """Bias + penalties + grammar mask, mirroring the host
    ``RequestSampler`` pipeline order exactly (the oracle contract).
    With ``use_planes=False`` (a static batch-level flag: no row has
    bias/penalties) the dense ``[S, V]`` planes are placeholder-shaped
    and the whole penalty stage is skipped — the common hot path
    uploads only per-row scalars and mask words."""
    x = logits.astype(jnp.float32)
    if use_planes:
        x = x + bias
        seen = counts > 0
        x = x - freq_pen[:, None] * counts
        x = jnp.where(seen, x - pres_pen[:, None], x)
        rep = rep_pen[:, None]
        x = jnp.where(seen, jnp.where(x > 0, x / rep, x * rep), x)
    allowed = unpack_bitmask(mask_bits, logits.shape[-1])
    # finite sentinels: allowed tokens never sink below ALLOWED_FLOOR,
    # disallowed ones sit strictly under it — an all-underflow row still
    # argmaxes to an allowed token
    return jnp.where(allowed, jnp.maximum(x, ALLOWED_FLOOR), MASKED)


def batched_accept(tokens, drafts, win_off):
    """Batched speculative acceptance over one step's sampling rows.

    Verification packs each sequence's window of ``k+1`` positions as
    ``k+1`` CONSECUTIVE sampling rows; ``win_off[s]`` is row ``s``'s
    offset inside its window (0 for the window head — and for every
    ordinary non-speculative row, which is just a width-1 window).
    ``drafts[s]`` is the draft token position ``s`` proposed as INPUT to
    the next position, or ``-1`` when there is nothing to check (the
    bonus position at offset ``k``, and all non-speculative rows).

    Row ``s`` is EMITTED iff every earlier row of its window resampled
    exactly its own draft — i.e. the window prefix up to ``s`` is the
    token stream the sequential path would have produced, so row ``s``'s
    (seed, counter) draw saw exactly the sequential logits.  The first
    mismatching row is itself emitted (its fresh draw IS the sequential
    token); everything after it is discarded and rewound.

    Pure jnp over ``[S]`` arrays — rides inside the fused step jit next
    to ``batched_sample``, adding zero dispatches.  Returns ``emit [S]
    bool``.
    """
    miss = ((drafts >= 0) & (tokens != drafts)).astype(jnp.int32)
    # c[j] = number of rejected drafts among rows < j; misses inside
    # this row's window before it = c[s] - c[window_start]
    c = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(miss)])
    idx = jnp.arange(tokens.shape[0])
    before = c[idx] - c[idx - win_off]
    return before == 0


def batched_sample(logits, seeds, counters, temperature, top_k, top_p,
                   min_p, typical_p, freq_pen, pres_pen, rep_pen, bias,
                   counts, mask_bits, *, n_top: int = 0,
                   use_planes: bool = True, all_greedy: bool = False,
                   need_logprobs: bool = True):
    """Sample one token per row of ``logits [S, V]`` in a single device
    op.

    Per-row params (all ``[S]``): ``seeds``/``counters`` drive the
    counter-based PRNG; ``temperature == 0`` is exact argmax; ``top_k ==
    0`` / ``top_p >= 1`` / ``min_p <= 0`` / ``typical_p >= 1`` disable
    those filters (min-p drops tokens whose probability under the
    post-top-k softmax is below ``min_p * max(p)``; typical-p keeps the
    lowest ``|surprisal - entropy|`` tokens until their cumulative mass
    reaches ``typical_p`` — the top token always survives).
    ``bias``/``counts`` are
    dense ``[S, V]`` (logit bias and generated-token counts for the
    frequency/presence/repetition penalties); ``mask_bits`` is the
    packed ``uint32 [S, ceil(V/32)]`` grammar bitmask (all-ones when a
    row is unconstrained).  ``use_planes``, ``all_greedy``, and
    ``need_logprobs`` are STATIC batch-level flags skipping whole
    stages for the common cases: no row carries bias/penalties (planes
    placeholder-shaped, stage skipped), every row has ``temperature ==
    0`` (the sort/softmax/Gumbel stochastic pipeline is skipped), no
    row asked for logprobs (the ``[S, V]`` log-softmax is skipped and
    the logprob outputs are zeros).  The top-k and typical-p stages are
    selected at RUN time instead (``lax.cond`` on
    :func:`optional_stages`), so one program serves every batch and
    their sorts run only when some sampled row asks: the always-on path
    holds one full-vocabulary sort (top-p's) and no ``[S, V]`` gather
    or scatter.

    The draw is Gumbel-max over the filtered distribution: ``argmax(x/T
    + g)`` samples exactly ``softmax(x/T)`` restricted to the surviving
    support, with no renormalization or cumulative-inverse transform —
    and collapses to plain argmax at ``T == 0``.

    Returns ``(token [S] int32, logprob [S] f32, top_ids [S, n_top]
    int32, top_lps [S, n_top] f32)``: ``logprob`` is the sampled token's
    log-probability under the *raw* distribution (pre-bias/penalty/mask,
    the OpenAI ``logprobs`` semantics), and the top arrays are the
    batched ``top_logprobs`` gather (empty when ``n_top == 0``)."""
    S, V = logits.shape
    assert n_top <= V, (n_top, V)
    x = _penalized(logits, bias, counts, freq_pen, pres_pen, rep_pen,
                   mask_bits, use_planes)
    greedy = jnp.argmax(x, axis=-1)

    if all_greedy:
        token = greedy.astype(jnp.int32)
    else:
        # temperature (guarded for the greedy rows), then top-k.  Each
        # filter below is a threshold compared in the original token
        # order; the optional ones sit behind runtime conds, so their
        # sorts run only on steps where some sampled row asks for them
        stages = optional_stages(temperature, top_k, typical_p)
        t = jnp.where(temperature > 0, temperature, 1.0)
        z = x / t[:, None]

        def _top_k(z):
            # the k-th largest value, read from the ascending sort at
            # V - k; ties at it all survive
            srt = jnp.sort(z, axis=-1)
            kth = jnp.take_along_axis(
                srt, (V - jnp.clip(top_k, 1, V))[:, None], axis=-1)
            return jnp.where((top_k > 0)[:, None] & (z < kth), FILTERED, z)

        z = jax.lax.cond(stages["top_k"], _top_k, lambda z: z, z)

        # top-p over the softmax of the surviving support: the cut is a
        # prefix of the probability-descending stable order (token j
        # survives iff the cumulative mass BEFORE it is < p);
        # top_p >= 1 disables the filter entirely (the host-oracle
        # semantics): float32 cumsum rounding must not cut a real tail
        # token
        m = jnp.max(z, axis=-1, keepdims=True)
        e = jnp.exp(z - m)
        p = e / jnp.sum(e, axis=-1, keepdims=True)
        keep = _prefix_keep(-p, top_p) | (top_p >= 1.0)[:, None]
        # min-p on the SAME pre-filter probs: token survives iff
        # p >= min_p * max(p); min_p <= 0 disables the filter
        pmax = jnp.max(p, axis=-1, keepdims=True)
        keep = keep & ((p >= min_p[:, None] * pmax)
                       | (min_p <= 0.0)[:, None])

        # typical-p (locally typical sampling) on the SAME pre-filter
        # probs: rank tokens by |surprisal − entropy| ascending and keep
        # until their cumulative mass reaches typical_p (same
        # searchsorted-left + 1 rule as top-p, in deviation order);
        # typical_p >= 1 disables, and the most-typical token always
        # survives its own filter (the host cutoff is max(1, ...))
        def _typical(p):
            surp = -jnp.log(jnp.where(p > 0, p, 1.0))
            ent = jnp.sum(p * surp, axis=-1, keepdims=True)
            dev = jnp.where(p > 0, jnp.abs(surp - ent), jnp.inf)
            return (_prefix_keep(dev, typical_p, p)
                    | (typical_p >= 1.0)[:, None])

        keep = keep & jax.lax.cond(
            stages["typical_p"], _typical,
            lambda p: jnp.ones(p.shape, bool), p)

        # the host keeps AT LEAST the max-probability token: top-p/min-p
        # keep it by construction (max(1, cutoff)), the typical filter
        # may not — a degenerate combination must degrade to top-1, not
        # filter everything
        iota = jax.lax.broadcasted_iota(jnp.int32, (S, V), 1)
        keep = keep | (iota == jnp.argmax(p, axis=-1)[:, None])
        z = jnp.where(keep, z, FILTERED)

        # counter-based per-row keys: deterministic for a (seed,
        # counter) pair no matter how rows are batched across steps
        def _noise(seed, counter):
            key = jax.random.fold_in(jax.random.PRNGKey(seed), counter)
            return jax.random.gumbel(key, (V,), jnp.float32)

        g = jax.vmap(_noise)(seeds, counters)
        stoch = jnp.argmax(z + g, axis=-1)
        token = jnp.where(temperature == 0.0, greedy,
                          stoch).astype(jnp.int32)

    # raw-distribution logprobs (the OpenAI semantics: what the model
    # believed, not what the filters allowed); skipped as a whole when
    # no row in the batch asked
    if need_logprobs or n_top > 0:
        ls = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        lp = jnp.take_along_axis(ls, token[:, None].astype(jnp.int32),
                                 axis=-1)[:, 0]
    else:
        lp = jnp.zeros((S,), jnp.float32)
    if n_top > 0:
        top_lps, top_ids = jax.lax.top_k(ls, n_top)
        top_ids = top_ids.astype(jnp.int32)
    else:
        top_ids = jnp.zeros((S, 0), jnp.int32)
        top_lps = jnp.zeros((S, 0), jnp.float32)
    return token, lp, top_ids, top_lps
