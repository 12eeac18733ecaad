"""Batched on-device sampling vs the host RequestSampler oracle.

The device op (``kernels.sampling.batched_sample``) must agree with
``core/sampler.RequestSampler`` — the dense-backend fallback — across
the whole parameter space: greedy results exactly, stochastic results at
the distribution level (same support, empirical frequencies matching
``RequestSampler.dist``), with counter-based determinism."""
import numpy as np
import pytest

try:                       # hypothesis widens the sweep when available;
    from hypothesis import given, settings    # the oracle equivalence
    from hypothesis import strategies as st   # itself must run in every
    _HYP = True                               # environment (tier-1)
except ImportError:
    _HYP = False


def _sweep(fn):
    """Hypothesis-driven data_seed sweep when installed, a fixed seed
    grid otherwise — the device-vs-oracle contract is tier-1 either
    way."""
    if _HYP:
        return settings(max_examples=30, deadline=None)(
            given(data_seed=st.integers(0, 2**31 - 1))(fn))
    return pytest.mark.parametrize("data_seed", list(range(12)))(fn)


from repro.core.sampler import RequestSampler, SamplingParamsBatch
from repro.grammar.matcher import pack_token_bitmask
from repro.kernels import ref
from repro.kernels.ops import batched_sample

V = 32
S = 8          # fixed row count so hypothesis examples share one jit


def _device(batch: SamplingParamsBatch, logits: np.ndarray, n_top=0):
    """Run the standalone fused sampling op on pre-gathered rows."""
    out = batched_sample(
        logits[batch.parent].astype(np.float32), batch.seeds,
        batch.counters, batch.temperature, batch.top_k, batch.top_p,
        batch.min_p, batch.typical_p, batch.freq_pen, batch.pres_pen,
        batch.rep_pen, batch.bias, batch.counts, batch.mask_bits,
        n_top=n_top, use_planes=batch.use_planes)
    return tuple(np.asarray(x) for x in out)


def _sampler(rng, *, temperature) -> RequestSampler:
    s = RequestSampler(
        temperature=temperature,
        top_k=int(rng.integers(0, V + 1)),
        top_p=float(rng.uniform(0.05, 1.0)) if rng.random() < 0.7 else 1.0,
        min_p=float(rng.uniform(0.0, 0.5)) if rng.random() < 0.5 else 0.0,
        typical_p=(float(rng.uniform(0.2, 1.0))
                   if rng.random() < 0.5 else 1.0),
        frequency_penalty=float(rng.uniform(0, 1.5)),
        presence_penalty=float(rng.uniform(0, 1.5)),
        repetition_penalty=float(rng.choice([1.0, 0.7, 1.8])),
        logit_bias=({int(rng.integers(0, V)): float(rng.uniform(-5, 5))}
                    if rng.random() < 0.5 else None),
        seed=int(rng.integers(0, 2**31 - 1)))
    for _ in range(int(rng.integers(0, 6))):
        s.observe(int(rng.integers(0, V)))   # populate penalty counts
    return s


def _mask(rng):
    if rng.random() < 0.5:
        return None
    m = rng.random(V) < 0.4
    if not m.any():
        m[int(rng.integers(0, V))] = True
    return m


def _case(data_seed: int, temperature: float):
    rng = np.random.default_rng(data_seed)
    logits = (rng.standard_normal((S, V)) * 3).astype(np.float32)
    samplers = [_sampler(rng, temperature=temperature) for _ in range(S)]
    masks = [_mask(rng) for _ in range(S)]
    specs = [(i, samplers[i],
              None if masks[i] is None else pack_token_bitmask(masks[i]))
             for i in range(S)]
    return logits, samplers, masks, SamplingParamsBatch.build(specs, V)


@_sweep
def test_greedy_matches_host_oracle_exactly(data_seed):
    """temperature=0 across random bias/penalty/mask combos: the device
    op and the host sampler pick the SAME token."""
    logits, samplers, masks, batch = _case(data_seed, temperature=0.0)
    tokens, _, _, _ = _device(batch, logits)
    for i in range(S):
        assert int(tokens[i]) == samplers[i].sample(logits[i], masks[i]), i


@_sweep
def test_stochastic_support_and_ref_equivalence(data_seed):
    """temperature>0: every device-sampled token lies in the host
    oracle's final distribution support, and the batched op matches the
    row-at-a-time reference implementation token-for-token."""
    logits, samplers, masks, batch = _case(data_seed, temperature=0.9)
    tokens, lp, top_ids, top_lps = _device(batch, logits, n_top=4)
    rtok, rlp, rtids, rtlps = ref.batched_sample_ref(
        logits[batch.parent], batch.seeds, batch.counters,
        batch.temperature, batch.top_k, batch.top_p, batch.min_p,
        batch.typical_p, batch.freq_pen, batch.pres_pen, batch.rep_pen,
        batch.bias, batch.counts, batch.mask_bits, n_top=4)
    assert np.array_equal(tokens, rtok)
    np.testing.assert_allclose(lp, rlp, atol=1e-5)
    np.testing.assert_allclose(top_lps, rtlps, atol=1e-5)
    for i in range(S):
        dist = samplers[i].dist(logits[i], masks[i])
        assert dist[int(tokens[i])] > 0, (i, int(tokens[i]))
        if masks[i] is not None:
            assert masks[i][int(tokens[i])], i


def test_empirical_distribution_matches_oracle():
    """512 counter-indexed draws from one row: empirical frequencies
    within total-variation tolerance of ``RequestSampler.dist`` (the
    exact distribution the host fallback samples from)."""
    rng = np.random.default_rng(0)
    logits_row = (rng.standard_normal(V) * 2).astype(np.float32)
    sampler = RequestSampler(temperature=1.1, top_k=12, top_p=0.9,
                             seed=123)
    n = 512
    specs = [(0, sampler, None)] * n
    batch = SamplingParamsBatch.build(specs, V)
    batch.counters[:] = np.arange(n)       # counter-based: distinct draws
    tokens, _, _, _ = _device(batch, logits_row[None])
    freq = np.bincount(tokens, minlength=V) / n
    dist = sampler.dist(logits_row)
    tv = 0.5 * np.abs(freq - dist).sum()
    assert tv < 0.12, tv
    # filtered-out tokens are never sampled
    assert set(np.flatnonzero(freq)) <= set(np.flatnonzero(dist))


def test_counter_based_determinism():
    """Same (seed, counter) -> same token regardless of batching;
    distinct counters actually vary the draw."""
    rng = np.random.default_rng(1)
    logits = (rng.standard_normal((S, V))).astype(np.float32)
    mk = lambda: RequestSampler(temperature=1.5, seed=42)  # noqa: E731
    batch1 = SamplingParamsBatch.build([(i, mk(), None)
                                        for i in range(S)], V)
    batch1.counters[:] = np.arange(S)
    batch2 = SamplingParamsBatch.build([(i, mk(), None)
                                        for i in range(S)], V)
    batch2.counters[:] = np.arange(S)
    t1, _, _, _ = _device(batch1, logits)
    t2, _, _, _ = _device(batch2, logits)
    assert np.array_equal(t1, t2)
    # one row re-drawn under successive counters is not constant
    row = np.tile(logits[:1], (S, 1))
    b3 = SamplingParamsBatch.build([(i, mk(), None)
                                    for i in range(S)], V)
    b3.counters[:] = np.arange(S)
    t3, _, _, _ = _device(b3, row)
    assert len(set(int(t) for t in t3)) > 1


def test_planeless_batch_matches_dense_planes():
    """A batch with no bias/penalties builds placeholder [S, 1] planes
    (use_planes=False — no 2·S·V upload) and samples exactly like the
    dense-plane variant with all-zero planes."""
    rng = np.random.default_rng(5)
    logits = rng.standard_normal((S, V)).astype(np.float32)
    mk = (lambda i: RequestSampler(temperature=0.8, top_k=10,
                                   top_p=0.9, seed=i))
    batch = SamplingParamsBatch.build([(i, mk(i), None)
                                       for i in range(S)], V)
    assert batch.use_planes is False
    assert batch.bias.shape == (S, 1) and batch.counts.shape == (S, 1)
    lean, _, _, _ = _device(batch, logits)
    dense = np.asarray(batched_sample(
        logits, batch.seeds, batch.counters, batch.temperature,
        batch.top_k, batch.top_p, batch.min_p, batch.typical_p,
        batch.freq_pen,
        batch.pres_pen, batch.rep_pen, np.zeros((S, V), np.float32),
        np.zeros((S, V), np.float32), batch.mask_bits,
        use_planes=True)[0])
    assert np.array_equal(lean, dense)
    # a logit_bias row flips the whole batch to dense planes
    biased = mk(0)
    biased.logit_bias = {3: 2.0}
    b2 = SamplingParamsBatch.build(
        [(0, biased, None)] + [(i, mk(i), None) for i in range(1, S)], V)
    assert b2.use_planes is True and b2.bias.shape == (S, V)


def test_top_p_one_never_filters():
    """top_p == 1.0 disables the nucleus filter (host semantics): even
    the tiniest-probability token must stay sampleable despite float32
    cumsum rounding."""
    logits = np.zeros((1, V), np.float32)
    logits[0, 0] = 20.0                        # rest of the mass ~1e-9
    s = RequestSampler(temperature=1.0, top_p=1.0, seed=0)
    n = 256
    batch = SamplingParamsBatch.build([(0, s, None)] * n, V)
    batch.counters[:] = np.arange(n)
    tokens, _, _, _ = _device(batch, logits)
    # the dominant token wins essentially always, but nothing errors
    # and any draw that does land elsewhere is legal
    assert ((tokens >= 0) & (tokens < V)).all()
    # the filter truly kept everything: a near-uniform row with
    # top_p=1.0 must reach tail tokens across draws
    flat = np.linspace(0, 0.01, V, dtype=np.float32)[None]
    t2, _, _, _ = _device(batch, flat)
    assert len(set(int(t) for t in t2)) > V // 2


def test_top_p_zero_degrades_to_top1_respecting_mask():
    """Regression: top_p <= 0 used to filter EVERY token on device
    (argmax of all-FILTERED returned token 0, ignoring the grammar).
    Host semantics keep at least the top token — device must match."""
    mask = np.zeros(V, bool)
    mask[3] = mask[11] = True
    logits = np.zeros((1, V), np.float32)
    logits[0, 11] = 5.0                        # top allowed token
    s = RequestSampler(temperature=1.0, top_p=0.0, seed=0)
    batch = SamplingParamsBatch.build(
        [(0, s, pack_token_bitmask(mask))] * 8, V)
    batch.counters[:] = np.arange(8)
    tokens, _, _, _ = _device(batch, logits)
    assert (tokens == 11).all(), tokens        # top-1 allowed, every draw


def test_top_k_above_vocab_is_disabled_on_host_too():
    """Regression: host dist() used to raise ValueError on
    top_k > vocab while the device op clamps — both must treat it as
    'filter disabled'."""
    rng = np.random.default_rng(7)
    logits = rng.standard_normal(V).astype(np.float32)
    s = RequestSampler(temperature=1.0, top_k=10 * V, seed=0)
    off = RequestSampler(temperature=1.0, top_k=0, seed=0)
    np.testing.assert_allclose(s.dist(logits), off.dist(logits))
    assert 0 <= s.sample(logits) < V


def test_bitmask_pack_roundtrip():
    rng = np.random.default_rng(2)
    for v in (1, 31, 32, 33, 100):
        m = rng.random(v) < 0.5
        packed = pack_token_bitmask(m)
        assert packed.shape == (-(-v // 32),)
        idx = np.arange(v)
        unpacked = (packed[idx // 32] >> (idx % 32).astype(np.uint32)) & 1
        assert np.array_equal(unpacked.astype(bool), m)


def test_min_p_filters_tail_and_matches_host_support():
    """min_p drops exactly the tokens with p < min_p * max(p) from both
    the host dist and the device support; empirical device draws stay
    inside it."""
    # probs ~ softmax([4, 3, 2, 1, 0, ...]): ratios to max are
    # 1, e^-1 (.37), e^-2 (.135), e^-3 (.05), ...
    logits = np.full((1, V), -40.0, np.float32)
    logits[0, :5] = np.array([4, 3, 2, 1, 0], np.float32)
    s = RequestSampler(temperature=1.0, min_p=0.2, seed=7)
    dist = s.dist(logits[0])
    assert set(np.flatnonzero(dist)) == {0, 1}      # .37 in, .135 out
    n = 256
    batch = SamplingParamsBatch.build([(0, s, None)] * n, V)
    batch.counters[:] = np.arange(n)
    tokens, _, _, _ = _device(batch, logits)
    assert set(int(t) for t in tokens) <= {0, 1}
    assert len(set(int(t) for t in tokens)) == 2    # both actually drawn
    # min_p=0 is an exact no-op: same dist as a min_p-less sampler
    s0 = RequestSampler(temperature=1.0, min_p=0.0, seed=7)
    base = RequestSampler(temperature=1.0, seed=7)
    np.testing.assert_array_equal(s0.dist(logits[0]), base.dist(logits[0]))


def test_min_p_one_degrades_to_top1():
    """min_p=1.0 keeps only max-probability tokens — argmax-like, but
    still a draw among exact ties; the top token always survives."""
    rng = np.random.default_rng(11)
    logits = rng.standard_normal((1, V)).astype(np.float32) * 3
    s = RequestSampler(temperature=1.3, min_p=1.0, seed=5)
    dist = s.dist(logits[0])
    assert set(np.flatnonzero(dist)) == {int(np.argmax(logits[0]))}
    batch = SamplingParamsBatch.build([(0, s, None)] * 8, V)
    batch.counters[:] = np.arange(8)
    tokens, _, _, _ = _device(batch, logits)
    assert (tokens == int(np.argmax(logits[0]))).all()
    # out-of-range request values clamp instead of emptying the support
    assert RequestSampler(temperature=1.0, min_p=7.5).min_p == 1.0
    assert RequestSampler(temperature=1.0, min_p=-3.0).min_p == 0.0


def test_min_p_composes_with_top_p_and_grammar_mask():
    """min_p and top_p filter the SAME pre-filter probs and the result
    respects the grammar mask — device ≡ ref token-for-token, and every
    draw is mask-allowed."""
    rng = np.random.default_rng(13)
    logits = (rng.standard_normal((S, V)) * 3).astype(np.float32)
    mask = np.zeros(V, bool)
    mask[: V // 2] = True
    samplers = [RequestSampler(temperature=0.9, top_p=0.8, min_p=0.1,
                               seed=i) for i in range(S)]
    specs = [(i, samplers[i], pack_token_bitmask(mask)) for i in range(S)]
    batch = SamplingParamsBatch.build(specs, V)
    tokens, lp, _, _ = _device(batch, logits)
    rtok, rlp, _, _ = ref.batched_sample_ref(
        logits[batch.parent], batch.seeds, batch.counters,
        batch.temperature, batch.top_k, batch.top_p, batch.min_p,
        batch.typical_p, batch.freq_pen, batch.pres_pen, batch.rep_pen,
        batch.bias, batch.counts, batch.mask_bits)
    assert np.array_equal(tokens, rtok)
    for i in range(S):
        assert mask[int(tokens[i])], i
        assert samplers[i].dist(logits[i], mask)[int(tokens[i])] > 0, i


def test_typical_p_one_is_noop():
    """typical_p=1.0 (the default) disables the filter exactly: same
    host dist, same device draws as a sampler that never heard of it."""
    rng = np.random.default_rng(17)
    logits = (rng.standard_normal((S, V)) * 3).astype(np.float32)
    mk = lambda tp: [RequestSampler(temperature=0.9, top_p=0.9,  # noqa: E731
                                    typical_p=tp, seed=i) for i in range(S)]
    on, off = mk(1.0), mk(1.0)
    np.testing.assert_array_equal(on[0].dist(logits[0]),
                                  RequestSampler(temperature=0.9,
                                                 top_p=0.9,
                                                 seed=0).dist(logits[0]))
    b1 = SamplingParamsBatch.build([(i, on[i], None)
                                    for i in range(S)], V)
    b2 = SamplingParamsBatch.build([(i, off[i], None)
                                    for i in range(S)], V)
    t1, _, _, _ = _device(b1, logits)
    t2, _, _, _ = _device(b2, logits)
    assert np.array_equal(t1, t2)
    # out-of-range request values clamp instead of misbehaving
    assert RequestSampler(temperature=1.0, typical_p=7.5).typical_p == 1.0
    assert RequestSampler(temperature=1.0, typical_p=-3.0).typical_p == 0.0


def test_typical_p_filters_atypical_tail():
    """probs ~ (0.735, 0.245, 0.020): deviation order is 0, 1, 2, so
    typical_p=0.9 keeps {0, 1} and drops the surprising tail — host
    dist and device support agree."""
    logits = np.full((1, V), -40.0, np.float32)
    logits[0, :3] = np.array([2.0, 0.9, -1.6], np.float32)
    s = RequestSampler(temperature=1.0, typical_p=0.9, seed=19)
    dist = s.dist(logits[0])
    assert set(np.flatnonzero(dist)) == {0, 1}
    n = 256
    batch = SamplingParamsBatch.build([(0, s, None)] * n, V)
    batch.counters[:] = np.arange(n)
    tokens, _, _, _ = _device(batch, logits)
    assert set(int(t) for t in tokens) == {0, 1}   # both actually drawn


def test_typical_p_excluding_mode_still_keeps_top1():
    """probs ~ (0.4, 0.1 x 6): the six tail tokens are MORE typical
    than the mode (devs 0.55 vs 0.83), so typical_p=0.5 keeps five of
    them and would drop the mode — the forced top-1 keeps it, and
    device ≡ ref token-for-token on the composed support."""
    logits = np.full((1, V), -40.0, np.float32)
    logits[0, 0] = float(np.log(4.0))
    logits[0, 1:7] = 0.0
    s = RequestSampler(temperature=1.0, typical_p=0.5, seed=23)
    dist = s.dist(logits[0])
    # deviation-ascending cumulative mass crosses 0.5 at the fifth tail
    # token; the mode (token 0) rides in on the top-1 guarantee
    assert set(np.flatnonzero(dist)) == {0, 1, 2, 3, 4, 5}
    n = 256
    batch = SamplingParamsBatch.build([(0, s, None)] * n, V)
    batch.counters[:] = np.arange(n)
    tokens, _, _, _ = _device(batch, logits)
    rtok, _, _, _ = ref.batched_sample_ref(
        np.tile(logits, (n, 1)), batch.seeds, batch.counters,
        batch.temperature, batch.top_k, batch.top_p, batch.min_p,
        batch.typical_p, batch.freq_pen, batch.pres_pen, batch.rep_pen,
        batch.bias, batch.counts, batch.mask_bits)
    assert np.array_equal(tokens, rtok)
    assert set(int(t) for t in tokens) <= {0, 1, 2, 3, 4, 5}


def test_typical_p_plumbs_request_to_batch():
    """The API field flows through RequestSampler into the packed
    device batch; the default stays 'disabled'."""
    from repro.core import api
    req = api.ChatCompletionRequest(messages=[], typical_p=0.7)
    assert req.typical_p == 0.7
    s = RequestSampler(temperature=1.0, typical_p=0.7, seed=0)
    batch = SamplingParamsBatch.build([(0, s, None)], V)
    assert batch.typical_p[0] == np.float32(0.7)
    assert SamplingParamsBatch.build(
        [(0, RequestSampler(seed=0), None)], V).typical_p[0] == 1.0


def test_typical_p_end_to_end_engine():
    """A typical_p request runs the whole fused paged path (engine →
    SamplingParamsBatch → on-device filter) and generates."""
    from repro.configs import get_config
    from repro.core import ChatCompletionRequest, ChatMessage, MLCEngine
    eng = MLCEngine()
    eng.load_model("m", get_config("llama-3.1-8b", reduced=True),
                   max_slots=2, max_context=96, seed=0,
                   backend="paged", page_size=8)
    try:
        resp = eng.chat_completions_create(ChatCompletionRequest(
            messages=[ChatMessage("user", "hi")], model="m",
            max_tokens=4, temperature=0.9, typical_p=0.85, seed=1))
        assert resp.choices[0].message.content
    finally:
        eng.shutdown()


def test_grammar_mask_respected_even_when_allowed_underflow():
    """All allowed logits at -inf (bias-driven underflow): the sampled
    token must STILL be grammar-allowed — the device op's finite
    sentinel ordering and the host fallback agree."""
    mask = np.zeros(V, bool)
    mask[5] = mask[9] = True
    sampler = RequestSampler(temperature=0.0, seed=0,
                             logit_bias={5: float("-inf"),
                                         9: float("-inf")})
    logits = np.zeros((1, V), np.float32)
    host = sampler.sample(logits[0], mask)
    assert mask[host]
    batch = SamplingParamsBatch.build(
        [(0, sampler, pack_token_bitmask(mask))], V)
    tokens, _, _, _ = _device(batch, logits)
    assert mask[int(tokens[0])]
    assert int(tokens[0]) == host


# -- threshold-form filters: exactness at the cut -------------------------

def _draws(samplers, logits, masks=None, n_rounds=1):
    """Device and ref tokens for ``samplers`` (row i draws from
    ``logits[i]``) over ``n_rounds`` successive counters."""
    v = logits.shape[-1]
    masks = masks or [None] * len(samplers)
    specs = [(i, s, None if m is None else pack_token_bitmask(m))
             for i, (s, m) in enumerate(zip(samplers, masks))]
    dev, oracle = [], []
    for r in range(n_rounds):
        batch = SamplingParamsBatch.build(specs, v)
        batch.counters[:] = r
        dev.append(_device(batch, logits)[0])
        oracle.append(ref.batched_sample_ref(
            logits[batch.parent], batch.seeds, batch.counters,
            batch.temperature, batch.top_k, batch.top_p, batch.min_p,
            batch.typical_p, batch.freq_pen, batch.pres_pen,
            batch.rep_pen, batch.bias, batch.counts, batch.mask_bits)[0])
    return np.stack(dev), np.stack(oracle)


def test_top_p_cut_inside_a_tie_group():
    """Tokens of EQUAL probability on both sides of the top-p cut: the
    stable order keeps the lower indices of the tie group, on device as
    in the ref and the host dist."""
    logits = np.full((1, V), -40.0, np.float32)
    logits[0, 7] = 3.0
    tied = [2, 9, 20, 30]                  # p ~ .405, then 4 x .149
    logits[0, tied] = 2.0
    for top_p, kept in ((0.5, {7, 2}), (0.6, {7, 2, 9}),
                        (0.75, {7, 2, 9, 20})):
        n = 128
        samplers = [RequestSampler(temperature=1.0, top_p=top_p, seed=i)
                    for i in range(n)]
        assert set(np.flatnonzero(samplers[0].dist(logits[0]))) == kept
        dev, oracle = _draws(samplers, np.tile(logits, (n, 1)))
        assert np.array_equal(dev, oracle), top_p
        assert set(int(t) for t in dev.ravel()) == kept, top_p


def test_underflowed_tails_match_ref():
    """Tails whose probability underflows to exactly 0 (ties at p = 0,
    deviation inf): top-p just below 1 cuts into them, typical-p ranks
    them last, min-p drops them — device ≡ ref token for token."""
    rng = np.random.default_rng(29)
    logits = np.full((S, V), -150.0, np.float32)
    logits[:, :6] = rng.standard_normal((S, 6)).astype(np.float32)
    below_one = float(np.nextafter(np.float32(1), np.float32(0)))
    kw = [dict(top_p=below_one), dict(top_p=1.0), dict(typical_p=0.7),
          dict(typical_p=below_one), dict(min_p=0.05),
          dict(top_p=below_one, typical_p=0.9, min_p=0.01),
          dict(top_k=V, top_p=0.9), dict(top_k=3, typical_p=0.5)]
    samplers = [RequestSampler(temperature=0.7, seed=100 + i, **kw[i])
                for i in range(S)]
    dev, oracle = _draws(samplers, logits, n_rounds=16)
    assert np.array_equal(dev, oracle)
    assert (dev < 6).all()


def test_top_k_ties_at_the_kth_value_all_survive():
    """Ties AT the k-th value: every token equal to it survives (the
    host's ``z < kth`` rule), on device as in the ref and the host."""
    logits = np.full((1, V), -40.0, np.float32)
    logits[0, 4] = 2.5
    logits[0, [1, 12, 25]] = 1.5           # k = 2 cuts inside these ties
    n = 128
    samplers = [RequestSampler(temperature=1.5, top_k=2, seed=i)
                for i in range(n)]
    support = {4, 1, 12, 25}
    assert set(np.flatnonzero(samplers[0].dist(logits[0]))) == support
    dev, oracle = _draws(samplers, np.tile(logits, (n, 1)))
    assert np.array_equal(dev, oracle)
    assert set(int(t) for t in dev.ravel()) == support


def test_mixed_optional_stages_in_one_batch():
    """One batch whose rows mix top_k 0 / > 0, typical_p 1 / < 1 and
    min_p 0 / > 0 (all eight combinations): device ≡ ref, and a row that
    asks for no optional stage draws the same token whether or not its
    neighbours switch the runtime conds on."""
    rng = np.random.default_rng(31)
    logits = (rng.standard_normal((S, V)) * 2).astype(np.float32)
    combos = [(k, t, m) for k in (0, 6) for t in (1.0, 0.6)
              for m in (0.0, 0.1)]
    mk = lambda i, k, t, m: RequestSampler(  # noqa: E731
        temperature=0.9, top_p=0.9, top_k=k, typical_p=t, min_p=m,
        seed=1000 + i)
    samplers = [mk(i, *c) for i, c in enumerate(combos)]
    dev, oracle = _draws(samplers, logits, n_rounds=8)
    assert np.array_equal(dev, oracle)
    plain = [mk(i, 0, 1.0, m) for i, (_, _, m) in enumerate(combos)]
    alone, _ = _draws(plain, logits, n_rounds=8)
    assert np.array_equal(alone[:, 0], dev[:, 0])      # combos[0] is plain
    assert np.array_equal(alone[:, 1], dev[:, 1])      # min-p only


def test_grammar_row_beside_unmasked_rows_odd_vocab():
    """A grammar-masked row (allowing tokens in both bitmask words)
    beside unmasked rows at a vocabulary that is not a multiple of 32:
    the shift-and-reshape unpack equals the word/bit definition, the
    masked row draws only allowed tokens, and device ≡ ref."""
    from repro.kernels.sampling import unpack_bitmask
    v = 45
    rng = np.random.default_rng(37)
    bits = rng.random((4, v)) < 0.5
    packed = np.stack([pack_token_bitmask(b) for b in bits])
    np.testing.assert_array_equal(np.asarray(unpack_bitmask(packed, v)),
                                  bits)
    logits = (rng.standard_normal((4, v)) * 2).astype(np.float32)
    mask = np.zeros(v, bool)
    mask[[3, 31, 32, 40, 44]] = True
    masks = [None, mask, None, None]
    samplers = [RequestSampler(temperature=t, top_p=0.95, seed=50 + i)
                for i, t in enumerate((0.8, 0.8, 0.0, 1.2))]
    dev, oracle = _draws(samplers, logits, masks, n_rounds=16)
    assert np.array_equal(dev, oracle)
    assert all(mask[t] for t in dev[:, 1])
    assert (dev < v).all()


def test_prefix_keep_matches_the_sorted_oracle():
    """The threshold form of a cumulative-mass cut keeps exactly the
    stable-order prefix the permute-back form keeps, through heavy ties
    in the key and zero-mass tails (dyadic masses: no rounding)."""
    from repro.kernels.sampling import _prefix_keep
    rng = np.random.default_rng(41)
    rows, v = 16, 64
    key = rng.integers(0, 6, (rows, v)).astype(np.float32)
    mass = rng.integers(0, 4, (rows, v)).astype(np.float32) / 256
    limit = rng.choice([0.0, 1 / 256, 0.1, 0.25, 0.5, 2.0], rows)
    limit = limit.astype(np.float32)
    want = np.zeros((rows, v), bool)
    for s in range(rows):
        order = np.argsort(key[s], kind="stable")
        before = np.cumsum(mass[s][order]) - mass[s][order]
        n = max(int((before < limit[s]).sum()), 1)
        want[s, order[:n]] = True
    np.testing.assert_array_equal(
        np.asarray(_prefix_keep(key, limit, mass)), want)


def test_sample_branch_steps_counts_conditional_stages():
    """``stats()["runner"]["sample_branch_steps"]`` counts the
    stochastic steps that ran each runtime-conditional stage: a top-k
    request counts top_k only, a typical-p request typical_p only, and a
    greedy request nothing, whatever it sets."""
    from repro.configs import get_config
    from repro.core import ChatCompletionRequest, ChatMessage, MLCEngine
    eng = MLCEngine()
    eng.load_model("m", get_config("llama-3.1-8b", reduced=True),
                   max_slots=2, max_context=96, seed=0,
                   backend="paged", page_size=8)

    def run(**kw):
        eng.chat_completions_create(ChatCompletionRequest(
            messages=[ChatMessage("user", "hi")], model="m",
            max_tokens=3, seed=1, **kw))
        return dict(eng.stats("m")["runner"]["sample_branch_steps"])

    try:
        assert run(temperature=0.9, top_p=0.9) == {"top_k": 0,
                                                   "typical_p": 0}
        a = run(temperature=0.9, top_k=5)
        assert a["top_k"] >= 1 and a["typical_p"] == 0, a
        b = run(temperature=0.9, typical_p=0.8)
        assert b["top_k"] == a["top_k"] and b["typical_p"] >= 1, b
        assert run(temperature=0.0, top_k=5, typical_p=0.5) == b
    finally:
        eng.shutdown()
