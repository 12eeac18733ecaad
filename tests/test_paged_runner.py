"""Paged decode path (Pallas paged-attention kernel e2e) vs dense oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.paged_runner import PagedModelRunner, paged_supported
from repro.models import model
from repro.models.pdef import init_params


def test_supported_matrix():
    assert paged_supported(get_config("yi-6b"))
    assert paged_supported(get_config("llama-3.1-8b"))
    assert not paged_supported(get_config("jamba-1.5-large-398b"))
    assert not paged_supported(get_config("whisper-base"))
    assert not paged_supported(get_config("deepseek-v2-lite-16b"))


@pytest.mark.parametrize("arch", ["yi-6b", "mistral-nemo-12b"])
def test_paged_decode_matches_dense(arch, rng_key):
    cfg = get_config(arch, reduced=True)
    params = init_params(model.params_def(cfg), rng_key)
    pr = PagedModelRunner(cfg, params, num_pages=32, page_size=8,
                          max_slots=2, pages_per_seq=6)
    S, T = 20, 11
    tokens = np.asarray(jax.random.randint(rng_key, (1, S), 0,
                                           cfg.vocab_size))
    full, _, _ = model.forward(cfg, params, jnp.asarray(tokens),
                               mode="prefill")
    sid = pr.prefill_seq(list(tokens[0, :T]))
    errs = [float(np.max(np.abs(
        pr.last_prefill_logits()
        - np.asarray(full[0, T - 1].astype(jnp.float32)))))]
    for t in range(T, S):
        lg = pr.decode({sid: int(tokens[0, t])})
        errs.append(float(np.max(np.abs(
            lg[sid] - np.asarray(full[0, t].astype(jnp.float32))))))
    assert max(errs) < 0.06, errs


def test_paged_concurrent_ragged(rng_key):
    cfg = get_config("yi-6b", reduced=True)
    pr = PagedModelRunner(cfg, num_pages=32, page_size=8, max_slots=2,
                          pages_per_seq=6)
    a = pr.prefill_seq([1, 2, 3, 4, 5, 6, 7])
    b = pr.prefill_seq([9, 8])
    for step in range(4):
        out = pr.decode({a: 10 + step, b: 20 + step})
        assert set(out) == {a, b}
        assert all(np.isfinite(v).all() for v in out.values())
    assert pr.pm.context_lens([a])[0] == 11
    assert pr.pm.context_lens([b])[0] == 6
    pr.free(a)
    pr.free(b)
    assert pr.pm.num_free_pages == 32


# ---------------------------------------------------------------------------
# lag-k rewind: the speculative verify window's rejected tail is unwound
# ---------------------------------------------------------------------------

def _runner(rng_key, **kw):
    cfg = get_config("yi-6b", reduced=True)
    params = init_params(model.params_def(cfg), rng_key)
    kw.setdefault("num_pages", 32)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_slots", 2)
    kw.setdefault("pages_per_seq", 8)
    return PagedModelRunner(cfg, params, **kw)


def test_rewind_across_page_boundary(rng_key):
    """Rewinding k tokens that straddle a page boundary frees exactly
    the drained trailing page, and re-decoding the same tokens at the
    same positions reproduces the original logits bit-for-bit (the
    rejected K/V really is gone, not shadowing the rewritten one)."""
    pr = _runner(rng_key)
    base = pr.pm.stats()
    sid = pr.prefill_seq(list(range(1, 12)))           # 11 tokens, 3 pages
    first = {}
    for i, t in enumerate([20, 21, 22]):               # 12..14: page 4 opens
        first[i] = pr.decode({sid: t})[sid]
    assert len(pr.pm.seqs[sid].pages) == 4
    frees = pr.pm.num_free_pages
    pr.rewind_tokens(sid, 3)                           # 14 -> 11: crosses 12
    assert pr.pm.context_lens([sid])[0] == 11
    assert len(pr.pm.seqs[sid].pages) == 3             # page 4 returned
    assert pr.pm.num_free_pages == frees + 1
    for i, t in enumerate([20, 21, 22]):               # replay the window
        again = pr.decode({sid: t})[sid]
        assert np.array_equal(first[i], again), i
    pr.free(sid)
    assert pr.pm.stats() == base


def test_rewind_cow_forked_tail(rng_key):
    """A fork copies the partial tail page CoW; rewinding the fork's own
    appended tokens pops only its private pages — the source sequence's
    stream is byte-identical to a run where the fork never existed."""
    pr = _runner(rng_key)
    base = pr.pm.stats()
    prompt = list(range(1, 11))                        # 10 tokens: tail of 2
    sid = pr.prefill_seq(prompt)
    fork = pr.fork_seq(sid)
    assert pr.pm.n_cow_forks >= 1
    # both advance; the fork then speculates 2 tokens and rejects them
    both = pr.decode({sid: 30, fork: 40})
    f1 = pr.decode({fork: 41})[fork]
    pr.decode({fork: 42})
    pr.rewind_tokens(fork, 2)                          # back to length 11
    assert pr.pm.context_lens([fork])[0] == 11
    # the source's next logits match a fork-free straight-through run
    nxt = pr.decode({sid: 31})[sid]
    again = pr.decode({fork: 41})[fork]                # fork replays too
    assert np.array_equal(f1, again)
    pr.free(fork)
    ref_logits = {}
    ref = _runner(rng_key)
    rsid = ref.prefill_seq(prompt)
    for t in [30, 31]:
        ref_logits[t] = ref.decode({rsid: t})[rsid]
    assert np.allclose(both[sid], ref_logits[30], atol=1e-5)
    assert np.allclose(nxt, ref_logits[31], atol=1e-5)
    pr.free(sid)
    st = pr.pm.stats()
    # cow_forks/shared_pages are cumulative counters; the pool itself
    # must be back to baseline
    assert (st["free_pages"], st["used_pages"], st["active_seqs"]) == \
        (base["free_pages"], base["used_pages"], base["active_seqs"])


def test_rewind_next_to_published_prefix_pages(rng_key):
    """A sequence whose prompt was adopted from the prefix cache rewinds
    its speculated tail without disturbing the published pages: the
    cache keeps every cached page, refcounts stay consistent, and the
    adopted prefix still matches fresh prefill logits afterwards."""
    pr = _runner(rng_key)
    prompt = list(range(1, 14))                        # 13 tokens
    s1 = pr.prefill_seq(prompt)
    pr.free(s1, publish=True)                          # pages -> radix tree
    cached = pr.prefix_cache.stats()["cached_pages"]
    assert cached >= 3                                 # 3 full pages shared
    s2 = pr.prefill_seq(prompt)                        # adopts the prefix
    assert pr.last_prefill_info["prefix_cached_tokens"] > 0
    for t in [50, 51, 52]:                             # grow past adoption
        pr.decode({s2: t})
    pr.rewind_tokens(s2, 3)                            # drop the window tail
    assert pr.pm.context_lens([s2])[0] == len(prompt)
    assert pr.prefix_cache.stats()["cached_pages"] == cached
    # published pages untouched: a third adopter still prefills clean
    s3 = pr.prefill_seq(prompt)
    l2 = pr.decode({s2: 60})[s2]
    l3 = pr.decode({s3: 60})[s3]
    assert np.allclose(l2, l3, atol=1e-5)
    pr.free(s2)
    pr.free(s3)
    st = pr.pm.stats()
    assert st["active_seqs"] == 0
    assert st["used_pages"] == pr.prefix_cache.stats()["cached_pages"]


# ---------------------------------------------------------------------------
# quantized KV pages (kv_dtype="int8"): every page-lifecycle path must
# carry the per-(token, kv-head) scales coherently.  Within one int8
# runner replay is BIT-identical (quantization is deterministic, so
# re-scattered K/V requantizes to the same bytes); against a fresh f32
# runner the logits agree to quantization noise and greedy argmax.
# ---------------------------------------------------------------------------

INT8_ATOL = 0.25          # yi-6b reduced: observed max |Δlogit| ~0.1


def _close_and_same_argmax(a, b, atol=INT8_ATOL):
    assert np.max(np.abs(a - b)) < atol, np.max(np.abs(a - b))
    assert np.argmax(a) == np.argmax(b)


def test_int8_decode_matches_f32(rng_key):
    """Plain prefill+decode with int8 pages tracks the f32 runner."""
    pr = _runner(rng_key, kv_dtype="int8")
    ref = _runner(rng_key)
    assert pr.k_pages[0].dtype == jnp.int8
    assert pr.k_scales[0].shape == (33, 4, pr.cfg.n_kv_heads)
    prompt = list(range(1, 12))
    a, b = pr.prefill_seq(prompt), ref.prefill_seq(prompt)
    _close_and_same_argmax(pr.last_prefill_logits(),
                           ref.last_prefill_logits())
    for t in [20, 21, 22]:
        _close_and_same_argmax(pr.decode({a: t})[a], ref.decode({b: t})[b])


def test_int8_rewind_across_page_boundary(rng_key):
    """Rewind across a page boundary on the quantized pool: the popped
    page's K/V AND scales are really gone — replaying the window is
    bit-identical to the first pass."""
    pr = _runner(rng_key, kv_dtype="int8")
    sid = pr.prefill_seq(list(range(1, 12)))           # 11 tokens, 3 pages
    first = {}
    for i, t in enumerate([20, 21, 22]):               # 12..14: page 4 opens
        first[i] = pr.decode({sid: t})[sid]
    assert len(pr.pm.seqs[sid].pages) == 4
    pr.rewind_tokens(sid, 3)                           # 14 -> 11: crosses 12
    assert pr.pm.context_lens([sid])[0] == 11
    for i, t in enumerate([20, 21, 22]):               # replay the window
        assert np.array_equal(first[i], pr.decode({sid: t})[sid]), i


def test_int8_cow_forked_tail(rng_key):
    """CoW fork copies the partial tail page's scale rows along with the
    quantized K/V: fork and source keep tracking an f32 oracle after the
    fork diverges."""
    pr = _runner(rng_key, kv_dtype="int8")
    prompt = list(range(1, 11))                        # 10 tokens: tail of 2
    sid = pr.prefill_seq(prompt)
    fork = pr.fork_seq(sid)
    assert pr.pm.n_cow_forks >= 1
    both = pr.decode({sid: 30, fork: 40})              # divergence
    nxt = pr.decode({sid: 31})[sid]
    f2 = pr.decode({fork: 41})[fork]
    ref = _runner(rng_key)
    rs = ref.prefill_seq(prompt)
    rf = ref.fork_seq(rs)
    rboth = ref.decode({rs: 30, rf: 40})
    _close_and_same_argmax(both[sid], rboth[rs])
    _close_and_same_argmax(both[fork], rboth[rf])
    _close_and_same_argmax(nxt, ref.decode({rs: 31})[rs])
    _close_and_same_argmax(f2, ref.decode({rf: 41})[rf])


def test_int8_published_prefix_adopt(rng_key):
    """Prefix-cache publish/adopt shares the quantized pages AND their
    scales: an adopter's stream is bit-identical to a fresh quantized
    prefill of the same prompt (same bytes, same scales)."""
    pr = _runner(rng_key, kv_dtype="int8")
    prompt = list(range(1, 14))                        # 13 tokens
    s1 = pr.prefill_seq(prompt)
    pr.free(s1, publish=True)                          # pages -> radix tree
    assert pr.prefix_cache.stats()["cached_pages"] >= 3
    s2 = pr.prefill_seq(prompt)                        # adopts the prefix
    assert pr.last_prefill_info["prefix_cached_tokens"] > 0
    s3 = pr.prefill_seq(prompt)                        # second adopter
    l2 = pr.decode({s2: 60})[s2]
    l3 = pr.decode({s3: 60})[s3]
    assert np.array_equal(l2, l3)
    fresh = _runner(rng_key, kv_dtype="int8", enable_prefix_cache=False)
    f = fresh.prefill_seq(prompt)
    assert np.array_equal(l2, fresh.decode({f: 60})[f])


def test_int8_preempt_resume(rng_key):
    """Preempt (free without publish) then resume by re-prefilling
    prompt+kept tokens: requantization is deterministic, so the resumed
    quantized stream matches straight-through int8 AND stays within
    quantization noise of the f32 oracle."""
    pr = _runner(rng_key, kv_dtype="int8")
    base = pr.pm.stats()
    prompt = list(range(2, 12))
    sid = pr.prefill_seq(prompt)
    kept = []
    for t in [70, 71]:
        pr.decode({sid: t})
        kept.append(t)
    pr.free(sid)                                       # preemption
    assert pr.pm.stats() == base
    rsid = pr.prefill_seq(prompt + kept)               # resume
    resumed = pr.decode({rsid: 74})[rsid]
    straight = _runner(rng_key, kv_dtype="int8")
    ss = straight.prefill_seq(prompt)
    for t in [70, 71]:
        straight.decode({ss: t})
    assert np.array_equal(resumed, straight.decode({ss: 74})[ss])
    f32 = _runner(rng_key)
    fs = f32.prefill_seq(prompt)
    for t in [70, 71]:
        f32.decode({fs: t})
    _close_and_same_argmax(resumed, f32.decode({fs: 74})[fs])


def test_rewind_then_preempt_then_resume(rng_key):
    """Round trip: speculate, reject (rewind), preempt (free without
    publish), then resume by re-prefilling prompt+kept tokens — the
    resumed stream continues exactly where the rewound one left off."""
    pr = _runner(rng_key)
    base = pr.pm.stats()
    prompt = list(range(2, 12))
    sid = pr.prefill_seq(prompt)
    kept = []
    for t in [70, 71]:                                 # accepted tokens
        pr.decode({sid: t})
        kept.append(t)
    pr.decode({sid: 72})                               # speculated...
    pr.decode({sid: 73})
    pr.rewind_tokens(sid, 2)                           # ...and rejected
    pr.free(sid)                                       # preemption
    assert pr.pm.stats() == base                       # fully returned
    rsid = pr.prefill_seq(prompt + kept)               # resume
    resumed = pr.decode({rsid: 74})[rsid]
    ref = _runner(rng_key)
    ref_sid = ref.prefill_seq(prompt)
    for t in [70, 71]:
        ref.decode({ref_sid: t})
    straight = ref.decode({ref_sid: 74})[ref_sid]
    assert np.allclose(resumed, straight, atol=1e-5)
    pr.free(rsid)
    assert pr.pm.stats() == base
