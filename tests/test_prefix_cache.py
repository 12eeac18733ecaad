"""Prefix-cache subsystem: radix match + CoW page sharing + LRU eviction,
and the paged engine backend end-to-end (WebLLM multi-round chat reuse).
"""
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import ChatCompletionRequest, ChatMessage, MLCEngine
from repro.core.paged_cache import OutOfPages, PageManager
from repro.core.paged_runner import PagedEngineBackend, PagedModelRunner
from repro.core.prefix_cache import PrefixCache


# ---------------------------------------------------------------------------
# pure bookkeeping (no model)
# ---------------------------------------------------------------------------

def _pm(num_pages=16, page_size=4, max_slots=4, pages_per_seq=8):
    return PageManager(num_pages=num_pages, page_size=page_size,
                       max_slots=max_slots, pages_per_seq=pages_per_seq)


def test_radix_match_page_granularity():
    pm = _pm()
    cache = PrefixCache(pm)
    a = pm.new_seq()
    ids = list(range(10))                      # 2 full pages + tail of 2
    pm.append_tokens(a.seq_id, len(ids))
    cache.insert(ids, pm.seqs[a.seq_id].pages)
    assert cache.cached_pages == 3
    pm.free_seq(a.seq_id)
    # cached pages survive the owning sequence
    assert pm.num_free_pages == 16 - 3

    full, tail = cache.match(list(range(10)) + [99])
    assert len(full) == 2                      # 8 tokens shared in place
    assert tail is not None and tail[1] == 2   # 2-token tail, CoW fork
    # diverging after one page matches only that page
    full, tail = cache.match([0, 1, 2, 3, 7, 7, 7, 7, 7])
    assert len(full) == 1 and tail is None
    # total miss
    full, tail = cache.match([5, 5, 5, 5, 5])
    assert not full and tail is None
    assert cache.misses == 1 and cache.hits == 2


def test_refcounts_shared_pages_survive_free():
    pm = _pm()
    cache = PrefixCache(pm)
    a = pm.new_seq()
    ids = list(range(8))                       # 2 full pages
    pm.append_tokens(a.seq_id, 8)
    cache.insert(ids, pm.seqs[a.seq_id].pages)
    pm.free_seq(a.seq_id)

    b = pm.new_seq()
    full, _ = cache.match(ids + [42])
    pm.share_pages(b.seq_id, full, 8)
    assert all(pm.ref[p] == 2 for p in full)   # cache + seq b
    cache.reclaim(16)                          # evict everything evictable
    # shared pages dropped from cache but NOT freed (b still holds them)
    assert all(pm.ref[p] == 1 for p in full)
    assert cache.cached_pages == 0
    pm.free_seq(b.seq_id)
    assert pm.num_free_pages == 16             # nothing leaked


def test_lru_eviction_under_page_pressure():
    pm = _pm(num_pages=8, page_size=4, max_slots=4, pages_per_seq=4)
    cache = PrefixCache(pm)
    for base in (0, 100):                      # two cached 8-token seqs
        s = pm.new_seq()
        pm.append_tokens(s.seq_id, 8)
        cache.insert([base + i for i in range(8)], pm.seqs[s.seq_id].pages)
        pm.free_seq(s.seq_id)
    assert pm.num_free_pages == 4
    cache.match([100 + i for i in range(8)])   # touch the second -> MRU
    big1 = pm.new_seq()
    pm.append_tokens(big1.seq_id, 12)          # 3 pages (1 from eviction)
    big2 = pm.new_seq()
    pm.append_tokens(big2.seq_id, 12)          # 3 more, all via eviction
    assert cache.evictions >= 2
    # the recently-used entry outlived the LRU one
    lru_full, _ = cache.match([0, 1, 2, 3, 4])
    mru_full, _ = cache.match([100, 101, 102, 103, 104])
    assert len(mru_full) >= len(lru_full)
    pm.free_seq(big1.seq_id)
    pm.free_seq(big2.seq_id)
    # conservation: every page is free or cache-held
    assert pm.num_free_pages + cache.cached_pages == 8


def test_max_cached_pages_proactive_eviction():
    """With a page cap the cache evicts LRU leaves ON INSERT — its
    footprint is bounded without waiting for allocation pressure."""
    pm = _pm()
    cache = PrefixCache(pm, max_cached_pages=2)
    for base in (0, 100, 200):             # 3 seqs x 2 full pages each
        s = pm.new_seq()
        pm.append_tokens(s.seq_id, 8)
        cache.insert([base + i for i in range(8)], pm.seqs[s.seq_id].pages)
        pm.free_seq(s.seq_id)
        assert cache.cached_pages <= 2     # enforced at every insert
    assert cache.cap_evictions >= 4
    st = cache.stats()
    assert st["max_cached_pages"] == 2
    assert st["cached_pages"] <= 2
    # the survivors are the most recently inserted pages
    full, _ = cache.match([200 + i for i in range(8)])
    assert len(full) >= 1
    # evicted pages actually returned to the free list
    assert pm.num_free_pages + cache.cached_pages == 16


def test_max_cached_bytes_cap():
    """The byte-based cap converts to a per-model page count via
    page_bytes (tighter of the two caps wins) and is enforced the same
    proactive way — one byte budget can govern several loaded models."""
    pm = _pm()
    # 3 pages worth of bytes at 128 B/page
    cache = PrefixCache(pm, max_cached_bytes=3 * 128 + 50, page_bytes=128)
    assert cache.max_cached_pages == 3
    for base in (0, 100, 200):
        s = pm.new_seq()
        pm.append_tokens(s.seq_id, 8)
        cache.insert([base + i for i in range(8)], pm.seqs[s.seq_id].pages)
        pm.free_seq(s.seq_id)
        assert cache.cached_pages <= 3
    st = cache.stats()
    assert st["max_cached_bytes"] == 3 * 128 + 50
    assert st["cached_bytes"] == st["cached_pages"] * 128 <= 3 * 128
    # both caps set: the tighter one wins
    tight = PrefixCache(_pm(), max_cached_pages=1,
                        max_cached_bytes=10 * 128, page_bytes=128)
    assert tight.max_cached_pages == 1


def test_max_cached_bytes_engine_knob():
    """load_model(max_cached_bytes=...) reaches the cache with the
    model's real per-page KV byte cost (head_dim padded to the pools'
    128-lane width)."""
    cfg = get_config("llama-3.1-8b", reduced=True)
    eng = MLCEngine()
    dh = -(-cfg.head_dim // 128) * 128
    page_bytes = 2 * cfg.n_layers * 16 * cfg.n_kv_heads * dh * 2
    eng.load_model("m", cfg, max_slots=2, max_context=128, seed=0,
                   backend="paged", page_size=16,
                   max_cached_bytes=2 * page_bytes)
    pc = eng.models["m"].runner.prefix_cache
    assert pc.page_bytes == page_bytes
    assert pc.max_cached_pages == 2
    eng.shutdown()


def test_page_bytes_tracks_pool_dtype():
    """page_bytes is derived from the ACTUAL pool dtype: bf16 K/V
    vectors by default; int8 vectors plus one bf16 scale per (token,
    kv-head) when the pool is quantized, at the pools' 128-lane padded
    head width.  The same byte cap therefore admits ~2x the pages on a
    quantized pool (256 B vs 130 B per padded KV vector pair)."""
    from repro.core.paged_runner import PagedModelRunner
    cfg = get_config("llama-3.1-8b", reduced=True)
    psz = 16
    kw = dict(num_pages=4, page_size=psz, max_slots=1, pages_per_seq=2)
    bf16 = PagedModelRunner(cfg, **kw)
    i8 = PagedModelRunner(cfg, kv_dtype="int8", **kw)
    dh = -(-cfg.head_dim // 128) * 128
    assert bf16.page_bytes == (2 * cfg.n_layers * psz * cfg.n_kv_heads
                               * dh * 2)
    assert i8.page_bytes == (2 * cfg.n_layers * psz * cfg.n_kv_heads
                             * (dh + 2))
    assert bf16.page_bytes / i8.page_bytes >= 1.8
    # the engine knob path sees the quantized cost too
    eng = MLCEngine()
    eng.load_model("m", cfg, max_slots=2, max_context=128, seed=0,
                   backend="paged", page_size=psz, kv_dtype="int8",
                   max_cached_bytes=2 * bf16.page_bytes)
    pc = eng.models["m"].runner.prefix_cache
    assert pc.page_bytes == i8.page_bytes
    assert pc.max_cached_pages == (2 * bf16.page_bytes) // i8.page_bytes
    eng.shutdown()


def test_peek_len_is_pure():
    """peek_len reports the cached-prefix length without perturbing LRU
    clocks or hit/miss counters (the scheduler probes every step)."""
    pm = _pm()
    cache = PrefixCache(pm)
    s = pm.new_seq()
    ids = list(range(10))
    pm.append_tokens(s.seq_id, 10)
    cache.insert(ids, pm.seqs[s.seq_id].pages)
    pm.free_seq(s.seq_id)
    h, m, clock = cache.hits, cache.misses, cache._clock
    assert cache.peek_len(ids + [99]) == 10
    assert cache.peek_len(ids[:6]) == 4    # page-granular, like match()
    assert cache.peek_len([7, 7, 7]) == 0
    assert (cache.hits, cache.misses, cache._clock) == (h, m, clock)


def test_out_of_pages_when_cache_cannot_help():
    pm = _pm(num_pages=4, page_size=4, max_slots=4, pages_per_seq=4)
    PrefixCache(pm)                            # installs reclaim hooks
    a = pm.new_seq()
    pm.append_tokens(a.seq_id, 16)             # whole pool, nothing cached
    b = pm.new_seq()
    with pytest.raises(OutOfPages):
        pm.append_tokens(b.seq_id, 1)


# ---------------------------------------------------------------------------
# runner-level: real KV pages
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def runner():
    cfg = get_config("llama-3.1-8b", reduced=True)
    return PagedModelRunner(cfg, num_pages=48, page_size=8, max_slots=4,
                            pages_per_seq=8, seed=0)


def test_cached_prefill_matches_cold_logits(runner):
    toks = list(range(2, 40))
    a = runner.prefill_seq(toks)
    cold = runner.last_prefill_logits()
    assert runner.last_prefill_info["prefix_cached_tokens"] == 0
    runner.free(a, publish=True)

    b = runner.prefill_seq(toks)
    warm = runner.last_prefill_logits()
    info = runner.last_prefill_info
    assert info["prefix_cached_tokens"] >= runner.page_size
    assert float(np.max(np.abs(cold - warm))) < 0.06
    runner.free(b)


def test_cow_isolation_between_branches(runner):
    shared = list(range(3, 30))                # 27 tokens: 3 full + tail
    a = runner.prefill_seq(shared)
    runner.free(a, publish=True)
    full, tail = runner.prefix_cache.match(shared)
    assert tail is not None
    src_page = tail[0]
    snapshot = np.stack([np.asarray(k[src_page]) for k in runner.k_pages])

    b = runner.prefill_seq(shared + [50, 51])
    c = runner.prefill_seq(shared + [60, 61, 62])
    # both branches decode without touching the shared cached tail
    for step in range(3):
        out = runner.decode({b: 70 + step, c: 80 + step})
        assert all(np.isfinite(v).all() for v in out.values())
    after = np.stack([np.asarray(k[src_page]) for k in runner.k_pages])
    np.testing.assert_array_equal(snapshot, after)
    # the two branches forked *different* private tail pages
    pages_b = runner.pm.seqs[b].pages
    pages_c = runner.pm.seqs[c].pages
    assert pages_b[3] != pages_c[3] and src_page not in (pages_b[3],
                                                         pages_c[3])
    runner.free(b)
    runner.free(c)


def test_refcount_eviction_under_pressure_runner():
    cfg = get_config("llama-3.1-8b", reduced=True)
    pr = PagedModelRunner(cfg, num_pages=10, page_size=8, max_slots=4,
                          pages_per_seq=8, seed=0)
    a = pr.prefill_seq(list(range(2, 35)))     # 33 tokens -> 5 pages
    pr.free(a, publish=True)
    assert pr.prefix_cache.cached_pages == 5
    # a big unrelated prompt forces LRU eviction of cached pages
    b = pr.prefill_seq(list(range(40, 96)))    # 56 tokens -> 7 pages
    assert pr.prefix_cache.evictions >= 2
    pr.free(b)
    pm = pr.pm
    assert pm.num_free_pages + pr.prefix_cache.cached_pages == 10
    assert all(pm.ref[p] >= 1
               for a_ in pm.seqs.values() for p in a_.pages)


# ---------------------------------------------------------------------------
# engine-level: paged backend end-to-end
# ---------------------------------------------------------------------------

def _chat(eng, messages, **kw):
    kw.setdefault("max_tokens", 8)
    kw.setdefault("temperature", 0.0)
    kw.setdefault("seed", 0)
    return eng.chat_completions_create(ChatCompletionRequest(
        messages=list(messages), model="m", **kw))


def _two_turns(eng):
    msgs = [{"role": "user", "content":
             "hello world this is a tiny corpus for the demo engine"}]
    r1 = _chat(eng, msgs)
    msgs.append({"role": "assistant",
                 "content": r1.choices[0].message.content})
    msgs.append({"role": "user", "content": "tell me more"})
    return r1, _chat(eng, msgs)


def test_engine_paged_two_turn_prefix_reuse():
    cfg = get_config("llama-3.1-8b", reduced=True)
    eng = MLCEngine()
    eng.load_model("m", cfg, max_slots=2, max_context=128, seed=0,
                   backend="paged", page_size=16)
    r1, r2 = _two_turns(eng)
    page_size = eng.models["m"].runner.runner.page_size
    assert r2.usage.extra["prefix_cached_tokens"] >= page_size
    stats = eng.stats("m")
    assert stats["backend"] == "paged"
    assert stats["runner"]["prefix_cache"]["hits"] >= 1
    eng.shutdown()

    # greedy turn-2 completion must be byte-identical on a cold cache
    cold = MLCEngine()
    cold.load_model("m", cfg, max_slots=2, max_context=128, seed=0,
                    backend="paged", page_size=16,
                    enable_prefix_cache=False)
    _, c2 = _two_turns(cold)
    assert c2.usage.extra["prefix_cached_tokens"] == 0
    assert (c2.choices[0].message.content
            == r2.choices[0].message.content)
    cold.shutdown()


def test_engine_paged_preemption_with_shared_pages():
    """Page pressure preempts the newest sequence; it resumes later and
    every request completes, with refcount-consistent accounting."""
    cfg = get_config("llama-3.1-8b", reduced=True)
    eng = MLCEngine()
    # tiny pool: 2 concurrent seqs + cache cannot all fit
    eng.load_model("m", cfg, max_slots=2, max_context=96, seed=0,
                   backend="paged", page_size=8, num_pages=18)
    base = [{"role": "user", "content":
             "hello world this is a tiny corpus for the demo engine"}]
    r0 = _chat(eng, base, max_tokens=6)        # seeds the prefix cache
    import threading
    results = [None] * 3

    def go(i):
        results[i] = _chat(eng, base + [
            {"role": "user", "content": f"question number {i}"}],
            max_tokens=10, seed=i)

    ts = [threading.Thread(target=go, args=(i,)) for i in range(3)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=300)
    assert all(r is not None for r in results)
    assert all(r.usage.completion_tokens > 0 for r in results)
    backend = eng.models["m"].runner
    pm = backend.pm
    assert not pm.seqs                          # all sequences released
    assert (pm.num_free_pages
            + backend.prefix_cache.cached_pages) == pm.num_pages
    eng.shutdown()


def test_paged_engine_backend_interface():
    cfg = get_config("llama-3.1-8b", reduced=True)
    be = PagedEngineBackend(cfg, max_slots=2, max_context=64, page_size=8,
                            seed=0)
    prompt = list(range(2, 20))
    assert be.begin_prefill(0, prompt) == 0     # cold: nothing adopted
    logits = be.run_step([(0, prompt, "prefill")])[0]
    assert logits.ndim == 1 and np.isfinite(logits).all()
    out = be.run_step([(0, [5], "decode")])
    assert np.isfinite(out[0]).all()
    be.release(0)                               # publishes into the cache
    assert be.prefix_cache.cached_pages > 0
    # the slot is reusable and the next prefill hits the cache
    cached = be.begin_prefill(0, prompt)
    assert be.last_prefill_info["prefix_cached_tokens"] == cached >= 8
    be.run_step([(0, prompt[cached:], "prefill")])
    be.release(0, publish=False)
