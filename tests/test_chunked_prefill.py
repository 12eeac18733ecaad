"""Chunked paged prefill: logits equivalence vs the dense oracle,
partial-final-chunk padding, preemption mid-prefill resume, and decode
liveness while a long prompt prefills (the step-plan scheduler's whole
point)."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import ChatCompletionRequest, ChatMessage, MLCEngine
from repro.core.paged_cache import OutOfPages
from repro.core.paged_runner import PagedModelRunner
from repro.models import model
from repro.models.pdef import init_params


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("yi-6b", reduced=True)
    params = init_params(model.params_def(cfg), jax.random.PRNGKey(0))
    return cfg, params


def _oracle(cfg, params, tokens):
    full, _, _ = model.forward(cfg, params, jnp.asarray(tokens),
                               mode="prefill")
    return np.asarray(full[0].astype(jnp.float32))


def test_chunk_logits_match_dense_per_chunk(setup):
    """Every chunk's returned logits equal the dense full-prompt forward
    at that position — including the padded partial final chunk."""
    cfg, params = setup
    pr = PagedModelRunner(cfg, params, num_pages=32, page_size=8,
                          max_slots=2, pages_per_seq=6, chunk_size=8)
    T = 21                                     # 8 + 8 + partial 5
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (1, T), 0, cfg.vocab_size))
    full = _oracle(cfg, params, tokens)
    ids = [int(t) for t in tokens[0]]
    sid = pr.begin_seq(ids)
    assert pr.seq_len(sid) == 0                # cold: nothing adopted
    done, errs = 0, []
    while done < T:
        n = min(8, T - done)
        logits = pr.prefill_chunk(sid, ids[done:done + n])
        done += n
        errs.append(float(np.max(np.abs(logits - full[done - 1]))))
    assert max(errs) < 0.06, errs
    assert pr.n_ragged_steps == 3
    assert pr.n_prefill_tokens == T
    pr.free(sid)
    assert pr.pm.num_free_pages == 32          # trash page not leased


def test_prompt_shorter_than_chunk_pads(setup):
    """A prompt smaller than chunk_size runs as one padded chunk, and the
    pad rows corrupt neither its own pages nor a neighbour sequence."""
    cfg, params = setup
    pr = PagedModelRunner(cfg, params, num_pages=32, page_size=8,
                          max_slots=2, pages_per_seq=6, chunk_size=8)
    Ta, Tb = 5, 3                              # both < chunk_size
    toks = np.asarray(jax.random.randint(
        jax.random.PRNGKey(2), (1, Ta + 6), 0, cfg.vocab_size))
    full = _oracle(cfg, params, toks)
    a = pr.prefill_seq([int(t) for t in toks[0, :Ta]])
    b = pr.prefill_seq(list(range(2, 2 + Tb)))
    assert float(np.max(np.abs(
        pr.last_prefill_logits()))) >= 0.0     # b's logits are finite
    errs = [float(np.max(np.abs(
        # a's prefill logits were overwritten by b's — recompute via log
        pr.decode({a: int(toks[0, Ta])})[a] - full[Ta])))]
    # continue decoding a with b live: pad-row writes from either prompt
    # must not have leaked into real pages
    for t in range(Ta + 1, Ta + 6):
        out = pr.decode({a: int(toks[0, t]), b: 40 + t})
        errs.append(float(np.max(np.abs(out[a] - full[t]))))
        assert np.isfinite(out[b]).all()
    assert max(errs) < 0.06, errs


def test_preempt_midprefill_publish_and_resume(setup):
    """Freeing a sequence mid-prefill with publish=True pushes exactly
    the completed chunks into the prefix cache; re-admission adopts them
    and finishes from the cursor with oracle-equivalent logits."""
    cfg, params = setup
    pr = PagedModelRunner(cfg, params, num_pages=32, page_size=8,
                          max_slots=2, pages_per_seq=6, chunk_size=8)
    T = 30
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(3), (1, T), 0, cfg.vocab_size))
    full = _oracle(cfg, params, tokens)
    ids = [int(t) for t in tokens[0]]
    sid = pr.begin_seq(ids)
    pr.prefill_chunk(sid, ids[:8])             # 2 of 4 chunks, then preempt
    pr.prefill_chunk(sid, ids[8:16])
    pr.free(sid, publish=True)                 # mid-prefill publication
    assert pr.prefix_cache.cached_pages == 2   # exactly the 16 full tokens

    sid2 = pr.begin_seq(ids)                   # resume: adopt the cursor
    cached = pr.seq_len(sid2)
    assert cached == 16
    assert pr.last_prefill_info["prefix_cached_tokens"] == 16
    done = cached
    while done < T:
        n = min(8, T - done)
        logits = pr.prefill_chunk(sid2, ids[done:done + n])
        done += n
    assert float(np.max(np.abs(logits - full[T - 1]))) < 0.06
    pr.free(sid2)


def test_decode_liveness_during_long_prefill():
    """Acceptance: with one running decode stream and a concurrently
    submitted long prompt (many chunks), the decode stream emits tokens
    WITHIN the same fused steps that advance the prompt's prefill —
    asserted via the runner's step log of ``("ragged", n_decode_rows,
    n_prefill_tokens)`` entries, which also proves the whole mixed step
    was ONE attention kernel dispatch."""
    cfg = get_config("llama-3.1-8b", reduced=True)
    eng = MLCEngine()
    eng.load_model("m", cfg, max_slots=2, max_context=256, seed=0,
                   backend="paged", page_size=8, prefill_chunk_size=4,
                   token_budget=6)            # decode both + one chunk
    runner = eng.models["m"].runner.runner
    # warmup compiles the fused ragged step buckets
    eng.chat_completions_create(ChatCompletionRequest(
        messages=[ChatMessage("user", "warm up this engine")],
        model="m", max_tokens=2, temperature=0.0))

    chunks_seen = []

    def stream():
        it = eng.chat_completions_create(ChatCompletionRequest(
            messages=[ChatMessage("user", "hi")], model="m",
            max_tokens=40, seed=1, stream=True))
        for c in it:
            chunks_seen.append(c)

    ts = threading.Thread(target=stream)
    ts.start()
    # wait until the short stream is actually decoding
    deadline = time.time() + 120
    while len(chunks_seen) < 3 and time.time() < deadline:
        time.sleep(0.02)
    assert len(chunks_seen) >= 3
    runner.step_log.clear()
    long_msg = " ".join(f"word{i} mixed tokens" for i in range(12))
    resp = eng.chat_completions_create(ChatCompletionRequest(
        messages=[ChatMessage("user", long_msg)], model="m",
        max_tokens=3, seed=2, temperature=0.0))
    ts.join(timeout=300)
    assert resp.usage.completion_tokens > 0
    log = list(runner.step_log)
    assert all(e[0] == "ragged" for e in log), log   # engine path is fused
    prefill_steps = [e for e in log if e[2] > 0]
    assert len(prefill_steps) >= 8, log        # a genuinely long prefill
    fused_mixed = sum(1 for e in prefill_steps if e[1] > 0)
    assert fused_mixed >= 4, log    # decode rode ALONG in the same call
    # TTFT of the long request reflects budgeted chunking, not a stall
    assert resp.usage.extra["ttft_s"] > 0.0
    eng.shutdown()


def test_run_step_matches_per_sequence_path(setup):
    """One fused run_step over a mixed decode+prefill batch returns the
    same logits as the per-sequence chunk/decode calls it replaces —
    including a row longer than chunk_size and bucket padding."""
    cfg, params = setup
    T_a, T_b = 25, 14
    toks = np.asarray(jax.random.randint(
        jax.random.PRNGKey(7), (1, T_a + T_b), 0, cfg.vocab_size))[0]
    ids_a = [int(t) for t in toks[:T_a]]
    ids_b = [int(t) for t in toks[T_a:]]
    full_a = _oracle(cfg, params, np.asarray(ids_a)[None])
    full_b = _oracle(cfg, params, np.asarray(ids_b)[None])
    pr = PagedModelRunner(cfg, params, num_pages=32, page_size=8,
                          max_slots=4, pages_per_seq=6, chunk_size=8,
                          enable_prefix_cache=False)
    sa = pr.begin_seq(ids_a)
    sb = pr.prefill_seq(ids_b[:10])
    base_steps = pr.n_ragged_steps
    # fused: A prefills 9 tokens, B decodes — ONE ragged step
    out = pr.run_step([(sa, ids_a[:9], "prefill"), (sb, [ids_b[10]],
                                                    "decode")])
    assert float(np.max(np.abs(out[sa] - full_a[8]))) < 0.06
    assert float(np.max(np.abs(out[sb] - full_b[10]))) < 0.06
    # fused: A's remaining 16 (> chunk_size) as one ragged row
    out = pr.run_step([(sa, ids_a[9:], "prefill"), (sb, [ids_b[11]],
                                                    "decode")])
    assert float(np.max(np.abs(out[sa] - full_a[T_a - 1]))) < 0.06
    assert float(np.max(np.abs(out[sb] - full_b[11]))) < 0.06
    assert pr.n_ragged_steps == base_steps + 2  # nothing else dispatched
    assert list(pr.step_log)[-2:] == [("ragged", 1, 9), ("ragged", 1, 16)]
    pr.free(sa), pr.free(sb)
    assert pr.pm.num_free_pages == 32          # pads stayed in trash page


def test_run_step_out_of_pages_is_atomic(setup):
    """A fused step the pool cannot back raises OutOfPages BEFORE any
    sequence state mutates — lengths, pages, and the pool are untouched
    so the engine can preempt and replan."""
    cfg, params = setup
    pr = PagedModelRunner(cfg, params, num_pages=4, page_size=8,
                          max_slots=2, pages_per_seq=6, chunk_size=8,
                          enable_prefix_cache=False)
    sid = pr.prefill_seq(list(range(2, 26)))   # 24 tokens = 3 pages
    steps_before = pr.n_ragged_steps
    free_before = pr.pm.num_free_pages
    len_before = pr.seq_len(sid)
    with pytest.raises(OutOfPages):
        # 1 free page left; 17 more tokens need 3 new pages
        pr.run_step([(sid, list(range(2, 19)), "prefill")])
    assert pr.pm.num_free_pages == free_before
    assert pr.seq_len(sid) == len_before
    assert pr.n_ragged_steps == steps_before


def test_engine_one_kernel_call_per_step():
    """Acceptance: on the paged backend every engine step that executes
    work dispatches exactly ONE attention kernel call (previously >= 1
    per sequence), and — since batched on-device sampling — NO logit
    row ever crosses the device→host boundary (``host_logit_rows == 0``:
    only sampled token ids come back)."""
    cfg = get_config("llama-3.1-8b", reduced=True)
    eng = MLCEngine()
    eng.load_model("m", cfg, max_slots=3, max_context=128, seed=0,
                   backend="paged", page_size=8, prefill_chunk_size=4,
                   token_budget=8)
    reqs = [ChatCompletionRequest(
        messages=[ChatMessage("user", f"mixed traffic request {i} "
                              + "with words " * (1 + 3 * (i % 2)))],
        model="m", max_tokens=4, seed=i, temperature=0.0)
        for i in range(4)]
    threads = [threading.Thread(
        target=eng.chat_completions_create, args=(r,)) for r in reqs]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    s = eng.stats("m")
    assert s["engine"]["exec_steps"] > 0
    assert s["runner"]["ragged_steps"] == s["runner"]["attn_kernel_calls"]
    assert s["runner"]["attn_kernel_calls"] == s["engine"]["exec_steps"]
    assert s["runner"]["host_logit_rows"] == 0
    assert s["runner"]["sampled_tokens"] > 0
    # device→host traffic is tokens/logprobs, not [B, V] logit planes:
    # a handful of bytes per sampled token
    assert s["runner"]["host_sync_bytes"] \
        <= 16 * s["runner"]["sampled_tokens"]
    eng.shutdown()


def test_poisoned_fused_step_fails_request_not_loop():
    """A non-OutOfPages error inside the fused step must surface to the
    request's caller and leave the engine loop alive for later requests
    (the old per-chunk path's catch-all guarantee, kept by the fused
    path)."""
    cfg = get_config("llama-3.1-8b", reduced=True)
    eng = MLCEngine()
    eng.load_model("m", cfg, max_slots=2, max_context=128, seed=0,
                   backend="paged", prefill_chunk_size=4)
    backend = eng.models["m"].runner
    orig = backend.run_step
    state = {"armed": True}

    def poisoned(rows, **kw):
        if state["armed"]:
            state["armed"] = False
            raise RuntimeError("poisoned step")
        return orig(rows, **kw)

    backend.run_step = poisoned
    with pytest.raises(RuntimeError, match="poisoned step"):
        eng.chat_completions_create(ChatCompletionRequest(
            messages=[ChatMessage("user", "boom")], model="m",
            max_tokens=4, temperature=0.0))
    # engine survived: the next request completes normally
    r = eng.chat_completions_create(ChatCompletionRequest(
        messages=[ChatMessage("user", "still alive?")], model="m",
        max_tokens=4, temperature=0.0))
    assert r.usage.completion_tokens > 0
    eng.shutdown()


def test_grammar_dead_end_fails_request_not_engine(monkeypatch):
    """A grammar state that allows NO next token fails THAT request
    loudly ("grammar mask excludes every token" — the host sampler's
    historical behavior) instead of letting the device op sample a
    grammar-illegal token silently; the engine survives for later
    requests."""
    import numpy as np

    from repro.grammar.matcher import GrammarMatcher
    cfg = get_config("llama-3.1-8b", reduced=True)
    eng = MLCEngine()
    eng.load_model("m", cfg, max_slots=2, max_context=128, seed=0,
                   backend="paged", prefill_chunk_size=4)
    monkeypatch.setattr(
        GrammarMatcher, "token_bitmask",
        lambda self: np.zeros(-(-self.tok.vocab_size // 32), np.uint32))
    with pytest.raises(RuntimeError, match="excludes every token"):
        eng.chat_completions_create(ChatCompletionRequest(
            messages=[ChatMessage("user", "json please")], model="m",
            max_tokens=8, temperature=0.0,
            response_format={"type": "json_object"}))
    monkeypatch.undo()
    r = eng.chat_completions_create(ChatCompletionRequest(
        messages=[ChatMessage("user", "still alive?")], model="m",
        max_tokens=3, temperature=0.0))
    assert r.usage.completion_tokens > 0
    eng.shutdown()


def test_chunked_equivalence_engine_cold_vs_seed_dense():
    """The same greedy completion falls out of the paged chunked path
    and the dense monolithic path (the seed's prefill architecture)."""
    cfg = get_config("llama-3.1-8b", reduced=True)
    req = dict(messages=[ChatMessage("user", "hello world tell me")],
               model="m", max_tokens=6, temperature=0.0, seed=0)
    outs = []
    for backend in ("dense", "paged"):
        eng = MLCEngine()
        eng.load_model("m", cfg, max_slots=2, max_context=128, seed=0,
                       backend=backend, prefill_chunk_size=4)
        outs.append(eng.chat_completions_create(
            ChatCompletionRequest(**req)).choices[0].message.content)
        eng.shutdown()
    assert outs[0] == outs[1], outs
