"""Serving launcher — the end-to-end driver (the paper's kind).

Loads model(s) into an MLCEngine behind a ServiceWorkerMLCEngine frontend
and replays a batch of OpenAI-style requests through it, reporting
engine-level throughput stats.

    PYTHONPATH=src python -m repro.launch.serve --arch llama-3.1-8b \
        --requests 8 --max-tokens 24 --concurrency 4

``--replicas N`` (N >= 2) serves the same batch through a
:class:`~repro.core.router.RouterEngine` pool instead of a single
worker: N engine replicas behind one frontend, prefix-affine dispatch,
health-checked and restart-on-crash.  Multi-round traffic (each request
becomes a 2-turn conversation) exercises the affinity map; the run ends
with the router's per-replica dispatch/affinity/restart table.

    PYTHONPATH=src python -m repro.launch.serve --replicas 2 \
        --requests 8 --max-tokens 16
"""
from __future__ import annotations

import argparse
import threading
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama-3.1-8b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-tokens", type=int, default=24)
    ap.add_argument("--concurrency", type=int, default=4)
    ap.add_argument("--max-context", type=int, default=160)
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve through a RouterEngine pool of N worker "
                         "replicas (prefix-affine dispatch, health "
                         "checks, restart-on-crash)")
    ap.add_argument("--quantize", action="store_true",
                    help="serve int4 weights (the paper's q4f16 setting)")
    ap.add_argument("--json", action="store_true",
                    help="constrain all outputs to JSON via the grammar")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.launch.compile_cache import use_repo_compile_cache
    use_repo_compile_cache()
    from repro.configs import get_config
    from repro.core import (ChatCompletionRequest, ChatMessage, MLCEngine,
                            RouterEngine, ServiceWorkerMLCEngine)

    cfg = get_config(args.arch, reduced=True)

    def load(eng: MLCEngine):
        # the router pool serves multi-round chat, so replicas get the
        # paged backend (radix prefix cache) — that is what affinity
        # dispatch exists to exploit
        kw = (dict(backend="paged", page_size=16) if args.replicas > 1
              else dict(quantize=args.quantize))
        eng.load_model("main", cfg, max_slots=args.concurrency,
                       max_context=args.max_context, seed=args.seed, **kw)
        return eng

    t0 = time.time()
    if args.replicas > 1:
        engine = RouterEngine(lambda: load(MLCEngine()),
                              replicas=args.replicas)
        print(f"loaded {args.replicas}x {args.arch} (reduced, paged) "
              f"replica pool in {time.time()-t0:.1f}s")
    else:
        backend = load(MLCEngine())
        print(f"loaded {args.arch} (reduced, "
              f"{'int4' if args.quantize else 'bf16'}) "
              f"in {time.time()-t0:.1f}s")
        engine = ServiceWorkerMLCEngine(backend)

    # index FIRST so prompts diverge inside their first KV page —
    # otherwise every conversation shares a full-page prefix and
    # affinity (correctly, but unhelpfully for a demo) herds the whole
    # batch onto one replica
    prompts = [f"{i}: request number {i}, say something" for i in
               range(args.requests)]
    results = [None] * args.requests
    lock = threading.Lock()

    def run(i):
        history = [ChatMessage("user", prompts[i])]
        rounds = 2 if args.replicas > 1 else 1   # turn 2 tests affinity
        n_chunks = 0
        usage = None
        for _ in range(rounds):
            req = ChatCompletionRequest(
                messages=list(history), model="main",
                max_tokens=args.max_tokens, seed=args.seed + i,
                stream=True,
                response_format={"type": "json_object"} if args.json
                else {"type": "text"})
            text = []
            for chunk in engine.chat_completions_create(req):
                n_chunks += 1
                if chunk.choices and chunk.choices[0].delta.content:
                    text.append(chunk.choices[0].delta.content)
                if chunk.usage:
                    usage = chunk.usage
            history.append(ChatMessage("assistant", "".join(text)))
            history.append(ChatMessage("user", "tell me more"))
        with lock:
            results[i] = (n_chunks, usage)

    t0 = time.time()
    threads = [threading.Thread(target=run, args=(i,))
               for i in range(args.requests)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.time() - t0

    total_tokens = sum(u.completion_tokens for _, u in results if u)
    print(f"{args.requests} requests, {total_tokens} completion tokens "
          f"in {wall:.2f}s -> {total_tokens/wall:.1f} tok/s aggregate")
    for i, (nc, u) in enumerate(results):
        print(f"  req{i}: chunks={nc} decode_tok/s="
              f"{u.extra.get('decode_tokens_per_s') if u else '?'}")
    if args.replicas > 1:
        st = engine.stats()
        print(f"router: dispatches={st['dispatches']} "
              f"affinity_hit_rate={st['affinity_hit_rate']:.2f} "
              f"restarts={st['restarts']} "
              f"aggregate={st['aggregate_tok_s']:.1f} tok/s")
        for p in st["per_replica"]:
            print(f"  {p['replica']}: state={p['state']} "
                  f"dispatches={p['dispatches']} served={p['served']} "
                  f"affinity_hits={p['affinity_hits']} "
                  f"restarts={p['restarts']}")
    engine.shutdown()


if __name__ == "__main__":
    main()
