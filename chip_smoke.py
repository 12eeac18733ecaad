#!/usr/bin/env python3
"""Serve phi-3.5-mini at published width on one TPU through the paged engine.

    python chip_smoke.py                 # one chip: the serving main path
    python chip_smoke.py --replicas 4    # router over 4 chips vs 1 replica

The one-chip run builds random bf16 weights from ``--seed`` and then:

1. checks the fused ragged step's last-token logits for one prompt
   against the plain-jnp dense forward of ``models/model.py`` on the same
   weights (max |error| within ``LOGIT_ATOL + LOGIT_RTOL * max|ref|``);
2. loads the model with ``MLCEngine.load_model(backend="paged")`` and
   serves concurrent greedy requests plus a two-turn chat whose second
   turn must hit the prefix cache;
3. checks that every request completed with tokens, that each engine
   step issued exactly one attention kernel call, and that no logit row
   crossed to the host.

``--replicas N`` runs only the router path: N replicas of the model (full
width, depth cut to ``ROUTER_LAYERS``) behind a ``RouterEngine``, each
pinned to its own chip, against a one-replica baseline in the same
process; replicas must sit on distinct devices and answer exactly as the
baseline does.

Every failed check raises, so the exit code is non-zero and no result
line is printed.  The script exits non-zero before building anything
when JAX finds no TPU.  The last line of a passing run is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}`` as JAX
reports the device.  The persistent compilation cache is
``JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import concurrent.futures as cf
import dataclasses
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
MODEL = "phi-3.5-mini"
#: bf16 logits of two forwards that differ in summation order (paged
#: kernel in f32 over bf16 K/V vs the dense einsum) may differ by a few
#: bf16 ulps of the largest logit, plus a floor for near-zero logits
LOGIT_ATOL, LOGIT_RTOL = 0.06, 0.03
ROUTER_LAYERS = 4                  # depth of each replica on --replicas


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(cond: bool, what: str):
    if not cond:
        raise SmokeFailure(what)


class CompileClock:
    """Counts XLA backend compiles and their seconds, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax
        self.n, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration


def _chat(engine, model: str, messages, max_tokens: int):
    from repro.core import ChatCompletionRequest, ChatMessage
    return engine.chat_completions_create(ChatCompletionRequest(
        model=model, messages=[ChatMessage(**m) for m in messages],
        max_tokens=max_tokens, temperature=0.0))


def _completed(resp, what: str) -> str:
    choice = resp.choices[0]
    check(resp.usage.completion_tokens >= 1,
          f"{what}: request returned no tokens")
    check(choice.finish_reason in ("stop", "length"),
          f"{what}: finish_reason {choice.finish_reason!r}")
    return choice.message.content


def _logit_check(cfg, params, log) -> float:
    """Fused ragged step vs the dense forward on one 16-token prompt."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.paged_runner import PagedModelRunner
    from repro.models import model

    prompt = [(7 * i + 3) % cfg.vocab_size for i in range(16)]
    dense = jax.jit(lambda p, t: model.forward(
        cfg, p, t, mode="prefill")[0][0, -1])(params, jnp.asarray([prompt]))
    ref = np.asarray(dense.astype(jnp.float32))
    runner = PagedModelRunner(cfg, params, num_pages=2, page_size=16,
                              max_slots=1, pages_per_seq=1,
                              enable_prefix_cache=False)
    sid = runner.begin_seq(prompt)
    got = runner.run_step([(sid, prompt, "prefill")])[sid]
    del runner                     # frees its pools before serving
    check(bool(np.isfinite(got).all()), "fused-step logits not finite")
    err = float(np.max(np.abs(got - ref)))
    tol = LOGIT_ATOL + LOGIT_RTOL * float(np.max(np.abs(ref)))
    log(f"logits: fused ragged step vs dense forward, {ref.shape[0]} "
        f"columns: max|err| {err:.5f} (max|ref| "
        f"{float(np.max(np.abs(ref))):.3f}, tolerance {tol:.5f})")
    check(err <= tol, f"logit error {err} above tolerance {tol}")
    return err


def serve_phases(cfg, *, seed: int = 0, max_slots: int = 4,
                 max_context: int = 1024, page_size: int = 16,
                 max_tokens: int = 12, log=print) -> dict:
    """The one-chip smoke: logit check, then paged serving through the
    public API.  Raises :class:`SmokeFailure` (or the engine's own error)
    on any failed phase; returns the run's counters."""
    import jax
    from repro.core import MLCEngine
    from repro.models import model
    from repro.models.pdef import init_params

    clock = CompileClock()
    log(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"heads {cfg.n_heads}/{cfg.n_kv_heads} (q/kv), head_dim "
        f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}")
    t0 = time.perf_counter()
    params = init_params(model.params_def(cfg), jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    log(f"params: {sum(x.size for x in jax.tree.leaves(params))} "
        f"({param_bytes} bytes, random from seed {seed}) in "
        f"{time.perf_counter() - t0:.1f} s")

    err = _logit_check(cfg, params, log)

    eng = MLCEngine()
    try:
        t0 = time.perf_counter()
        eng.load_model("m", cfg, params=params, backend="paged",
                       max_slots=max_slots, max_context=max_context,
                       page_size=page_size, seed=seed)
        log(f"load_model(backend='paged', max_slots={max_slots}, "
            f"max_context={max_context}, page_size={page_size}): "
            f"{time.perf_counter() - t0:.1f} s")
        turn1 = [{"role": "user", "content":
                  "hello world this is a tiny corpus for the demo engine; "
                  "please keep this whole first turn in the prefix cache"}]
        prompts = [
            [{"role": "user", "content": "Tell me about paged attention."}],
            [{"role": "user", "content": '{"json": [1, 2.5, true]}'}],
            turn1,
        ]
        t0 = time.perf_counter()
        with cf.ThreadPoolExecutor(len(prompts)) as pool:
            futs = [pool.submit(_chat, eng, "m", p, max_tokens)
                    for p in prompts]
            resps = [f.result() for f in futs]
        texts = [_completed(r, f"request {i}") for i, r in enumerate(resps)]
        turn2 = turn1 + [{"role": "assistant", "content": texts[2]},
                         {"role": "user", "content": "and the second turn?"}]
        r2 = _chat(eng, "m", turn2, max_tokens)
        _completed(r2, "chat turn 2")
        serve_s = time.perf_counter() - t0
        cached = r2.usage.extra.get("prefix_cached_tokens", 0)
        tokens = sum(r.usage.completion_tokens for r in resps + [r2])
        log(f"served {len(resps) + 1} requests, {tokens} completion tokens "
            f"in {serve_s:.1f} s (compiles included); chat turn 2 "
            f"prefix_cached_tokens {cached}")
        check(cached > 0, "chat turn 2 missed the prefix cache")
        st = eng.stats("m")
        calls = st["runner"]["attn_kernel_calls"]
        steps = st["engine"]["exec_steps"]
        rows = st["runner"]["host_logit_rows"]
        log(f"engine: exec_steps {steps}, attn_kernel_calls {calls}, "
            f"host_logit_rows {rows}, jit_buckets "
            f"{st['runner']['jit_buckets']}")
        check(calls == steps, f"attn_kernel_calls {calls} != exec_steps "
              f"{steps}")
        check(rows == 0, f"host_logit_rows {rows} != 0")
    finally:
        eng.shutdown()
    mem = jax.devices()[0].memory_stats() or {}
    log(f"compiles: {clock.n} in {clock.seconds:.1f} s; device "
        f"peak_bytes_in_use {mem.get('peak_bytes_in_use', 'n/a')}")
    return {"logit_err": err, "tokens": tokens, "exec_steps": steps,
            "prefix_cached_tokens": cached, "param_bytes": param_bytes}


def _device_ids(engine, model: str) -> set:
    """Devices holding a loaded paged model's params and KV pools."""
    import jax
    runner = engine.models[model].runner.runner
    leaves = jax.tree.leaves((runner.params, runner.k_pages,
                              runner.v_pages))
    return {d.id for x in leaves for d in x.devices()}


def router_phases(cfg, replicas: int, *, seed: int = 0,
                  max_context: int = 256, max_tokens: int = 8,
                  per_replica: int = 2, log=print) -> dict:
    """Router path: ``replicas`` engines behind a RouterEngine, each on
    its own device, against a one-replica baseline serving the same
    prompts.  Raises on a shared device or on any answer that differs
    from the baseline's.  Each replica serves one sequence at a time, so
    a request's step shapes (and its greedy answer) do not depend on
    what else the replica is serving."""
    max_slots = 1
    from repro.core import MLCEngine, RouterEngine

    def factory():
        eng = MLCEngine()
        eng.load_model("m", cfg, backend="paged", max_slots=max_slots,
                       max_context=max_context, seed=seed)
        return eng

    prompts = [[{"role": "user", "content": f"request {i}: hello world "
                 f"this is a tiny corpus"}]
               for i in range(replicas * per_replica)]
    log(f"router: {cfg.name}, {cfg.n_layers} layers at d_model "
        f"{cfg.d_model}, {len(prompts)} greedy requests")
    results = {}
    for n in (1, replicas):
        router = RouterEngine(factory, replicas=n, heartbeat_s=0.5)
        try:
            placed = [_device_ids(e, "m") for e in router.replica_engines()]
            # first request per replica compiles; keep it out of the clock
            with cf.ThreadPoolExecutor(n) as pool:
                list(pool.map(lambda p: _chat(router, "m", p, 2),
                              prompts[:n]))
            t0 = time.perf_counter()
            with cf.ThreadPoolExecutor(len(prompts)) as pool:
                resps = list(pool.map(
                    lambda p: _chat(router, "m", p, max_tokens), prompts))
            wall = time.perf_counter() - t0
            texts = [_completed(r, f"{n}-replica request {i}")
                     for i, r in enumerate(resps)]
            after = [_device_ids(e, "m") for e in router.replica_engines()]
        finally:
            router.shutdown()
        tokens = sum(r.usage.completion_tokens for r in resps)
        for i, (ids, now) in enumerate(zip(placed, after)):
            log(f"  {n}-replica pool: replica r{i} on device ids "
                f"{sorted(ids)} (after serving: {sorted(now)})")
            check(len(ids) == 1 and ids == now,
                  f"replica r{i} is not held on one device: {ids} / {now}")
        flat = [i for ids in placed for i in ids]
        check(len(set(flat)) == len(flat),
              f"replicas share a device: {placed}")
        log(f"  {n}-replica pool: {tokens} completion tokens in "
            f"{wall:.2f} s ({tokens / wall:.1f} tok/s, host clock)")
        results[n] = texts
    check(results[replicas] == results[1],
          "router answers differ from the one-replica baseline")
    log(f"router: {replicas} replicas on distinct devices; answers equal "
        "the 1-replica baseline")
    return {"devices": replicas}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--replicas", type=int, default=0,
                    help="run only the router path over this many chips")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform "
              f"{devices[0].platform!r}); nothing run", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.configs import get_config
    from repro.launch.compile_cache import use_repo_compile_cache

    print(f"compile cache: {use_repo_compile_cache()}")
    print(f"device: {devices[0].device_kind} x{len(devices)}")
    cfg = get_config(MODEL)
    if args.replicas:
        check(len(devices) >= args.replicas,
              f"{args.replicas} replicas need as many chips, JAX finds "
              f"{len(devices)}")
        cut = dataclasses.replace(
            cfg, name=f"{cfg.name}-{ROUTER_LAYERS}L", n_layers=ROUTER_LAYERS,
            layer_pattern=cfg.layer_pattern[:ROUTER_LAYERS])
        router_phases(cut, args.replicas, seed=args.seed)
    else:
        serve_phases(cfg, seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
