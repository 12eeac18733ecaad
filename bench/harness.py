"""One run of one cell: set-up, the measured window, the check.

Everything a cell is made of is found by name: ``BENCHMARK.json`` names
the cell's configuration file and traffic mix, the traffic mix is
``bench/traffic/<traffic>.json``, the plain reference is
``bench/references/<reference>.py`` as the configuration file names it,
and each metric is read by ``bench/metrics/<metric>.py``.  The
configuration file states the program's model beyond its plain sizes
(layer kinds and their sub-configurations) in ``program``, and its
reference counts the model's operations and bytes.  A configuration, a
cell or a metric is added with files and an entry in ``BENCHMARK.json``
alone.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import json
import os
import shutil
import tempfile
import threading
import time
import typing
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import traffic as gen
from bench.readers import delta
from bench.serve import ClosedLoop, Record, clock
from bench.tokenizer import IdTokenizer

ROOT = Path(__file__).resolve().parents[1]
#: the name of the engine's loop thread, joined once the engine shuts down
ENGINE_LOOP = "repro-engine-loop"
#: the controls: the reference with its weights in these precisions
CONTROLS = ("int8", "fp8")


# -- the cell, from its files ---------------------------------------------
@dataclass
class Cell:
    name: str
    chips: int
    conf: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path = ROOT

    @property
    def serving(self) -> dict:
        return self.conf["serving"]


def load_spec(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def cell_names(spec: dict) -> List[str]:
    return [w["name"] for w in spec["workloads"]]


def load_cell(spec: dict, name: str, root: Path = ROOT) -> Cell:
    try:
        w = next(w for w in spec["workloads"] if w["name"] == name)
    except StopIteration:
        raise KeyError(f"no workload {name!r}; there are "
                       f"{cell_names(spec)}") from None
    c = next(c for c in spec["configs"] if c["name"] == w["config"])
    with open(root / c["file"]) as f:
        conf = json.load(f)
    with open(root / "bench" / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(name, w["chips"], conf, traffic, e2e, per_layer, root)


def _module(path: Path, prefix: str):
    name = prefix + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, root: Path = ROOT) -> Callable:
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    return _module(root / "bench" / "metrics" / f"{name}.py",
                   "bench_metric_").read


@functools.lru_cache(maxsize=None)
def _reference_at(path: Path):
    return _module(path, "bench_reference_")


def reference(conf: dict, root: Path = ROOT):
    """The plain reference ``bench/references/<reference>.py`` that the
    configuration file names, from the cell's root.  Besides the model it
    holds the model's counts: ``attention_flops``, ``attention_bytes``
    and ``model_flops`` of the window's rows."""
    return _reference_at(
        root / "bench" / "references" / f"{conf['reference']}.py")


# -- the system under test --------------------------------------------------
def _layer_pattern(pattern: list) -> tuple:
    from repro.configs import base
    kinds = (typing.get_args(base.MixerKind), typing.get_args(base.FFNKind))
    for spec in pattern:
        for kind, known in zip(spec, kinds):
            if kind not in known:
                raise ValueError(f"program: layer kind {kind!r} is none of "
                                 f"{known}")
    return tuple(base.LayerSpec(*spec) for spec in pattern)


def _program(prog: dict) -> dict:
    """``ModelConfig`` fields from a configuration file's ``program``:
    ``layer_pattern`` as ``[mixer, ffn]`` pairs, a sub-configuration
    (``moe``, ``mla``, ``mamba``, ``rwkv6``, ...) as an object of its
    fields, any other field as given."""
    from repro.configs.base import ModelConfig
    hints = typing.get_type_hints(ModelConfig)
    out = {}
    for k, v in prog.items():
        if k not in hints:
            raise ValueError(f"program: {k!r} is no field of ModelConfig")
        sub = [t for t in (hints[k], *typing.get_args(hints[k]))
               if dataclasses.is_dataclass(t)]
        if k == "layer_pattern":
            v = _layer_pattern(v)
        elif sub and isinstance(v, dict):
            known = {f.name for f in dataclasses.fields(sub[0])}
            unknown = sorted(set(v) - known)
            if unknown:
                raise ValueError(f"program: {k!r} ({sub[0].__name__}) has no "
                                 f"field {', '.join(unknown)}")
            v = sub[0](**v)
        out[k] = v
    return out


def program_config(conf: dict, root: Path = ROOT):
    """The program's model configuration, from the configuration file:
    the plain sizes its reference reads, and over them ``program``."""
    from repro.configs.base import ModelConfig
    s = reference(conf, root).sizes_of(conf)
    kw = dict(
        name=conf["name"], n_layers=s.layers, d_model=s.d, n_heads=s.heads,
        n_kv_heads=s.kv_heads, head_dim=s.head_dim, d_ff=s.d_ff,
        vocab_size=s.vocab, rope_theta=s.rope_theta, norm_eps=s.eps,
        act=conf["hidden_act"], tie_embeddings=conf["tie_word_embeddings"],
        max_context=conf["max_position_embeddings"], source=conf["source"])
    prog = conf.get("program", {})
    # the sizes the reference reads are the published keys'; the program
    # may restate only the head size (a latent attention's differs)
    fixed = sorted(set(prog) & (set(kw) - {"head_dim"}))
    if fixed:
        raise ValueError(f"program: {', '.join(fixed)} comes from the "
                         f"published keys, not from program")
    kw.update(_program(prog))
    n = len(kw.get("layer_pattern", ()))
    if n and n != kw["n_layers"]:
        raise ValueError(f"program: layer_pattern has {n} layers, the "
                         f"configuration {kw['n_layers']}")
    return ModelConfig(**kw)


def warm_buckets(cell: Cell) -> List[tuple]:
    """The fused-step ``(rows, chunk)`` buckets the window meets, as the
    traffic file lists them (the ramp's own are built as it runs)."""
    return [tuple(b) for b in cell.traffic["warm"]["buckets"]]


class CompileClock:
    """Counts programs built, from JAX's own monitoring events: every
    build is timed as a backend compile, and those the persistent cache
    served are counted apart as ``hits``."""

    def __init__(self):
        import jax
        self.n, self.seconds, self.hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._hit)

    def _on(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration

    def _hit(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def use_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else the fixed ``<checkout>/.jax_cache``."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class GcClock:
    """Times Python's garbage collections while it is entered: a pause
    of the collector stalls the engine's host loop."""

    def __enter__(self):
        self.n, self.seconds, self.longest, self._t = 0, 0.0, 0.0, None
        gc.callbacks.append(self._on)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on)

    def _on(self, phase: str, info: dict):
        if phase == "start":
            self._t = clock()
        elif self._t is not None:
            d = clock() - self._t
            self.n, self.seconds = self.n + 1, self.seconds + d
            self.longest = max(self.longest, d)


# -- what a metric reader sees ---------------------------------------------
@dataclass
class Run:
    cell: Cell
    sizes: object
    records: List[Record]
    t_open: float
    t_close: float
    stats_open: dict
    stats_close: dict
    setup_s: float
    peak: dict
    #: what counts the model's operations and bytes: the configuration's
    #: reference (``attention_flops``, ``attention_bytes``, ``model_flops``)
    ref: object
    trace: Optional[object] = None

    def window_rows(self) -> List[tuple]:
        """The rows the window's tokens required, as ``(start, n)``, from
        the benchmark's own requests: a decode row at its real context
        for every token streamed in the window after a request's first,
        and for each prompt the positions its prefill covered inside the
        window, taken as spread evenly over the time from its submission
        to its first token (a prompt with no first token is left out)."""
        a, b = self.t_open, self.t_close
        rows = []
        for r in self.records:
            if not r.chunks:
                continue
            P, t_first = len(r.req.prompt), r.chunks[0][0]
            span = max(t_first - r.t_submit, 1e-9)
            lo = min(max((a - r.t_submit) / span, 0.0), 1.0)
            hi = min(max((b - r.t_submit) / span, 0.0), 1.0)
            i, j = round(P * lo), round(P * hi)
            if j > i:
                rows.append((i, j - i))
            k = 0
            for t, n in r.chunks:
                if a <= t < b:
                    rows += [(P + m - 1, 1) for m in range(max(k, 1), k + n)]
                k += n
        return rows


# -- the check --------------------------------------------------------------
def _finished_greedy(records: List[Record]) -> List[Record]:
    return [r for r in records if r.req.greedy and r.finish == "length"
            and r.tokens == r.req.max_tokens]


def check_answers(cell: Cell, sizes, key, records: List[Record], seed: int,
                  control: bool = False, log=print) -> dict:
    """Compare a sample of finished greedy answers with the reference.

    For every served token, the gap by which its reference logit lies
    below the reference's best at that position; the reading is the
    widest gap over the sample.  With ``control`` the same positions are
    read for the token that the reference in int8, and in fp8, puts
    first (the controls)."""
    ref = reference(cell.conf, cell.root)
    tok = IdTokenizer(sizes.vocab)
    ck = cell.traffic["check"]
    done = _finished_greedy(records)
    rng = np.random.default_rng([seed, 1])
    pick = []
    if done:
        longest = max(done, key=lambda r: r.tokens)
        rest = [r for r in done if r is not longest]
        pick = [longest] + [rest[i] for i in rng.permutation(len(rest))[
            :ck["requests"] - 1]]
    T = cell.serving["max_context"]
    K = cell.traffic["output_tokens"][1]
    n = ck["requests"]              # one shape: one compile, then cached
    tokens = np.zeros((n, T), np.int32)
    at = np.zeros((n, K), np.int32)
    served = np.full((n, K), -1, np.int64)
    for i, r in enumerate(pick):
        p, out = r.req.prompt, np.asarray(tok.encode(r.text))
        seq = np.concatenate([p, out[:-1]])
        tokens[i, :len(seq)] = seq
        at[i, :len(out)] = len(p) - 1 + np.arange(len(out))
        served[i, :len(out)] = out
    out = {"checked_requests": len(pick),
           "checked_tokens": int((served >= 0).sum())}
    if not pick:
        return out
    logits = ref.logits_at(sizes, key, tokens, at)
    best = logits.max(-1)
    mask = served >= 0
    got = np.take_along_axis(logits, np.maximum(served, 0)[..., None],
                             -1)[..., 0]
    gap = (best - got)[mask]
    out.update(max_gap=float(gap.max()), mean_gap=float(gap.mean()),
               mismatch_share=float(np.mean(gap > 0)))
    for low in (CONTROLS if control else ()):
        top = ref.logits_at(sizes, key, tokens, at, low=low).argmax(-1)
        cg = (best - np.take_along_axis(logits, top[..., None], -1)[..., 0]
              )[mask]
        out.update({f"{low}_max_gap": float(cg.max()),
                    f"{low}_mean_gap": float(cg.mean()),
                    f"{low}_mismatch_share": float(np.mean(cg > 0))})
    return out


def limits(cell: Cell) -> dict:
    return cell.traffic["check"]["limits"]


def verdict(cell: Cell, counts: dict, check: dict) -> Dict[str, dict]:
    """Each number compared beside its limit: the readings the traffic
    file gives limits for (``checked_tokens`` a floor, the rest
    ceilings), failed requests and short answers."""
    v = {k: {"value": check.get(k), "limit": x}     # None: nothing read
         for k, x in limits(cell).items()}
    v["failed"] = {"value": counts["failed"], "limit": 0}
    v["short_answers"] = {"value": counts["short"], "limit": 0}
    return v


def control_verdict(cell: Cell, counts: dict, check: dict,
                    low: str) -> Dict[str, dict]:
    """The verdict with the control in the program's place: the readings
    of the token that the reference in ``low`` precision puts first,
    compared with the cell's own limits."""
    c = dict(check)
    for k in ("max_gap", "mean_gap", "mismatch_share"):
        c[k] = check.get(f"{low}_{k}")
    return verdict(cell, counts, c)


def passed(v: Dict[str, dict]) -> bool:
    def ok(k, c):
        if c["value"] is None:
            return False
        if k == "checked_tokens":
            return c["value"] >= c["limit"]
        return c["value"] <= c["limit"]
    return all(ok(k, c) for k, c in v.items())


# -- one run ----------------------------------------------------------------
def run_cell(cell: Cell, seed: int, seconds: float, trace: bool = False,
             t0: Optional[float] = None, control: bool = False,
             peak: Optional[dict] = None, dump: Optional[Path] = None,
             log=print) -> dict:
    """Set up, measure ``seconds``, check; returns the result line.

    ``control`` also reads the controls at the checked positions and
    judges each with the cell's limits (``controls``);
    ``peak`` stands in for the peak table's row off the chip; ``dump``
    receives the trace's structure and an excerpt of it."""
    import jax
    from repro.core import MLCEngine
    t0 = clock() if t0 is None else t0
    ref = reference(cell.conf, cell.root)
    sizes = ref.sizes_of(cell.conf)
    key = ref.seed_key(seed)
    dev = jax.devices()[0]
    compiles = CompileClock()
    if peak is None:
        with open(cell.root / "bench" / "peaks.json") as f:
            peaks = json.load(f)
        if dev.device_kind not in peaks:
            raise KeyError(f"no peaks for device kind {dev.device_kind!r}")
        peak = peaks[dev.device_kind]

    params = ref.program_params(sizes, key)
    jax.block_until_ready(params)
    log(f"weights: {sum(x.nbytes for x in jax.tree.leaves(params))} bytes "
        f"made on the device from the seed in {clock() - t0:.1f} s")
    tok = IdTokenizer(sizes.vocab)
    eng = MLCEngine()
    try:
        eng.load_model("m", program_config(cell.conf, cell.root),
                       params=params, tokenizer=tok, backend="paged",
                       **cell.serving)
        del params
        runner = eng.models["m"].runner.runner
        n = runner.warmup(sizes.vocab, buckets=warm_buckets(cell),
                          greedy=tuple(cell.traffic["warm"]["all_greedy"]))
        log(f"warm-up: {n} fused-step buckets; {compiles.n} programs "
            f"built ({compiles.seconds:.1f} s, {compiles.hits} from the "
            f"persistent cache) so far")
        plans = gen.closed_loop(cell.traffic, sizes.vocab, seed)
        loop = ClosedLoop(eng, "m", plans, tok)
        t_open = loop.start(timeout=cell.traffic["ramp_timeout_s"])
        setup_s = t_open - t0
        c_open = (compiles.n, compiles.seconds, compiles.hits)

        def window(a: float):
            """Serve ``seconds`` from ``a``, with the counters read and
            the collector timed at its two ends."""
            st = eng.stats("m")
            with GcClock() as g:
                time.sleep(max(0.0, a + seconds - clock()))
                b = clock()
            return a, b, st, eng.stats("m"), g

        tr = None
        if trace:
            tr = _traced_window(lambda: window(clock()), dump)
            t_open, t_close, st_open, st_close, gcs = tr.pop("window")
        else:
            t_open, t_close, st_open, st_close, gcs = window(t_open)
        c_window = (compiles.n - c_open[0], compiles.seconds - c_open[1],
                    compiles.hits - c_open[2])
        # until enough greedy answers have finished to check, the loop
        # runs on with the batch kept full
        need = limits(cell)["checked_tokens"]

        def enough():
            done = _finished_greedy(list(loop.records))
            return sum(r.tokens for r in done) >= need
        late = not loop.wait_until(enough, cell.traffic["drain_timeout_s"])
        loop.stop()
        mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    finally:
        eng.shutdown()
        for th in threading.enumerate():
            if th.name == ENGINE_LOOP:
                th.join(60)
    records = loop.records
    del eng, runner, loop
    gc.collect()
    ms = dev.memory_stats() or {}
    log(f"device bytes in use once the engine is freed: "
        f"{ms.get('bytes_in_use')}; peak while serving {mem} of "
        f"{ms.get('bytes_limit')}")

    run = Run(cell, sizes, records, t_open, t_close, st_open, st_close,
              setup_s, peak, ref)
    if trace:
        run.trace = tr["trace"]
    d = lambda *path: delta(st_open, st_close, *path)
    log(f"programs built: {c_open[0]} in set-up ({c_open[1]:.1f} s, "
        f"{c_open[2]} from the persistent cache), {c_window[0]} inside the "
        f"window ({c_window[1]:.1f} s, {c_window[2]} from the cache); step "
        f"buckets first met in the window: {d('runner', 'jit_buckets')}")
    in_win = sum(n for r in records for t, n in r.chunks
                 if t_open <= t < t_close)
    fin = [r for r in records if r.finish == "length"
           and t_open <= r.chunks[-1][0] < t_close]
    sent = [r for r in records if t_open <= r.t_submit < t_close]
    ts = sorted({t for r in records for t, _ in r.chunks
                 if t_open <= t < t_close})
    gaps = sorted(np.diff(ts).tolist(), reverse=True)
    long = [g for g in gaps if g > 0.25]
    at = ts[int(np.argmax(np.diff(ts)))] - t_open if gaps else 0.0
    log(f"window stalls: the longest gaps between streamed tokens "
        f"{[round(1000 * g, 1) for g in gaps[:5]]} ms (the longest from "
        f"{at:.3f} s into the window), {len(long)} over "
        f"250 ms ({sum(long):.3f} s); Python's "
        f"garbage collector ran {gcs.n} times in the window for "
        f"{1000 * gcs.seconds:.1f} ms, the longest "
        f"{1000 * gcs.longest:.1f} ms")
    log(f"window {t_close - t_open:.3f} s: {in_win} output tokens, "
        f"{len(fin)} requests finished, {len(sent)} sent; closed loop of "
        f"{cell.traffic['clients']} clients (no schedule, no lateness)")
    steps = d("engine", "exec_steps")
    per = lambda k: (st_close["engine"][k] * st_close["engine"]["exec_steps"]
                     - st_open["engine"][k] * st_open["engine"]["exec_steps"]
                     ) / max(steps, 1)
    log(f"window steps: {steps} "
        f"({1000 * (t_close - t_open) / max(steps, 1):.2f} ms each on the "
        f"host clock; host {per('host_ms_per_step'):.2f} ms a step), "
        f"decode tokens "
        f"{d('runner', 'decode_tokens')}, prefill tokens "
        f"{d('runner', 'prefill_tokens')}, preemptions "
        f"{d('scheduler', 'preemptions')}")
    counts = {"attempted": sum(r.t_submit < t_close for r in records),
              "failed": sum(r.error is not None for r in records),
              "short": sum(r.finish == "length"
                           and r.tokens != r.req.max_tokens
                           for r in records)}
    if late:
        log("greedy answers under way at the close did not finish in "
            f"{cell.traffic['drain_timeout_s']} s")
    check = check_answers(cell, sizes, key, records, seed, control, log)
    v = verdict(cell, counts, check)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        val = reader(m["name"], cell.root)(run)
        if val is not None:
            metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    out = {"correct": passed(v), "attempted": counts["attempted"],
           "failed": counts["failed"], "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
        out["breakdown"] = {"device_ops": run.trace.top_ops(10),
                            "idle_gaps": run.trace.idle_gaps(10)}
    if control:
        out["controls"] = {}
        for low in CONTROLS:
            cv = control_verdict(cell, counts, check, low)
            out["controls"][low] = {"correct": passed(cv), "checks": cv}
    out["readings"] = check
    out["checks"] = v
    return out


def _traced_window(body: Callable, dump: Optional[Path] = None) -> dict:
    """Trace ``body()``, the window, as the span :data:`trace.WINDOW`;
    returns the trace and what ``body`` returned."""
    import jax
    from bench import trace as tr
    d = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        opts = jax.profiler.ProfileOptions()
        # no Python tracer: it would slow the host loop being measured
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        with jax.profiler.TraceAnnotation(tr.WINDOW):
            out = body()
        jax.profiler.stop_trace()
        t = tr.load(d)
        if dump is not None:
            dump.mkdir(parents=True, exist_ok=True)
            (dump / "structure.json").write_text(
                json.dumps(tr.structure(d), indent=1))
            (dump / "excerpt.json").write_text(
                json.dumps(tr.excerpt(t).to_json()))
        return {"trace": t, "window": out}
    finally:
        shutil.rmtree(d, ignore_errors=True)
