"""Benchmark registry — one module per paper table/figure + framework
benches.  Prints ``name,us_per_call,derived`` CSV and records the same
rows as JSON so the perf trajectory is tracked in-repo.

    PYTHONPATH=src python -m benchmarks.run [--only table1] [--smoke]
                                            [--report BENCH_ragged_step.json]

``--smoke`` runs every module that supports it in a seconds-scale
configuration (tiny shapes, few steps) — wired into tier-1 via
``tests/test_tooling.py`` so benchmark scripts can't silently bit-rot.
Modules whose ``run()`` doesn't take a ``smoke`` kwarg are reported as
``SKIP`` in smoke mode rather than silently dropped.  ``--report``
(default ``BENCH_ragged_step.json`` at the repo root; pass an empty
string to disable) writes ``{"smoke": ..., "rows": [[name, us_per_call,
derived], ...]}`` after the run — full-registry runs only: a partial
``--only`` run never clobbers the recorded trajectory.
"""
from __future__ import annotations

import argparse
import inspect
import json
import sys
import traceback
from pathlib import Path

REGISTRY = [
    # (module, description)
    ("benchmarks.table1_retention",
     "paper Table 1: engine-vs-native decode throughput retention"),
    ("benchmarks.engine_throughput",
     "continuous batching: aggregate tok/s vs concurrency + TTFT/ITL"),
    ("benchmarks.grammar_bench",
     "structured generation: per-step token-mask latency"),
    ("benchmarks.kernel_bench",
     "kernel classes: flash/paged/chunked-prefill attention, w4a16, rmsnorm"),
    ("benchmarks.prefix_cache_bench",
     "radix prefix cache: turn-2 prefill latency + tok/s, cached vs cold"),
    ("benchmarks.roofline_report",
     "dry-run roofline table summary (reads benchmarks/dryrun_results)"),
    ("benchmarks.router_bench",
     "replicated serving: pool aggregate tok/s + prefix-affinity hit rate"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny configs / few steps; CI bit-rot guard")
    ap.add_argument("--report", default="BENCH_ragged_step.json",
                    help="JSON report path relative to the repo root "
                         "('' disables; skipped for partial --only runs "
                         "so they can't clobber a full-registry record)")
    args = ap.parse_args()

    from repro.launch.compile_cache import use_repo_compile_cache
    use_repo_compile_cache()
    print("name,us_per_call,derived")
    failures = 0
    report_rows = []
    for mod_name, desc in REGISTRY:
        if args.only and args.only not in mod_name:
            continue
        try:
            mod = __import__(mod_name, fromlist=["run"])
            kwargs = {}
            if args.smoke:
                if "smoke" not in inspect.signature(mod.run).parameters:
                    print(f"{mod_name},SKIP,no-smoke-mode", flush=True)
                    continue
                kwargs["smoke"] = True
            for row in mod.run(**kwargs):
                report_rows.append([str(x) for x in row])
                print(",".join(str(x) for x in row), flush=True)
        except Exception:
            failures += 1
            print(f"{mod_name},ERROR,", flush=True)
            traceback.print_exc(file=sys.stderr)
    # record the trajectory only for CLEAN full-registry runs: a partial
    # --only run or a run with module failures must not clobber the last
    # complete record (smoke runs do write — tier-1 keeps it fresh)
    if args.report and not args.only and not failures:
        path = Path(__file__).resolve().parents[1] / args.report
        path.write_text(json.dumps(
            {"smoke": args.smoke, "rows": report_rows}, indent=1) + "\n")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
