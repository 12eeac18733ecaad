#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload NAME --seed N --seconds S --trace 0|1

Builds the cell named in ``BENCHMARK.json`` from its files, sets it up
(weights from the seed on the device, the model loaded through
``MLCEngine.load_model(backend="paged")``, the cell's step shapes warmed,
the traffic ramped to a full batch), measures ``--seconds``, then checks
a sample of the served answers against the plain reference.  The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics read from a profiler trace of the
window), ``device`` and, last, ``checks``: every number compared, with
its limit, also printed as the last lines of standard error.

Exits 2, printing no result, when JAX finds no TPU or fewer chips than
the cell asks for.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    cell = harness.load_cell(harness.load_spec(ROOT), args.workload, ROOT)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        log(f"bench: the cell needs {cell.chips} TPU chip(s); JAX finds "
            f"{len(devices)} {devices[0].platform} device(s). No result.")
        return 2
    log(f"device: {devices[0].platform} {devices[0].device_kind} "
        f"x{len(devices)}; compile cache {harness.use_compile_cache(ROOT)}")
    res = harness.run_cell(cell, args.seed, args.seconds,
                           trace=bool(args.trace), t0=T0, log=log)
    for name, c in res["checks"].items():
        bound = "at least" if name == "checked_tokens" else "at most"
        log(f"check {name}: {c['value']} ({bound} {c['limit']})")
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
