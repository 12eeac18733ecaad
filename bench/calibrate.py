#!/usr/bin/env python3
"""Readings that the check's limits are set from, several seeds in one
process (set-up is long, so one process serves them all).

    python3 bench/calibrate.py --workload NAME --seeds 1 2 3 --seconds 10

For each seed: one run of the cell as ``bench/run.py`` makes it, plus the
controls, the reference in int8 and in fp8 put in the program's place and
read at the same positions.  Prints one JSON line per seed with the
program's readings (``mean_gap``, ``max_gap``) and the controls'
(``int8_mean_gap``, ...) under ``readings``, and each control's verdict
with the cell's own limits under ``controls`` (a sound limit makes every
control's ``correct`` false).  ``--trace`` traces the first seed's window and,
with ``--dump DIR``, writes that trace's structure and an excerpt there.
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
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", action="store_true")
    ap.add_argument("--dump", type=Path)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    cell = harness.load_cell(harness.load_spec(ROOT), args.workload, ROOT)
    import jax
    if jax.devices()[0].platform != "tpu":
        log("calibrate: JAX finds no TPU")
        return 2
    log(f"compile cache {harness.use_compile_cache(ROOT)}")
    t0 = T0
    for i, seed in enumerate(args.seeds):
        traced = args.trace and i == 0
        res = harness.run_cell(cell, seed, args.seconds, trace=traced,
                               t0=t0, control=True,
                               dump=args.dump if traced else None, log=log)
        print(json.dumps({"seed": seed, "traced": traced,
                          "wall_s": time.perf_counter() - t0, **res}),
              flush=True)
        t0 = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
