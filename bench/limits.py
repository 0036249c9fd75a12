#!/usr/bin/env python3
"""Readings from which a configuration's correctness limits are set.

    python3 bench/limits.py --workload celeba-bulk --seeds 1,2,3 --seconds 2

For each seed, in one process: a run of the cell as it is timed (its
program at its own size and load, for ``--seconds``), read against the
plain reference, and the controls read on the same rows:

* ``bf16`` -- the plain reference in the program's place, computed in
  bfloat16 throughout (`bench.run.CONTROLS`): one step below the stated
  float32 storage;
* ``int8`` -- a second run with the program's own int8 path
  (`EngineConfig.precision`) in the place of the float32 path.

Each prints one JSON line with the numbers compared.  A limit lies above
the largest reading of the program and below the smallest reading of a
control (see PERF.md).  ``--int8-seeds`` limits the int8 runs to the
first few seeds.  Like `bench/run.py`, it runs only on a TPU.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--int8-seeds", type=int, default=3)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import run as bench_run

    spec = bench_run.Spec(args.workload)
    bench_run.use_checkout_caches()
    import jax

    if jax.devices()[0].platform != "tpu":
        sys.exit("limits: needs a TPU")

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    def emit(seed, variant, correct, checks):
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "variant": variant, "correct": correct,
                          "checks": {k: v["value"]
                                     for k, v in checks.items()}}),
              flush=True)

    seeds = [int(s) for s in args.seeds.split(",")]
    for i, seed in enumerate(seeds):
        line = bench_run.run_cell(
            spec, seed, args.seconds, False, time.perf_counter(),
            controls=tuple(bench_run.CONTROLS), log=log)
        emit(seed, "program", line["correct"], line["checks"])
        for name, checks in line["control_checks"].items():
            emit(seed, name, bench_run.is_correct(checks), checks)
        if i < args.int8_seeds:
            line = bench_run.run_cell(
                spec, seed, args.seconds, False, time.perf_counter(),
                precision="int8", log=log)
            emit(seed, "int8", line["correct"], line["checks"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
