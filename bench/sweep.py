#!/usr/bin/env python3
"""Offered-load sweep of an open-loop cell, to find the highest rate the
system sustains (its knee).  A cell then fixes its rate in its mix file.

    python3 bench/sweep.py --workload mnist-frame --seed 5 --seconds 4 \\
        --rates 1000,2000,4000,8000

One process builds the cell's system once and offers each rate in turn
for ``--seconds``; each rate prints one JSON line: latency percentiles
from the due time, failures, the rate completed, how late the generator
ran, and the 95th percentile of the first and the last quarter of the
requests (a queue that grows through the window shows as a last quarter
far above the first).  Like `bench/run.py`, it runs only on a TPU.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, requests/s")
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import run as bench_run

    spec = bench_run.Spec(args.workload)
    if spec.mix["loop"] != "open":
        sys.exit(f"sweep: {args.workload} is not an open loop")
    bench_run.use_checkout_caches()
    import jax

    from bench import traffic

    if jax.devices()[0].platform != "tpu":
        sys.exit("sweep: needs a TPU")
    cfg = spec.cfg
    ref = bench_run.load_module(
        bench_run.BENCH / "references" / f"{cfg['reference']}.py", "ref")
    systems = bench_run.load_module(
        bench_run.BENCH / "systems" / f"{cfg['system']}.py", "sys")
    params = jax.block_until_ready(ref.init(cfg, args.seed))
    system = systems.System(cfg, params)
    sizes = traffic.row_support(spec.mix["rows"])
    system.warm(sizes)
    inputs = traffic.Inputs(args.seed, system.row_shape, max(sizes))
    try:
        with bench_run.set_up_objects_frozen(
                lambda m: print(m, file=sys.stderr)):
            for rate in (float(r) for r in args.rates.split(",")):
                mix = dict(spec.mix, rate_per_s=rate)
                schedule = traffic.open_schedule(mix, args.seconds, args.seed)
                sampler = traffic.Sampler(0, args.seed)
                t0 = time.perf_counter() + 0.05
                recs = traffic.run_open(mix, system.submit, system.result,
                                        inputs, sampler, t0, schedule)
                lat = [(r.done - r.due) * 1e3 if r.done is not None
                       else math.inf for r in recs]
                done = [r for r in recs if r.done is not None]
                span = max(r.done for r in done) - t0 if done else math.nan
                q = len(lat) // 4
                late = [(r.sent - r.due) * 1e3 for r in recs
                        if r.sent is not None]
                print(json.dumps({
                    "workload": args.workload, "rate_per_s": rate,
                    "requests": len(recs), "failed": len(recs) - len(done),
                    "completed_per_s": len(done) / span,
                    "rows_per_s": sum(r.rows for r in done) / span,
                    "p50_ms": traffic.percentile(lat, 50),
                    "p95_ms": traffic.percentile(lat, 95),
                    "p99_ms": traffic.percentile(lat, 99),
                    "p95_first_quarter_ms": traffic.percentile(lat[:q], 95),
                    "p95_last_quarter_ms": traffic.percentile(lat[-q:], 95),
                    "late_p50_ms": traffic.percentile(late, 50),
                    "late_max_ms": max(late, default=0.0)}), flush=True)
                time.sleep(0.5)
    finally:
        system.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
