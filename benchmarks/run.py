"""Benchmark runner — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--smoke]

``--smoke`` runs only the deconv traffic + autotune comparison with tiny
rep counts and emits BENCH_deconv.json (the CI perf-trajectory artifact).
Emits a ``name,us_per_call,derived`` CSV summary at the end (harness
convention) plus the full per-table reports above it."""
from __future__ import annotations

import sys


def main() -> None:
    fast = "--fast" in sys.argv
    smoke = "--smoke" in sys.argv
    reps = 10 if fast else 50

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from . import bench_deconv, bench_dse, bench_resource, bench_sparsity

    if smoke:
        print("=" * 72)
        print("Smoke: deconv HBM traffic (modeled vs measured) + autotuned "
              "vs fixed tiles")
        print("=" * 72)
        bench_deconv.main(smoke=True)
        # the artifact CI archives must validate: every section present,
        # required row keys intact, no NaN/inf leaked by a timing division
        from repro.analysis.check import check_bench_json

        report = check_bench_json("BENCH_deconv.json")
        print(report.render(strict=True))
        report.raise_if_failed(strict=True)
        return

    print("=" * 72)
    print("Table II — throughput / run-to-run variation (reverse-loop vs "
          "zero-insertion)")
    print("=" * 72)
    t2 = bench_deconv.main(reps=reps)

    print()
    print("=" * 72)
    print("Fig. 5 — design-space exploration")
    print("=" * 72)
    bench_dse.main()

    print()
    print("=" * 72)
    print("Table I — resource budget at the chosen design point")
    print("=" * 72)
    bench_resource.main()

    print()
    print("=" * 72)
    print("Fig. 6 — sparsity vs quality (zero-skipping + MMD + Eq. 6)")
    print("=" * 72)
    bench_sparsity.main()

    # ---- harness CSV summary ----------------------------------------------
    print()
    print("name,us_per_call,derived")
    for r in t2:
        if r["layer"].endswith("tpu-model") or r["rl_us"] == 0.0:
            continue
        name = f"{r['net']}_{r['layer']}"
        print(f"{name}_reverse_loop,{r['rl_us']:.1f},"
              f"gops={r['rl_gops']:.2f};cv={r['rl_cv']:.3f}")
        print(f"{name}_zero_insertion,{r['zi_us']:.1f},"
              f"gops={r['zi_gops']:.2f};cv={r['zi_cv']:.3f}")


if __name__ == "__main__":
    main()
