"""Serving driver (the paper's actual workload): batched DCNN inference
through the plan/execute engine, with the paper's throughput and
run-to-run-variation measurement.

    PYTHONPATH=src python examples/serve_dcnn.py [--net celeba] [--reqs 20]
                                                 [--precision int8]
                                                 [--plan-json plan.json]
                                                 [--async [--slo-ms 50]]

``--plan-json`` writes the engine's largest-bucket NetworkPlan to disk —
the artifact a deployment pins next to its checkpoint and reloads with
``NetworkPlan.load`` to serve exactly the validated configuration.  If
the file already exists it is instead *loaded*: the static plan DRC
(`repro.analysis.check`) runs before the engine is built, and a plan
that fails prints the rule-by-rule report and exits 2 instead of
tracebacking out of the middle of engine setup.

``--async`` routes the stream through the SLO-aware `AsyncServeFrontend`
instead of the raw engine: requests carry a per-tenant deadline
(``--slo-ms``), admission control sheds typed what cannot make it, and
the scheduler downgrades fp32 requests onto the pinned int8 chain when
that is the only way to hold the SLO.

``--trace out.json`` turns on the `repro.obs` span tracer for the run
and writes a Chrome/Perfetto ``trace_event`` JSON on exit — open it at
https://ui.perfetto.dev to see admission, EDF queue wait, wave dispatch,
per-bucket kernel calls and collect as one timeline, with retries and
remesh events as instant markers.  Each engine bucket call shows its
upload, call, wait and copy back; each span names the span it ran under
(``parent``), and a request's ``queue_wait`` the ``wave`` that served it.
Run under ``jax.profiler`` the same spans also land in the profile, next
to the device's ops.  The line it prints counts the events exported and
those the tracer's ring dropped (the oldest, once it is full).
"""
import argparse
import os
import sys
import time

import jax
import numpy as np

import repro.workloads as workloads
from repro.launch.compile_cache import enable_compile_cache
from repro.models.dcnn import generator_init
from repro.serve import (AdmissionRejected, AsyncServeFrontend,
                         DcnnServeEngine, EngineConfig, TenantClass)


def run_async(cfg, params, args):
    """Mixed gold/std tenant stream through the async frontend."""
    fe = AsyncServeFrontend.from_config(
        EngineConfig(model=cfg, backend=args.backend,
                     max_batch=args.batch, calib_batch=32),
        params,
        [TenantClass("gold", slo_ms=args.slo_ms, priority=0),
         TenantClass("std", slo_ms=None, priority=1)],
        # a tower that is not a deconvolution chain has no int8 path
        precisions=("fp32",) if cfg.is_graph else ("fp32", "int8"), prime=1)
    try:
        rng = np.random.RandomState(0)
        rids, rejected = [], 0
        for i in range(args.reqs):
            n = args.batch if i % 3 else max(1, args.batch - i % 5)
            z = rng.randn(n, *cfg.input_shape).astype(np.float32)
            try:
                rids.append(fe.submit(z, "gold" if i % 2 == 0 else "std"))
            except AdmissionRejected as e:
                rejected += 1
                print(f"  req {i}: shed at admission ({e.stage})")
        for rid in rids:
            try:
                fe.result(rid, timeout_s=300)
            except AdmissionRejected as e:
                print(f"  req {rid}: shed in queue ({e.stage})")
        st = fe.stats()
        print(f"{cfg.name} async serving, gold slo={args.slo_ms} ms "
              f"(admission rejected {rejected}):")
        for name, t in st["tenants"].items():
            p99 = f"{t['p99_ms']:.1f} ms" if "p99_ms" in t else "n/a"
            print(f"  {name}: completed={t['completed']} "
                  f"downgraded={t['downgraded']} shed={t['shed']} "
                  f"p99={p99}")
        print(f"  pinned plans: {sorted(fe.plan_fingerprints())}")
        overlapped = fe.metrics.counter("frontend.waves_overlapped").total()
        print(f"  waves launched behind another wave: {overlapped:.0f}")
        folded = fe.metrics.counter("engine.tap_folded_layers").total()
        print(f"  tap-folded deconvolution layers over the plans built: "
              f"{folded:.0f}")
    finally:
        fe.close()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--net", default="mnist", metavar="WORKLOAD",
                    help="a registered repro.workloads name "
                         f"({', '.join(workloads.names())}); unknown "
                         "names fail typed, never fall back")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--reqs", type=int, default=20)
    ap.add_argument("--backend", default="reverse_loop",
                    choices=["reverse_loop", "xla", "pallas"])
    ap.add_argument("--precision", default="fp32", choices=["fp32", "int8"])
    ap.add_argument("--plan-json", default=None,
                    help="write the largest bucket's NetworkPlan here")
    ap.add_argument("--async", dest="use_async", action="store_true",
                    help="serve through the SLO-aware async frontend")
    ap.add_argument("--slo-ms", type=float, default=200.0,
                    help="gold-tenant latency SLO for --async (ms)")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record a Perfetto trace of the run to this path")
    args = ap.parse_args()
    enable_compile_cache()

    if args.trace:
        from repro.obs import trace as obstrace

        obstrace.enable(clear=True)

    try:
        cfg = workloads.resolve_model(args.net)
    except workloads.WorkloadError as e:
        print(e)
        sys.exit(2)
    params, _ = generator_init(jax.random.PRNGKey(0), cfg)
    try:
        if args.use_async:
            run_async(cfg, params, args)
            return
        run_sync(cfg, params, args)
    finally:
        if args.trace:
            obstrace.disable()
            tracer = obstrace.get_tracer()
            n = tracer.export(args.trace)
            print(f"trace: {n} events -> {args.trace}, {tracer.dropped} "
                  f"dropped by the {tracer.capacity}-event ring "
                  f"(open at https://ui.perfetto.dev)")


def run_sync(cfg, params, args):
    # a pre-existing --plan-json is a pinned deployment artifact: DRC it
    # statically and serve it; a fresh path is written at the end instead
    pinned = None
    if args.plan_json and os.path.exists(args.plan_json):
        from repro.analysis.check import check_plan_json
        from repro.plan import NetworkPlan

        report = check_plan_json(args.plan_json)
        if not report.ok():
            print(f"pinned plan {args.plan_json} failed design-rule check:")
            print(report.render())
            sys.exit(2)
        pinned = NetworkPlan.load(args.plan_json)
        print(f"pinned plan {pinned.stable_hash()} <- {args.plan_json} "
              f"(DRC clean: {len(report.rules_run)} rules)")

    # plan/execute engine: one EngineConfig instead of a kwarg pile, one
    # pinned NetworkPlan + compiled executable per power-of-two bucket,
    # pre-compiled by warmup; mixed request sizes never recompile.
    eng = DcnnServeEngine.from_config(
        EngineConfig(model=cfg, backend=args.backend,
                     precision=args.precision, max_batch=args.batch,
                     warmup=True, calib_batch=32),
        params, plan=pinned)

    ops_per_img = sum(g.ops for g in cfg.geometries())
    rng = np.random.RandomState(0)

    lat = []
    imgs = None
    for i in range(args.reqs):
        # mixed sizes: full batches interleaved with ragged stragglers
        n = args.batch if i % 3 else max(1, args.batch - i % 5)
        z = rng.randn(n, *cfg.input_shape).astype(np.float32)
        t0 = time.perf_counter()
        rid = eng.submit(z)
        imgs = eng.collect(rid)
        lat.append((time.perf_counter() - t0) / n)
    lat = np.array(lat)
    gops = ops_per_img / lat / 1e9
    print(f"{cfg.name} x<= {args.batch} via {args.backend}/{args.precision}: "
          f"{gops.mean():.2f} GOps/s (std {gops.std():.2f}; "
          f"cv {lat.std()/lat.mean():.3f}) — "
          f"{1000*lat.mean():.2f} ms/image, last images {imgs.shape}, "
          f"{eng.total_compiles} compiles / {eng.plan_stats['builds']} plan "
          f"builds over {len(eng.buckets)} buckets")
    if args.plan_json and pinned is None:
        plan = eng.plans[eng.max_bucket]
        plan.to_json(args.plan_json)
        print(f"pinned plan {plan.stable_hash()} -> {args.plan_json}")


if __name__ == "__main__":
    main()
