#!/usr/bin/env python3
"""Smoke run of the DCNN serving path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the mesh-sharded engine on four chips

One chip: the paper's CelebA generator (Fig. 4: z=100 -> 1024 -> 512 ->
256 -> 128 -> 3, 64x64 images) at full width, with random weights from
``--seed``, is served through `DcnnServeEngine.from_config` (Pallas
kernels, per-bucket `NetworkPlan`s from the autotune model) in fp32 and in
int8, then through `AsyncServeFrontend` for two tenants.  Every output is
checked against a reference run on the same chip.  ``--chips 4`` runs only
an fp32 engine on a four-device serving mesh against a one-device engine.

Earlier lines report each phase: errors, compile and plan counts, and wall
times (smoke timings that include compilation; not a benchmark).  The last
line is ``{"ok": true, "device": {"platform", "kind", "count"}}``.  A
failed phase raises and exits non-zero before that line, and so does a run
in which JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

import numpy as np

REPO = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

# fp32: at default precision the TPU runs an f32 matmul as bf16 passes
# (8 significant bits).  Emulating one bf16 pass per layer (bf16 operands,
# f32 accumulation) through the full-width CelebA tower on the CPU gives a
# max error of 0.63% of max|ref| at batch 16; the bound leaves 5x headroom.
# A misplaced tap or halo row errs by the size of the signal itself.
FP32_TOL = 2.0 ** -5
# int8: the int32 accumulators are exact and the epilogue is the same f32
# math, so kernel and reference agree except where a requant lands on a
# rounding tie and one intermediate activation moves by one int8 step:
# 1/127 of its calibrated range.
INT8_TOL = 1.0 / 127
REQUEST_ROWS = (1, 5, 37, 64)   # mixed sizes; 5 and 37 are ragged


def log(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def rel_err(got: np.ndarray, ref: np.ndarray) -> float:
    """max |got - ref| over max |ref| (the outputs are tanh images)."""
    assert got.shape == ref.shape, (got.shape, ref.shape)
    assert np.isfinite(got).all(), "non-finite output"
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


def serve(eng, zs):
    """Submit every request, then collect each (bucketed queue path)."""
    rids = [eng.submit(z) for z in zs]
    return [eng.collect(rid) for rid in rids]


def kernel_calls(eng, bucket: int) -> int:
    """Mosaic kernels in the bucket's program.  An interpret-mode kernel
    lowers to plain HLO and is not counted."""
    import jax.numpy as jnp

    z = jnp.zeros((bucket, eng.cfg.z_dim), jnp.float32)
    return eng._get_fn(bucket).lower(eng.params, z).as_text().count(
        "tpu_custom_call")


def check_engine(phase: str, eng, outs, refs, tol: float) -> None:
    errs = [rel_err(o, r) for o, r in zip(outs, refs)]
    n_layers = len(eng.cfg.layers)
    # compile count first: lowering below may trace the bucket programs
    assert eng.total_compiles <= len(eng.buckets), (
        eng.total_compiles, eng.buckets)
    for b in eng.buckets:
        n = kernel_calls(eng, b)
        assert n == n_layers, f"bucket {b}: {n} Mosaic kernels, not {n_layers}"
    log(phase, rel_err_max=max(errs), tol=tol,
        rel_err_per_request=[f"{e:.3e}" for e in errs],
        compiles=eng.total_compiles, buckets=list(eng.buckets),
        plans_built=eng.plan_stats["builds"],
        plan_seconds=round(eng.plan_stats["build_seconds"], 2),
        tiles={b: [t.as_kwargs() for t in eng.tile_choices[b].values()]
               for b in (eng.buckets[0], eng.buckets[-1])})
    assert max(errs) <= tol, f"{phase}: rel error {max(errs)} > {tol}"


def fp32_reference(params, cfg):
    import jax

    from repro.models.dcnn import generator_apply

    @jax.jit
    def ref(p, z):
        with jax.default_matmul_precision("highest"):
            return generator_apply(p, cfg, z, backend="reverse_loop")

    return lambda z: np.asarray(ref(params, z))


def one_chip(params, cfg, zs) -> None:
    import jax

    from repro.quant import quantize_params, quantized_generator_ref
    from repro.serve import (AsyncServeFrontend, DcnnServeEngine,
                             EngineConfig, TenantClass)

    ref32 = fp32_reference(params, cfg)
    refs32 = [ref32(z) for z in zs]

    t0 = time.perf_counter()
    eng = DcnnServeEngine.from_config(
        EngineConfig(model=cfg, backend="pallas", precision="fp32",
                     max_batch=64, warmup=True), params)
    t1 = time.perf_counter()
    outs = serve(eng, zs)
    log("fp32", build_and_warmup_s=round(t1 - t0, 1),
        serve_s=round(time.perf_counter() - t1, 2))
    check_engine("fp32", eng, outs, refs32, FP32_TOL)

    t0 = time.perf_counter()
    eng8 = DcnnServeEngine.from_config(
        EngineConfig(model=cfg, backend="pallas", precision="int8",
                     max_batch=64, warmup=True), params)
    t1 = time.perf_counter()
    outs8 = serve(eng8, zs)
    log("int8", build_and_warmup_s=round(t1 - t0, 1),
        serve_s=round(time.perf_counter() - t1, 2))
    qcfg = eng8.quant_cfg
    qp = quantize_params(params, cfg, qcfg)
    ref8 = jax.jit(lambda q, z: quantized_generator_ref(q, cfg, qcfg, z))
    refs8 = [np.asarray(ref8(qp, z)) for z in zs]
    log("int8", bit_equal_share=float(np.mean(
        [np.mean(o == r) for o, r in zip(outs8, refs8)])))
    check_engine("int8", eng8, outs8, refs8, INT8_TOL)

    # two tenants through the async frontend, over one fp32 and one int8
    # engine: "strict" never degrades, "bulk" may be served in int8
    t0 = time.perf_counter()
    fe = AsyncServeFrontend.from_config(
        EngineConfig(model=cfg, backend="pallas", max_batch=8), params,
        [TenantClass("strict", priority=0, allow_degrade=False),
         TenantClass("bulk", priority=1)],
        precisions=("fp32", "int8"))
    try:
        reqs = [(fe.submit(z, tenant), tenant, z) for tenant, z in (
            ("strict", zs[1]), ("bulk", zs[0]), ("bulk", zs[1][:3]),
            ("strict", zs[0]), ("bulk", zs[1][:2]))]
        results = [(fe.result(rid, timeout_s=600), tenant, z)
                   for rid, tenant, z in reqs]
        stats = fe.stats()
    finally:
        fe.close()
    for y, tenant, z in results:
        assert y.shape == (len(z), cfg.img_hw, cfg.img_hw, cfg.img_c)
        assert np.isfinite(y).all(), f"{tenant}: non-finite output"
    strict = [rel_err(y, ref32(z)) for y, t, z in results if t == "strict"]
    log("frontend", resolved=f"{len(results)}/{len(reqs)}",
        strict_rel_err_max=max(strict),
        downgraded={t: s["downgraded"] for t, s in stats["tenants"].items()},
        wall_s=round(time.perf_counter() - t0, 1))
    assert max(strict) <= FP32_TOL


def four_chips(params, cfg, zs) -> None:
    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_serving_mesh
    from repro.serve import DcnnServeEngine, EngineConfig

    t0 = time.perf_counter()
    mesh_eng = DcnnServeEngine.from_config(
        EngineConfig(model=cfg, backend="pallas", max_batch=64,
                     mesh=make_serving_mesh(4)), params)
    one_eng = DcnnServeEngine.from_config(
        EngineConfig(model=cfg, backend="pallas", max_batch=64), params)
    assert all(b % 4 == 0 for b in mesh_eng.buckets), mesh_eng.buckets
    outs_mesh = serve(mesh_eng, zs)
    outs_one = serve(one_eng, zs)
    errs = [rel_err(m, o) for m, o in zip(outs_mesh, outs_one)]
    # work lands on every device: the images shard over all four, and
    # each holds a full replica of the params
    b = mesh_eng.buckets[-1]
    y = mesh_eng._get_fn(b)(mesh_eng.params,
                            jnp.zeros((b, cfg.z_dim), jnp.float32))
    out_devices = len(y.sharding.device_set)
    assert out_devices == 4 and not y.sharding.is_fully_replicated
    for leaf in jax.tree_util.tree_leaves(mesh_eng.params):
        assert leaf.sharding.is_fully_replicated
        assert len(leaf.sharding.device_set) == 4
    assert mesh_eng.total_compiles <= len(mesh_eng.buckets)
    log("mesh4", rel_err_vs_one_chip_max=max(errs), tol=FP32_TOL,
        buckets=list(mesh_eng.buckets), per_device_batch=b // 4,
        output_devices=out_devices, params_replicated_on=4,
        compiles=mesh_eng.total_compiles,
        wall_s=round(time.perf_counter() - t0, 1))
    assert max(errs) <= FP32_TOL


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" or len(jax.devices()) < args.chips:
        sys.exit(f"chip_smoke: needs {args.chips} TPU chip(s); JAX found "
                 f"{len(jax.devices())} {dev.platform} device(s)")

    from repro.core.dse import planning_device
    from repro.kernels import autotune
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.dcnn import CELEBA_DCNN, generator_init

    log("device", kind=dev.device_kind, count=len(jax.devices()),
        planning=planning_device().name,
        compile_cache=enable_compile_cache(),
        timings="smoke wall clock incl. compilation, not a benchmark")
    # tiles come from the autotune model alone: a cache in the checkout,
    # emptied first, so the run depends only on committed files
    os.environ["REPRO_AUTOTUNE_CACHE"] = str(REPO / ".autotune_cache.json")
    autotune.clear_cache()

    cfg = CELEBA_DCNN
    params, _ = generator_init(jax.random.PRNGKey(args.seed), cfg)
    rng = np.random.RandomState(args.seed)
    zs = [rng.randn(n, cfg.z_dim).astype(np.float32) for n in REQUEST_ROWS]
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(params, cfg, zs)
    log("done", wall_s=round(time.perf_counter() - t0, 1))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
