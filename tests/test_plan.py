"""Plan/execute API: DeconvPlan/NetworkPlan round-tripping, the v4
plan-hash autotune cache, plan-path vs legacy-path bit-identity on all
four execution paths (dense fp32, sparse, int8, fused-chain), and the
EngineConfig-driven serve engine."""
import dataclasses
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.tiling import DeconvGeometry
from repro.models.dcnn import (MNIST_DCNN, DcnnConfig, DeconvLayerCfg,
                               generator_apply, generator_init,
                               make_fused_generator)
from repro.plan import (PLAN_SCHEMA_VERSION, DeconvPlan, NetworkPlan,
                        PlanSchemaError, build_layer_plan,
                        build_network_plan)
from repro.serve import DcnnServeEngine, EngineConfig

# the real MNIST / CelebA layer cascades with channel counts cut down so
# interpret-mode execution stays cheap (matches test_batch_serving.py)
MNIST_SMALL = DcnnConfig(
    name="dcnn-mnist-small",
    z_dim=24, img_hw=28, img_c=1,
    layers=(
        DeconvLayerCfg(24, 32, 7, 1, 0, "relu"),
        DeconvLayerCfg(32, 16, 4, 2, 1, "relu"),
        DeconvLayerCfg(16, 1, 4, 2, 1, "tanh"),
    ),
)

CELEBA_SMALL = DcnnConfig(
    name="dcnn-celeba-small",
    z_dim=24, img_hw=64, img_c=3,
    layers=(
        DeconvLayerCfg(24, 32, 4, 1, 0, "relu"),
        DeconvLayerCfg(32, 16, 4, 2, 1, "relu"),
        DeconvLayerCfg(16, 16, 4, 2, 1, "relu"),
        DeconvLayerCfg(16, 8, 4, 2, 1, "relu"),
        DeconvLayerCfg(8, 3, 4, 2, 1, "tanh"),
    ),
)

# the Algorithm-1 OH=7/S=2/K=5 parity geometry (CelebA layer type whose
# phase structure exercises every tap path) + a non-square variant
ALGO1_GEOMS = [
    DeconvGeometry(4, 4, 6, 5, 5, 2, 2),
    DeconvGeometry(4, 6, 3, 4, 5, 2, 2),
]


@pytest.fixture
def tmp_cache(tmp_path, monkeypatch):
    from repro.kernels import autotune

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    monkeypatch.setattr(autotune, "_cache", None)
    yield tmp_path / "at.json"
    monkeypatch.setattr(autotune, "_cache", None)


def _prune(params, frac=0.6, seed=0):
    """Magnitude-prune the weight tree so sparse plans have zero blocks."""
    rng = np.random.RandomState(seed)
    out = {}
    for k, leaf in params.items():
        w = np.asarray(leaf["w"])
        mask = rng.rand(*w.shape[2:]) < frac  # prune whole (ci, co) fibers
        out[k] = {"w": jnp.asarray(np.where(mask, 0.0, w)), "b": leaf["b"]}
    return out


# ---------------------------------------------------------------------------
# DeconvPlan basics
# ---------------------------------------------------------------------------
def test_layer_plan_is_frozen_and_hashable(tmp_cache):
    g = ALGO1_GEOMS[0]
    p1 = build_layer_plan(g, batch=4, activation="relu")
    p2 = build_layer_plan(g, batch=4, activation="relu")
    assert p1 == p2 and hash(p1) == hash(p2)
    assert p1.stable_hash() == p2.stable_hash()
    with pytest.raises(dataclasses.FrozenInstanceError):
        p1.batch = 8
    # tiles resolved (the plan is executable as-is)
    assert p1.tiles is not None and p1.tiles.t_oh % g.stride == 0


def test_layer_plan_padded_geometry(tmp_cache):
    """The plan exposes the halo_pad_geometry the kernel runs at: output
    extents, tile-multiple grid, halo padding, padded channels/batch."""
    g = ALGO1_GEOMS[0]
    p = build_layer_plan(g, batch=3)
    (oh, ow, ohp, owp, pad_l, pad_rh, pad_rw, cip, cop, t_n,
     np_) = p.padded_geometry()
    assert (oh, ow) == (g.out_h, g.out_w)
    assert ohp % p.tiles.t_oh == 0 and owp % p.tiles.t_ow == 0
    assert cip % p.tiles.t_ci == 0 and cop % p.tiles.t_co == 0
    assert t_n <= 3 and np_ % t_n == 0 and np_ >= 3
    assert pad_l >= 0 and pad_rh >= 0 and pad_rw >= 0


def test_stable_hash_scopes_and_aliasing(tmp_cache):
    """Tile-scope hashes split on every tile-planning input and nothing
    else; full-scope hashes additionally pin the epilogue + tiles."""
    g = ALGO1_GEOMS[0]
    base = DeconvPlan(geometry=g, batch=4, dtype="float32")
    assert base.stable_hash("tiles") == dataclasses.replace(
        base, activation="relu").stable_hash("tiles")
    assert base.stable_hash() != dataclasses.replace(
        base, activation="relu").stable_hash()
    for other in (dataclasses.replace(base, dtype="int8"),
                  dataclasses.replace(base, batch=8),
                  dataclasses.replace(base, backend="pallas_sparse"),
                  dataclasses.replace(base, out_dtype_bytes=4)):
        assert base.stable_hash("tiles") != other.stable_hash("tiles")


# ---------------------------------------------------------------------------
# satellite: plan round-tripping
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cfg", [MNIST_SMALL, CELEBA_SMALL],
                         ids=lambda c: c.name)
def test_network_plan_roundtrip_fp32(cfg, tmp_cache):
    plan = build_network_plan(cfg, batch=4, backend="pallas")
    back = NetworkPlan.from_json(plan.to_json())
    assert back == plan
    assert back.stable_hash() == plan.stable_hash()
    assert back.tile_overrides() == plan.tile_overrides()


def test_network_plan_roundtrip_int8(tmp_cache):
    params, _ = generator_init(jax.random.PRNGKey(0), MNIST_SMALL)
    plan = build_network_plan(MNIST_SMALL, batch=4, precision="int8",
                              params=params, calib_batch=8)
    back = NetworkPlan.from_json(plan.to_json())
    assert back == plan and back.stable_hash() == plan.stable_hash()
    # the calibrated scales survive exactly (the requant chain is pinned)
    assert back.quant_config() == plan.quant_config()
    assert [l.out_scale for l in back.layers] == \
        [l.out_scale for l in plan.layers]


def test_network_plan_roundtrip_sparse(tmp_cache):
    params, _ = generator_init(jax.random.PRNGKey(0), MNIST_SMALL)
    pruned = _prune(params)
    plan = build_network_plan(MNIST_SMALL, batch=2,
                              backend="pallas_sparse", params=pruned)
    assert plan.sparse_plans() is not None
    back = NetworkPlan.from_json(plan.to_json())
    assert back == plan and back.stable_hash() == plan.stable_hash()
    # the zero-skip tables round-trip bit-exactly
    for i, tabs in plan.sparse_plans().items():
        for a, b in zip(tabs, back.sparse_plans()[i]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_stale_schema_json_rejected(tmp_cache):
    plan = build_network_plan(MNIST_SMALL, batch=2)
    doc = json.loads(plan.to_json())
    doc["schema"] = PLAN_SCHEMA_VERSION + 1
    with pytest.raises(PlanSchemaError, match="schema"):
        NetworkPlan.from_json(json.dumps(doc))
    with pytest.raises(PlanSchemaError, match="kind"):
        NetworkPlan.from_json("{}")
    with pytest.raises(PlanSchemaError):
        NetworkPlan.from_json("not json at all")
    # a tampered document (edited after pinning) is rejected too
    doc = json.loads(plan.to_json())
    doc["layers"][0]["tiles"]["t_oh"] *= 2
    with pytest.raises(PlanSchemaError, match="hash"):
        NetworkPlan.from_json(json.dumps(doc))


def test_plan_for_wrong_network_rejected(tmp_cache):
    plan = build_network_plan(MNIST_SMALL, batch=2)
    with pytest.raises(ValueError, match="layers"):
        plan.validate_for(CELEBA_SMALL)
    params, _ = generator_init(jax.random.PRNGKey(0), CELEBA_SMALL)
    with pytest.raises(ValueError):
        generator_apply(params, CELEBA_SMALL,
                        jnp.zeros((2, CELEBA_SMALL.z_dim)), plan=plan)


# ---------------------------------------------------------------------------
# all four execution paths: plan path vs pre-refactor wrappers,
# bit-identical on the Algorithm-1 S=2/K=5 parity geometries
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("geom", ALGO1_GEOMS, ids=str)
def test_dense_plan_path_bit_identical(geom, tmp_cache, rng):
    from repro.kernels.deconv2d import deconv2d

    x = jnp.asarray(rng.randn(3, geom.in_h, geom.in_w, geom.c_in),
                    jnp.float32)
    w = jnp.asarray(rng.randn(geom.kernel, geom.kernel, geom.c_in,
                              geom.c_out) * 0.1, jnp.float32)
    b = jnp.asarray(rng.randn(geom.c_out), jnp.float32)
    plan = build_layer_plan(geom, batch=3, activation="relu")
    y_plan = np.asarray(deconv2d(x, w, b, plan=plan))
    t = plan.tiles
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        y_leg = np.asarray(deconv2d(x, w, b, geom.stride, geom.padding,
                                    activation="relu", **t.as_kwargs()))
    np.testing.assert_array_equal(y_plan, y_leg)


@pytest.mark.parametrize("geom", ALGO1_GEOMS, ids=str)
def test_sparse_plan_path_bit_identical(geom, tmp_cache, rng):
    from repro.kernels.deconv2d_sparse import (deconv2d_sparse,
                                               make_sparse_plan)

    x = jnp.asarray(rng.randn(2, geom.in_h, geom.in_w, geom.c_in),
                    jnp.float32)
    w = np.asarray(rng.randn(geom.kernel, geom.kernel, geom.c_in,
                             geom.c_out) * 0.1, np.float32)
    w[:, :, :, :: 2] = 0.0  # prune alternating C_out fibers
    w = jnp.asarray(w)
    plan = build_layer_plan(geom, batch=2, backend="pallas_sparse",
                            activation="relu", weights=np.asarray(w))
    assert plan.sparse_tables is not None and plan.sparse_digest
    y_plan = np.asarray(deconv2d_sparse(x, w, None, plan=plan))
    t = plan.tiles
    legacy_tables = make_sparse_plan(np.asarray(w), geom.stride,
                                     geom.padding, t.t_ci, t.t_co)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        y_leg = np.asarray(deconv2d_sparse(
            x, w, None, geom.stride, geom.padding, activation="relu",
            plan=legacy_tables, **t.as_kwargs()))
    np.testing.assert_array_equal(y_plan, y_leg)


def test_int8_plan_path_bit_identical(tmp_cache, rng):
    from repro.quant.infer import quantized_generator_apply
    from repro.quant.calibrate import calibrate, quantize_params

    params, _ = generator_init(jax.random.PRNGKey(0), MNIST_SMALL)
    z = jnp.asarray(rng.randn(4, MNIST_SMALL.z_dim), jnp.float32)
    qcfg = calibrate(params, MNIST_SMALL, z)
    qp = quantize_params(params, MNIST_SMALL, qcfg)
    plan = build_network_plan(MNIST_SMALL, batch=4, precision="int8",
                              quant_cfg=qcfg)
    y_plan = np.asarray(quantized_generator_apply(qp, MNIST_SMALL, None, z,
                                                  plan=plan))
    y_leg = np.asarray(quantized_generator_apply(
        qp, MNIST_SMALL, qcfg, z, tile_overrides=plan.tile_overrides()))
    np.testing.assert_array_equal(y_plan, y_leg)


def test_fused_chain_plan_path_bit_identical(tmp_cache, rng):
    params, _ = generator_init(jax.random.PRNGKey(0), MNIST_SMALL)
    z = jnp.asarray(rng.randn(4, MNIST_SMALL.z_dim), jnp.float32)
    plan = build_network_plan(MNIST_SMALL, batch=4, backend="pallas")
    gen_plan = make_fused_generator(MNIST_SMALL, plan=plan)
    gen_leg = make_fused_generator(MNIST_SMALL,
                                   tiles=plan.tile_overrides())
    np.testing.assert_array_equal(np.asarray(gen_plan(params, z)),
                                  np.asarray(gen_leg(params, z)))
    # and the fused chain stays differentiable through the plan path
    g = jax.grad(lambda p: jnp.sum(gen_plan(p, z)))(params)
    assert np.isfinite(np.asarray(g["l0"]["w"])).all()


# ---------------------------------------------------------------------------
# satellite: deprecation shims route old calls through the plan path
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cfg", [MNIST_SMALL, CELEBA_SMALL],
                         ids=lambda c: c.name)
def test_engine_old_kwargs_equal_new_config(cfg, tmp_cache, rng):
    """Regression: the deprecated kwarg constructor and the EngineConfig
    path serve bit-identical images on both network configs."""
    params, _ = generator_init(jax.random.PRNGKey(0), cfg)
    z = rng.randn(5, cfg.z_dim).astype(np.float32)
    with pytest.warns(DeprecationWarning, match="EngineConfig"):
        warnings.simplefilter("always")
        old = DcnnServeEngine(cfg, params, backend="pallas",
                              buckets=(1, 2, 4))
    new = DcnnServeEngine.from_config(
        EngineConfig(model=cfg, backend="pallas", buckets=(1, 2, 4)),
        params)
    np.testing.assert_array_equal(old.generate(z), new.generate(z))
    assert old.trace_counts == new.trace_counts


def test_tile_kwargs_deprecation_warning(tmp_cache, rng):
    from repro.kernels.deconv2d import ops
    from repro.kernels.deconv2d import deconv2d

    x = jnp.asarray(rng.randn(1, 4, 4, 8), jnp.float32)
    w = jnp.asarray(rng.randn(4, 4, 8, 8) * 0.1, jnp.float32)
    ops._warned_tile_kwargs.discard("deconv2d")
    with pytest.warns(DeprecationWarning, match="DeconvPlan"):
        warnings.simplefilter("always")
        deconv2d(x, w, None, 2, 1, t_oh=2, t_ow=2)
    # plain geometry-only calls (auto-resolved tiles) stay warning-free
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        deconv2d(x, w, None, 2, 1)


def test_plan_geometry_mismatch_rejected(tmp_cache, rng):
    from repro.kernels.deconv2d import deconv2d

    plan = build_layer_plan(ALGO1_GEOMS[0], batch=2)
    x = jnp.zeros((2, 9, 9, ALGO1_GEOMS[0].c_in), jnp.float32)
    w = jnp.zeros((5, 5, ALGO1_GEOMS[0].c_in, ALGO1_GEOMS[0].c_out),
                  jnp.float32)
    with pytest.raises(ValueError, match="geometry"):
        deconv2d(x, w, None, plan=plan)


# ---------------------------------------------------------------------------
# EngineConfig-driven serving: both generators x both precisions through
# the bucket machinery with unchanged per-bucket compile counts
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cfg,precision", [
    (MNIST_SMALL, "fp32"), (MNIST_SMALL, "int8"),
    (CELEBA_SMALL, "fp32"), (CELEBA_SMALL, "int8"),
], ids=lambda v: getattr(v, "name", v))
def test_from_config_serves_both_precisions(cfg, precision, tmp_cache, rng):
    params, _ = generator_init(jax.random.PRNGKey(0), cfg)
    eng = DcnnServeEngine.from_config(
        EngineConfig(model=cfg, precision=precision, buckets=(1, 2, 4),
                     calib_batch=8),
        params)
    for n in (3, 4, 1):
        imgs = eng.generate(rng.randn(n, cfg.z_dim).astype(np.float32))
        assert imgs.shape == (n, cfg.img_hw, cfg.img_hw, cfg.img_c)
        assert np.isfinite(imgs).all()
    # compile-once per touched bucket, plan-once per touched bucket
    assert all(v == 1 for v in eng.trace_counts.values())
    assert eng.plan_stats["builds"] == len(eng.trace_counts)
    for b in eng.trace_counts:
        assert eng.plans[b].precision == precision
        assert eng.plans[b].batch == eng.shard_batch(b)


def test_from_config_pinned_plan_no_rebuild(tmp_cache, rng):
    """A deserialized plan is served verbatim: no plan build, no
    recalibration, same images as the self-planning engine."""
    params, _ = generator_init(jax.random.PRNGKey(0), MNIST_SMALL)
    plan = build_network_plan(MNIST_SMALL, batch=4, precision="int8",
                              params=params, calib_batch=8)
    pinned = NetworkPlan.from_json(plan.to_json())
    cfgE = EngineConfig(model=MNIST_SMALL, precision="int8", buckets=(4,),
                        calib_batch=8)
    eng = DcnnServeEngine.from_config(cfgE, params, plan=pinned)
    auto = DcnnServeEngine.from_config(cfgE, params)
    z = rng.randn(4, MNIST_SMALL.z_dim).astype(np.float32)
    np.testing.assert_array_equal(eng.generate(z), auto.generate(z))
    assert eng.plan_stats["builds"] == 0
    assert auto.plan_stats["builds"] == 1
    # pinned calibration == self-calibration (same seed/batch/strategy)
    assert eng.quant_cfg == auto.quant_cfg


def test_from_config_plan_mismatch_rejected(tmp_cache):
    params, _ = generator_init(jax.random.PRNGKey(0), MNIST_SMALL)
    plan = build_network_plan(MNIST_SMALL, batch=4, backend="pallas")
    with pytest.raises(ValueError, match="precision"):
        DcnnServeEngine.from_config(
            EngineConfig(model=MNIST_SMALL, precision="int8",
                         buckets=(4,)), params, plan=plan)
    with pytest.raises(ValueError, match="bucket"):
        DcnnServeEngine.from_config(
            EngineConfig(model=MNIST_SMALL, buckets=(8, 16)), params,
            plan=plan)


def test_sparse_engine_via_config_shares_tables(tmp_cache, rng):
    """pallas_sparse through from_config: zero-skip schedules come from
    the per-bucket plans, memoized across buckets sharing channel tiles
    (the table cache never rebuilds per bucket needlessly)."""
    params, _ = generator_init(jax.random.PRNGKey(0), MNIST_SMALL)
    pruned = _prune(params)
    eng = DcnnServeEngine.from_config(
        EngineConfig(model=MNIST_SMALL, backend="pallas_sparse",
                     buckets=(1, 2)), pruned)
    z = rng.randn(3, MNIST_SMALL.z_dim).astype(np.float32)
    imgs = eng.generate(z)
    ref = np.asarray(generator_apply(pruned, MNIST_SMALL, jnp.asarray(z),
                                     backend="reverse_loop"))
    np.testing.assert_allclose(imgs, ref, rtol=1e-4, atol=1e-4)
    n_layers = len(MNIST_SMALL.layers)
    # both buckets planned; the memo holds at most one entry per distinct
    # (layer, t_ci, t_co) — not one per (bucket, layer)
    assert eng.plan_stats["builds"] == 2
    assert len(eng._sparse_plan_memo) <= 2 * n_layers
    shared = [k for k in eng._sparse_plan_memo]
    assert len(set(shared)) == len(shared)


def test_stale_sparse_plan_rejected_at_engine_load(tmp_cache, rng):
    """Review regression: a pinned pallas_sparse plan whose zero-skip
    schedule no longer matches the served weights (checkpoint re-pruned
    after pinning) must fail loudly at engine construction, not silently
    skip now-nonzero blocks."""
    def tap_prune(params, taps):
        """Zero whole kernel taps of layer 1 (block-level sparsity the
        schedule actually encodes)."""
        out = {k: dict(v) for k, v in params.items()}
        w = np.asarray(out["l1"]["w"]).copy()
        w[list(taps)] = 0.0
        out["l1"]["w"] = jnp.asarray(w)
        return out

    params, _ = generator_init(jax.random.PRNGKey(0), MNIST_SMALL)
    pruned_a = tap_prune(params, (0, 1))
    pruned_b = tap_prune(params, (2, 3))   # different sparsity pattern
    plan = build_network_plan(MNIST_SMALL, batch=2,
                              backend="pallas_sparse", params=pruned_a)
    cfgE = EngineConfig(model=MNIST_SMALL, backend="pallas_sparse",
                        buckets=(2,))
    # matching weights load fine...
    DcnnServeEngine.from_config(cfgE, pruned_a, plan=plan)
    # ...re-pruned weights are rejected
    with pytest.raises(ValueError, match="stale"):
        DcnnServeEngine.from_config(cfgE, pruned_b, plan=plan)


def test_conflicting_calibrations_rejected(tmp_cache):
    """Review regression: quant_cfg in the EngineConfig AND a pinned int8
    plan with a different calibration would quantize params with one
    scale set and requant with another — rejected up front."""
    from repro.quant.calibrate import calibrate

    params, _ = generator_init(jax.random.PRNGKey(0), MNIST_SMALL)
    plan = build_network_plan(MNIST_SMALL, batch=4, precision="int8",
                              params=params, calib_batch=8)
    other = calibrate(params, MNIST_SMALL,
                      jax.random.normal(jax.random.PRNGKey(9),
                                        (8, MNIST_SMALL.z_dim)),
                      strategy="minmax")
    with pytest.raises(ValueError, match="calibrations"):
        DcnnServeEngine.from_config(
            EngineConfig(model=MNIST_SMALL, precision="int8",
                         quant_cfg=other, buckets=(4,)),
            params, plan=plan)
    # the same calibration object is accepted
    eng = DcnnServeEngine.from_config(
        EngineConfig(model=MNIST_SMALL, precision="int8",
                     quant_cfg=plan.quant_config(), buckets=(4,)),
        params, plan=plan)
    assert eng.quant_cfg == plan.quant_config()


def test_sparse_network_plan_requires_params(tmp_cache):
    """Review regression: a weightless sparse plan would re-derive the
    zero-skip schedule per call (and crash under jit) — refused."""
    with pytest.raises(ValueError, match="pruned weights"):
        build_network_plan(MNIST_SMALL, batch=2, backend="pallas_sparse")


def test_tile_overrides_surface_does_not_warn(tmp_cache, rng):
    """Review regression: the supported legacy override surface
    (generator_apply(tile_overrides=...), the WganTrainer path) expands
    tile kwargs internally and must not nag the user."""
    from repro.kernels.autotune import choose_tiles

    params, _ = generator_init(jax.random.PRNGKey(0), MNIST_SMALL)
    z = jnp.asarray(rng.randn(2, MNIST_SMALL.z_dim), jnp.float32)
    tiles = {i: choose_tiles(g, jnp.float32, backend="pallas", batch=2)
             for i, g in enumerate(MNIST_SMALL.geometries())}
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        generator_apply(params, MNIST_SMALL, z, backend="pallas",
                        tile_overrides=tiles)
        make_fused_generator(MNIST_SMALL, tiles=tiles)(params, z)


def test_plan_roofline_estimates(tmp_cache):
    """NetworkPlan owns the traffic/roofline numbers the benches report:
    int8 plans model faster-than-fp32 network throughput at batch 64, at
    the paper's MNIST widths (the int8 kernel streams whole 128-lane
    channel tiles, so a few-channel toy tower would price lane padding)."""
    p32 = build_network_plan(MNIST_DCNN, batch=64, backend="pallas")
    params, _ = generator_init(jax.random.PRNGKey(0), MNIST_DCNN)
    p8 = build_network_plan(MNIST_DCNN, batch=64, precision="int8",
                            params=params, calib_batch=8)
    t32 = p32.traffic_report()
    t8 = p8.traffic_report()
    assert set(t32) == set(t8) == set(range(len(MNIST_DCNN.layers)))
    # int8 streams fewer bytes on every intermediate layer
    for i in range(len(MNIST_DCNN.layers) - 1):
        assert t8[i].total_bytes < t32[i].total_bytes
    a32 = p32.modeled_network_ops()
    a8 = p8.modeled_network_ops()
    assert a8 > a32 > 0
