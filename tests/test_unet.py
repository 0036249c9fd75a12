"""The pix2pix U-Net on the plan -> engine -> frontend path, on the CPU.

A tiny U-Net (32x32x3 in and out, 5 downs, ngf 8, down to a 1x1
innermost layer) with seeded random weights and BatchNorm statistics
drawn away from the identity is served through `AsyncServeFrontend` over
`DcnnServeEngine.from_config` (Pallas kernels in interpret mode) and
compared with the benchmark's plain reference, `bench/references/
unet.py`, which applies every BatchNorm as written and writes each
convolution as its definition.  The U-Net's products are float32
(``dot_precision="highest"``), as are the CPU's, so the reference is
told its operands are float32 too.

The tolerances: program and reference compute the same float32 sums in
different orders (XLA's convolution against one einsum per tap, the
kernel's phase decomposition against the definition), and the program
computes the BatchNorm's scale before applying it; the gap is a few
float32 ulps of the largest value.  ``REL_TOL`` = 2e-5 of the
largest reference value leaves ~10x room over that and is ~100x below
what one bfloat16 rounding of the dot operands gives (~1e-3 to 1e-2).
"""
import dataclasses
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.check.plan_drc import check_network_plan
from repro.kernels.deconv2d import deconv2d
from repro.models.dcnn import (CELEBA_DCNN, MNIST_DCNN, PIX2PIX_UNET256,
                               conv2d, generator_apply, normalize,
                               unet_config)
from repro.plan import (NetworkPlan, build_network_plan,
                        executable_fingerprints)
from repro.serve import (AsyncServeFrontend, DcnnServeEngine, EngineConfig,
                         PrecisionUnsupported, TenantClass)

ROOT = pathlib.Path(__file__).resolve().parents[1]
TINY = unet_config("unet-tiny", img_hw=32, ngf=8, num_downs=5)
REL_TOL = 2e-5
SEED = 2**33 + 5


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "unet_reference", ROOT / "bench" / "references" / "unet.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = load_reference()


def ref_cfg(cfg):
    """The benchmark configuration file's form of ``cfg``: biases on the
    first and the last layer, as in ``pix2pix-unet256.json``."""
    last = len(cfg.layers) - 1
    return {"dtype": "float32", "dot_operands": "float32",
            "in_hw": cfg.in_hw, "in_c": cfg.in_c,
            "layers": [dict(kind=l.kind, c_in=l.c_in, c_out=l.c_out,
                            kernel=l.kernel, stride=l.stride,
                            padding=l.padding, norm=l.norm, skip=l.skip,
                            in_activation=l.in_activation,
                            activation=l.activation, bias=i in (0, last))
                       for i, l in enumerate(cfg.layers)]}


@pytest.fixture(autouse=True)
def tmp_cache(tmp_path, monkeypatch):
    from repro.kernels import autotune

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    monkeypatch.setattr(autotune, "_cache", None)


@pytest.fixture(scope="module")
def params():
    return REF.init(ref_cfg(TINY), SEED)


def images(n, seed=0):
    return np.random.RandomState(seed).randn(
        n, *TINY.input_shape).astype(np.float32)


def assert_close(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    gap = np.abs(got - want).max() / np.abs(want).max()
    assert gap <= REL_TOL, gap


def test_reference_draws_batchnorm_away_from_identity(params):
    """The BatchNorm is only checked if its statistics are not the
    identity."""
    bn = [params[f"l{i}"]["bn"] for i, l in enumerate(TINY.layers)
          if l.norm == "batch"]
    assert len(bn) == 7   # 3 encoder + 4 decoder layers of 5 downs
    for s in bn:
        assert np.abs(np.asarray(s["mean"])).min() > 0
        assert np.abs(np.asarray(s["var"]) - 1).max() > 0.1
        assert np.abs(np.asarray(s["gamma"]) - 1).max() > 0.1


@pytest.mark.parametrize("bucket", [1, 4])
def test_engine_through_frontend_matches_reference(params, bucket):
    eng = DcnnServeEngine.from_config(
        EngineConfig(model=TINY, backend="pallas", max_batch=bucket), params)
    fe = AsyncServeFrontend({"fp32": eng},
                            [TenantClass("t", slo_ms=None,
                                         allow_degrade=False)])
    try:
        x = images(bucket, seed=bucket)
        y = fe.result(fe.submit(x, "t"), timeout_s=300)
    finally:
        fe.close()
    assert_close(y, REF.forward(ref_cfg(TINY), params, x))
    plan = eng.plans[bucket]
    assert [l.kind for l in plan.layers] == ["conv"] * 5 + ["deconv"] * 5
    assert all(l.tiles is None for l in plan.layers[:5])
    assert all(l.tiles is not None for l in plan.layers[5:])
    # one increment of each transfer counter per bucket call
    rows = bucket * int(np.prod(TINY.input_shape)) * 4
    assert eng.metrics.counter("engine.upload_bytes").total() == \
        rows * eng.stats["generate_calls"]
    assert eng.metrics.counter("engine.copy_back_bytes").total() == \
        rows * eng.stats["generate_calls"]


def test_plan_matches_reference_layer_by_layer(params):
    """Each planned layer (decoder layers through the Pallas kernel, the
    epilogue stopping at the bias where a BatchNorm follows), then the
    step's `normalize`, against the reference's layer with its BatchNorm
    written out, on the same input."""
    plan = build_network_plan(TINY, batch=2, backend="pallas")
    rng = np.random.RandomState(1)
    for i, (l, g, lp) in enumerate(zip(TINY.layers, TINY.geometries(),
                                       plan.layers)):
        x = np.abs(rng.randn(2, g.in_h, g.in_w, g.c_in)).astype(np.float32)
        q = params[f"l{i}"]
        zero = np.zeros_like(q["b"])
        if l.kind == "conv":
            got = conv2d(x, q["w"], zero, l.stride, l.padding)
            want = REF.conv(x, q["w"], l.stride, l.padding, jnp.float32,
                            jnp.float32)
        else:
            assert lp.activation == (None if l.norm else l.activation)
            got = deconv2d(x, q["w"], zero, plan=lp, activation="none")
            want = REF.deconv(x, q["w"], l.stride, l.padding, jnp.float32,
                              jnp.float32)
        got = normalize(got, q) if l.norm else got + q["b"]
        want = want + q["b"]
        if l.norm == "batch":
            bn = q["bn"]
            want = ((want - bn["mean"]) / jnp.sqrt(bn["var"] + REF.BN_EPS)
                    * bn["gamma"] + bn["beta"])
        assert_close(got, want)


def _dot_precisions(jaxpr):
    """The precision of every convolution and dot in ``jaxpr``, the Pallas
    kernels' bodies included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("conv_general_dilated", "dot_general"):
            out.append((eqn.primitive.name, eqn.params["precision"]))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out.extend(_dot_precisions(inner))
    return out


@pytest.mark.parametrize("cfg,want", [
    (TINY, (jax.lax.Precision.HIGHEST,) * 2),
    (dataclasses.replace(TINY, dot_precision=None), None)],
    ids=["highest", "default"])
def test_unet_products_at_its_dot_precision(params, cfg, want):
    """Every convolution of the planned step, and every dot inside the
    decoder's Pallas kernels, takes ``cfg.dot_precision``; the plan pins
    it, so a plan for the other precision is refused."""
    plan = build_network_plan(cfg, batch=1, backend="pallas")
    jaxpr = jax.make_jaxpr(lambda p, x: generator_apply(p, cfg, x,
                                                        plan=plan))(
        params, images(1))
    got = _dot_precisions(jaxpr.jaxpr)
    kinds = {name for name, _ in got}
    assert kinds == {"conv_general_dilated", "dot_general"}
    assert {prec for _, prec in got} == {want}
    other = dataclasses.replace(
        cfg, dot_precision=None if cfg.dot_precision else "highest")
    with pytest.raises(ValueError, match="dot_precision"):
        plan.validate_for(other)


def test_leaky_relu_fused_in_kernel():
    rng = np.random.RandomState(2)
    x = rng.randn(2, 8, 8, 16).astype(np.float32)
    w = rng.randn(4, 4, 16, 8).astype(np.float32) * 0.1
    b = rng.randn(8).astype(np.float32)
    fused = deconv2d(x, w, b, 2, 1, activation="leaky_relu")
    plain = deconv2d(x, w, b, 2, 1, activation=None)
    # the epilogue applies the same select to the same float32 values
    np.testing.assert_array_equal(np.asarray(fused),
                                  np.asarray(jax.nn.leaky_relu(plain, 0.2)))
    assert (np.asarray(plain) < 0).any()


def test_drc_passes_the_unet_plan_and_refuses_a_mismatched_skip():
    plan = build_network_plan(TINY, batch=1, backend="pallas")
    assert check_network_plan(plan).ok()
    # layer 6 joins layer 3 (2x2, the previous output's size); pointing it
    # at layer 2 (4x4) breaks the join
    assert plan.layers[6].skip == 3
    bad = dataclasses.replace(plan, layers=tuple(
        dataclasses.replace(l, skip=2) if i == 6 else l
        for i, l in enumerate(plan.layers)))
    report = check_network_plan(bad)
    assert not report.ok()
    hits = [v for v in report.violations if v.rule_id == "drc.geometry_chain"]
    assert hits and "one spatial size" in hits[0].message
    with pytest.raises(ValueError, match="topology"):
        bad.validate_for(TINY)


def test_unet_plan_json_roundtrip():
    plan = build_network_plan(TINY, batch=1, backend="pallas")
    back = NetworkPlan.from_json(plan.to_json())
    assert back == plan and back.stable_hash() == plan.stable_hash()
    back.validate_for(TINY)


def test_engine_refuses_int8_for_the_graph_config(params):
    with pytest.raises(PrecisionUnsupported, match="fp32"):
        DcnnServeEngine.from_config(
            EngineConfig(model=TINY, backend="pallas", precision="int8"),
            params)
    with pytest.raises(ValueError, match="fp32"):
        build_network_plan(TINY, precision="int8", params=params)


def test_registered_workload_at_published_widths():
    from repro import workloads

    cfg = workloads.resolve_model("pix2pix-unet256")
    assert cfg is PIX2PIX_UNET256 and cfg.input_shape == (256, 256, 3)
    assert cfg.dot_precision == "highest"
    assert [l.c_out for l in cfg.layers] == [
        64, 128, 256, 512, 512, 512, 512, 512,
        512, 512, 512, 512, 256, 128, 64, 3]
    assert sum(g.ops for g in cfg.geometries()) == pytest.approx(12.10e9,
                                                                 rel=1e-3)


# The plan hashes of the two chain generators at buckets 1 and 64, as
# the parent of the U-Net change computed them (autotune model, empty
# cache).  Equal hashes mean the accepted cells run the same programs.
CHAIN_FINGERPRINTS = {
    "dcnn-mnist": {1: "ec0ed3d2d4b27fae0e39de5c",
                   64: "9375829e6293a9a0ccf34ba3"},
    "dcnn-celeba": {1: "68f7b7e12505bbd8812df616",
                    64: "0e78f76b0749a1cceea630d1"},
}


@pytest.mark.parametrize("cfg", [MNIST_DCNN, CELEBA_DCNN],
                         ids=lambda c: c.name)
def test_chain_plan_hashes_unchanged(cfg):
    plans = [build_network_plan(cfg, batch=b, backend="pallas")
             for b in (1, 64)]
    assert not cfg.is_graph and cfg.dot_precision is None
    assert executable_fingerprints(plans) == CHAIN_FINGERPRINTS[cfg.name]
