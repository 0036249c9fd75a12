"""The dense deconv kernel's tap fold: thin output-channel tiles put a group
of taps side by side in one lane-wide dot, then add each tap's lane block
at its offset.  Parity in interpret mode against the per-tap form and the
zero-insertion reference, the rule that picks the form, the tiles the
autotuner keeps for the benchmark's configurations, and the engine's
count of folded layers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels.deconv2d.kernel as kernel_mod
from repro.core.tiling import LANE
from repro.kernels.deconv2d.kernel import (apply_activation, kernel_name,
                                           tap_fold_group)
from repro.kernels.deconv2d.ops import _deconv2d_jit
from repro.kernels.deconv2d.ref import deconv2d_ref
from repro.models.dcnn import (CELEBA_DCNN, MNIST_DCNN, PIX2PIX_UNET256,
                               generator_init)
from repro.obs import trace
from repro.plan import build_network_plan
from repro.serve import DcnnServeEngine, EngineConfig

ACTS = (None, "relu", "tanh", "leaky_relu")
# (K, S, P, input extent, output tile): each output dim spans two tiles
GEOMS = ((4, 2, 1, 8, 8), (4, 1, 0, 6, 6), (7, 1, 0, 4, 6))
# (c_out, t_co): 128 is the unfolded control; 64 split in two 32-lane
# tiles folds across output-channel tiles
CHANNELS = ((1, 8), (3, 8), (8, 8), (64, 64), (128, 128), (64, 32))
CASES = [(g, c, t_n) for g in GEOMS for c in CHANNELS for t_n in (1, 4)]


@pytest.fixture
def tmp_cache(tmp_path, monkeypatch):
    from repro.kernels import autotune

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    monkeypatch.setattr(autotune, "_cache", None)
    yield
    monkeypatch.setattr(autotune, "_cache", None)


@pytest.mark.parametrize("case", range(len(CASES)), ids=lambda i: (
    "k{0[0]}s{0[1]}p{0[2]}-co{1[0]}t{1[1]}-n{2}".format(*CASES[i])))
def test_tapfold_matches_per_tap_and_reference(case, monkeypatch):
    """Both forms at the same tiles (two W tiles, two CI tiles, batch
    tile 1 or 4) against each other and the zero-insertion reference,
    every fused activation in turn."""
    (k, s, p, ih, t), (c_out, t_co), t_n = CASES[case]
    act = ACTS[case % len(ACTS)]
    ci, t_ci, n = 16, 8, 4
    rng = np.random.RandomState(case)
    x = jnp.asarray(rng.randn(n, ih, ih, ci).astype(np.float32))
    w = jnp.asarray(rng.randn(k, k, ci, c_out).astype(np.float32))
    b = jnp.asarray(rng.randn(c_out).astype(np.float32))
    args = (x, w, b, s, p, t, t, t_ci, t_co, t_n, act, True)
    with jax.default_matmul_precision("highest"):
        folded = np.asarray(_deconv2d_jit(*args))
        pre = deconv2d_ref(x, w, b, s, p)
        ref = np.asarray(apply_activation(pre, act))
        monkeypatch.setattr(kernel_mod, "tap_fold_group", lambda k, t_co: 1)
        per_tap = np.asarray(_deconv2d_jit.__wrapped__(*args))
    # the forms differ only in the order of the f32 sums, each activation
    # moves a value no further than its argument: ~100 float32 ulps of the
    # pre-activation scale over up to 784 addends
    tol = 1e-5 * float(jnp.max(jnp.abs(pre)))
    np.testing.assert_allclose(folded, per_tap, rtol=0, atol=tol)
    np.testing.assert_allclose(folded, ref, rtol=0, atol=tol)


def _kernel_names(t_co):
    x = jax.ShapeDtypeStruct((1, 8, 8, 16), jnp.float32)
    w = jax.ShapeDtypeStruct((4, 4, 16, t_co), jnp.float32)
    b = jax.ShapeDtypeStruct((t_co,), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda x, w, b: _deconv2d_jit(
        x, w, b, 2, 1, 8, 8, 16, t_co, 1, None, True, 15))(x, w, b)
    return {e.params["name"] for e in jaxpr.jaxpr.eqns[0].params["jaxpr"].eqns
            if e.primitive.name == "pallas_call"}


def test_fold_group_and_kernel_name_follow_t_co():
    """The fold is picked by the tile alone: as many taps as fit a lane,
    at most all of them; a lane-wide tile (or a 1x1 kernel) keeps the
    per-tap form and its name."""
    assert [tap_fold_group(4, c) for c in (1, 3, 8, 16, 32, 64, 96, 128)] \
        == [16, 16, 16, 8, 4, 2, 1, 1]
    assert tap_fold_group(7, 8) == 16 and tap_fold_group(7, 64) == 2
    assert tap_fold_group(1, 8) == 1
    assert all(tap_fold_group(4, c) * c <= LANE for c in (1, 3, 8, 64, 128))
    assert _kernel_names(8) == {kernel_name("halo_tapfold", 15)} \
        == {"deconv2d_l15_halo_tapfold"}
    assert _kernel_names(128) == {"deconv2d_l15_halo_reverse_loop"}


# The tiles the autotuner picks for the benchmark's configurations at their
# buckets (t_oh, t_ow, t_ci, t_co, t_n), one per deconvolution layer.
PINNED = {
    (PIX2PIX_UNET256.name, 16): [
        (2, 2, 512, 128, 16), (4, 4, 512, 128, 16), (8, 8, 512, 128, 16),
        (16, 16, 128, 128, 16), (32, 32, 256, 128, 4), (64, 64, 256, 128, 1),
        (64, 64, 256, 64, 1), (64, 64, 128, 8, 1)],
    (CELEBA_DCNN.name, 64): [
        (4, 4, 104, 128, 64), (8, 8, 128, 128, 64), (16, 16, 128, 128, 16),
        (32, 32, 256, 128, 4), (64, 64, 128, 8, 1)],
    (MNIST_DCNN.name, 16): [
        (7, 7, 104, 128, 16), (14, 14, 256, 128, 16), (28, 28, 128, 8, 4)],
}
CONFIGS = {c.name: c for c in (PIX2PIX_UNET256, CELEBA_DCNN, MNIST_DCNN)}
FOLDED = {PIX2PIX_UNET256.name: 2, CELEBA_DCNN.name: 1, MNIST_DCNN.name: 1}


@pytest.mark.parametrize("name,batch", sorted(PINNED))
def test_benchmark_plans_keep_their_tiles(name, batch, tmp_cache):
    """Counting the fold's temporaries in the VMEM model moves no tile of
    the benchmark's plans, and the plans fold their thin layers alone."""
    plan = build_network_plan(CONFIGS[name], batch=batch, backend="pallas")
    tiles = plan.tile_overrides()
    assert [(t.t_oh, t.t_ow, t.t_ci, t.t_co, t.t_n)
            for _, t in sorted(tiles.items())] == PINNED[name, batch]
    assert plan.tap_folded_layers() == FOLDED[name]
    assert build_network_plan(CONFIGS[name], batch=batch,
                              backend="xla").tap_folded_layers() == 0


def test_engine_counts_tap_folded_layers_per_plan_build(tmp_cache):
    """Each bucket's plan build adds its folded layers to
    ``engine.tap_folded_layers`` and names them on its span."""
    params, _ = generator_init(jax.random.PRNGKey(0), MNIST_DCNN)
    eng = DcnnServeEngine.from_config(
        EngineConfig(model=MNIST_DCNN, backend="pallas", buckets=(1, 4)),
        params)
    trace.enable(clear=True)
    try:
        for bucket in eng.buckets:
            eng._plan_for(bucket)
    finally:
        trace.disable()
    counter = eng.metrics.counter("engine.tap_folded_layers")
    assert [counter.total(bucket=b) for b in (1, 4)] == [1, 1]
    spans = {e["name"]: e["args"] for e in trace.get_tracer().events()
             if e["name"].startswith("plan_build")}
    assert {n: a["tap_folded"] for n, a in spans.items()} == {
        "plan_build b1": 1, "plan_build b4": 1}
