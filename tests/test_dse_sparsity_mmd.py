"""DSE (Fig. 5), pruning, MMD, and the Eq. 6 metric."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dse import PYNQ_Z2, TPU_V5E, layer_dse, optimize_unified_tile, per_layer_optimum
from repro.core.metric import optimal_sparsity, quality_speed_metric
from repro.core.mmd import median_bandwidth, mmd, mmd2
from repro.core.sparsity import magnitude_prune, prune_tree
from repro.core.tiling import DeconvGeometry
from repro.models.dcnn import CELEBA_DCNN, MNIST_DCNN


def test_dse_legality_and_bandwidth_flag():
    g = MNIST_DCNN.geometries()[1]
    pts = layer_dse(g, TPU_V5E)
    assert pts
    for p in pts:
        assert p.t_oh % g.stride == 0
        assert p.attainable_ops <= TPU_V5E.peak_ops
        if p.bandwidth_bound:
            assert p.attainable_ops == pytest.approx(p.ctc * TPU_V5E.bandwidth)


def test_unified_tile_is_common_and_optimal():
    geoms = MNIST_DCNN.geometries()
    best, scores = optimize_unified_tile(geoms, TPU_V5E)
    assert best in scores
    assert scores[best] == max(scores.values())
    # per-layer reconfiguration (paper's future work) can only help
    per_layer = per_layer_optimum(geoms, TPU_V5E)
    total_ops = sum(g.ops for g in geoms)
    t_unified = sum(g.ops / scores[best] for g in geoms)  # = total/throughput
    t_per_layer = sum(g.ops / p.attainable_ops
                      for g, p in zip(geoms, per_layer))
    assert t_per_layer <= t_unified * (1 + 1e-9)


@pytest.mark.parametrize("platform,kind,want", [
    ("cpu", "cpu", TPU_V5E),            # off the chip: the v5e model
    ("tpu", "TPU v5 lite", TPU_V5E),    # what a v5e reports
    ("tpu", "TPU v5e", TPU_V5E),
    ("tpu", "TPU v9", None),            # unknown TPU: refuse, never v5e
])
def test_planning_device_by_device_kind(monkeypatch, platform, kind, want):
    from types import SimpleNamespace

    from repro.core.dse import planning_device

    monkeypatch.setattr(jax, "devices", lambda: [
        SimpleNamespace(platform=platform, device_kind=kind)])
    if want is None:
        with pytest.raises(ValueError, match="no planning constants"):
            planning_device()
    else:
        assert planning_device() is want


def test_dse_on_pynq_reproduces_fig5_regime():
    """On the paper's PYNQ-Z2 point design, small tiles are bandwidth-bound
    (left of the slope) and attainable throughput is monotone until the roof."""
    g = CELEBA_DCNN.geometries()[2]
    pts = layer_dse(g, PYNQ_Z2, co_tile=32)
    assert pts[0].bandwidth_bound
    atts = [p.attainable_ops for p in pts]
    assert max(atts) <= PYNQ_Z2.peak_ops


@pytest.mark.parametrize(
    "s", [0.1, 0.2, 0.33, 0.42, 0.5, 0.61, 0.7, 0.8, 0.85, 0.9])
def test_prune_fraction(s):
    rng = np.random.RandomState(0)
    w = jnp.array(rng.randn(16, 64), jnp.float32)
    wp, mask = magnitude_prune(w, s)
    frac = 1.0 - np.asarray(mask).mean()
    assert abs(frac - s) < 0.05
    # surviving weights are exactly the original large-magnitude ones
    assert np.all(np.asarray(wp)[~np.asarray(mask)] == 0)


def test_prune_tree_skips_biases(rng):
    params = {"w": jnp.array(rng.randn(8, 8), jnp.float32),
              "b": jnp.array(rng.randn(8), jnp.float32)}
    pruned = prune_tree(params, 0.9)
    assert (np.asarray(pruned["w"]) == 0).mean() > 0.8
    assert (np.asarray(pruned["b"]) == 0).mean() == 0.0


def test_mmd_zero_iff_identical(rng):
    x = jnp.array(rng.randn(64, 10), jnp.float32)
    assert float(mmd2(x, x, unbiased=False)) == pytest.approx(0.0, abs=1e-5)
    y = jnp.array(rng.randn(64, 10) + 3.0, jnp.float32)
    assert float(mmd(x, y)) > 0.3


def test_mmd_monotone_in_shift(rng):
    x = jnp.array(rng.randn(96, 8), jnp.float32)
    ds = [float(mmd(x, x + d)) for d in (0.0, 0.5, 1.0, 2.0)]
    assert ds == sorted(ds)


def test_median_bandwidth_positive(rng):
    x = jnp.array(rng.randn(32, 4), jnp.float32)
    assert float(median_bandwidth(x)) > 0


def test_eq6_metric_concave_peak():
    """Speedup grows with sparsity, quality degrades -> interior peak."""
    sparsities = np.linspace(0, 0.9, 10)
    tp = 1.0 / (1.0 + 2.0 * sparsities)          # latency falls (zero-skip)
    dp = 0.1 * (1.0 + np.exp(6 * (sparsities - 0.55)))  # MMD blows up late
    best, curve = optimal_sparsity(sparsities, tp[0], dp[0], tp, dp)
    assert 0.1 < best < 0.9
    peak = int(np.argmax(curve))
    assert 0 < peak < len(curve) - 1             # interior (concave shape)
