"""The plain reference against the program's reverse-loop generator, on
the CPU, at a small batch and the configurations' own widths."""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.references import deconv_tower

ROOT = pathlib.Path(__file__).resolve().parents[2]


def program_config(cfg):
    from repro.models.dcnn import DcnnConfig, DeconvLayerCfg

    return DcnnConfig(name=cfg["name"], z_dim=cfg["z_dim"],
                      img_hw=cfg["img_hw"], img_c=cfg["img_c"],
                      layers=tuple(DeconvLayerCfg(**l)
                                   for l in cfg["layers"]))


def load(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("name", ["celeba", "mnist"])
def test_reference_matches_reverse_loop(name):
    """With float32 dot operands the reference is the program's
    reverse-loop generator to float32 rounding."""
    from repro.models.dcnn import generator_apply

    cfg = load(name)
    params = deconv_tower.init(cfg, 2**40 + 1)
    z = np.random.default_rng(0).standard_normal((3, cfg["z_dim"]),
                                                 dtype=np.float32)
    ref = np.asarray(deconv_tower.forward(cfg, params, z,
                                          operands="float32"))
    assert ref.shape == (3, cfg["img_hw"], cfg["img_hw"], cfg["img_c"])
    with jax.default_matmul_precision("highest"):
        got = np.asarray(generator_apply(params, program_config(cfg),
                                         jnp.asarray(z),
                                         backend="reverse_loop"))
    np.testing.assert_allclose(got, ref, rtol=0, atol=1e-5)
    # the weights make a tanh output that spans its range, not one that
    # sits at zero or saturates
    assert 0.3 < ref.std() < 0.8
    assert np.mean(np.abs(ref) > 0.99) < 0.05


def rel_gaps(a, b):
    d = np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))
    return d.max() / np.abs(b).max(), np.sqrt((d ** 2).mean()
                                              / (np.asarray(b) ** 2).mean())


@pytest.mark.parametrize("name", ["celeba", "mnist"])
def test_stated_arithmetic_rounds_the_dot_operands(name):
    """The stated arithmetic (bfloat16 dot operands, float32 storage) lies
    within bfloat16 rounding of float32 operands, and the bfloat16
    control lies farther from it than the float32 operands do."""
    cfg = load(name)
    assert cfg["dtype"] == "float32" and cfg["dot_operands"] == "bfloat16"
    params = deconv_tower.init(cfg, 2**40 + 3)
    z = np.random.default_rng(1).standard_normal((2, cfg["z_dim"]),
                                                 dtype=np.float32)
    stated = deconv_tower.forward(cfg, params, z)
    assert stated.dtype == jnp.float32
    full = deconv_tower.forward(cfg, params, z, operands="float32")
    low = deconv_tower.forward(cfg, params, z, storage="bfloat16",
                               operands="bfloat16")
    assert low.dtype == jnp.bfloat16
    full_max, full_rms = rel_gaps(full, stated)
    low_max, low_rms = rel_gaps(low.astype(jnp.float32), stated)
    assert 1e-4 < full_max < 0.05 and 1e-5 < full_rms < 0.01
    assert low_rms > 0.3 * full_rms and low_max > 1e-3


def test_weights_follow_the_seed():
    cfg = json.loads((ROOT / "bench" / "configs" / "mnist.json").read_text())
    a, b = (deconv_tower.init(cfg, s) for s in (7, 7 + 2**33))
    c = deconv_tower.init(cfg, 7)
    assert not np.array_equal(a["l1"]["w"], b["l1"]["w"])
    np.testing.assert_array_equal(a["l1"]["w"], c["l1"]["w"])
    assert a["l0"]["w"].shape == (7, 7, 100, 256)
    assert a["l2"]["b"].shape == (1,) and a["l2"]["b"].dtype == jnp.float32
