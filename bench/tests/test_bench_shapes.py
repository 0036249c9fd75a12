"""Operation and byte counts, and the peak table, on the CPU."""
import json
import pathlib

import pytest

from bench import shapes

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


@pytest.mark.parametrize("name,flops,macs", [
    ("celeba", 0.821e9, [1.64e6, 134.2e6, 134.2e6, 134.2e6, 6.29e6]),
    ("mnist", 54.7e6, [1.254e6, 25.69e6, 0.401e6]),
])
def test_flops_per_image(name, flops, macs):
    cfg = config(name)
    assert shapes.flops_per_row(cfg) == pytest.approx(flops, rel=1e-3)
    got = [c["flops"] / 2 for c in shapes.layer_costs(cfg, 1)]
    assert got == pytest.approx(macs, rel=2e-3)


def test_costs_scale_with_batch_except_weights():
    cfg = config("celeba")
    one, many = shapes.layer_costs(cfg, 1), shapes.layer_costs(cfg, 64)
    for a, b in zip(one, many):
        assert b["flops"] == 64 * a["flops"]
        assert a["bytes"] < b["bytes"] < 64 * a["bytes"]


def test_celeba_bucket64_bounds():
    """At 64 rows the 1024/512/256-channel layers are bound by compute and
    the 3-channel output layer by memory."""
    peak = shapes.peaks("TPU v5 lite")
    least = [shapes.least_seconds(c, peak)
             for c in shapes.layer_costs(config("celeba"), 64)]
    assert [b for _, b in least] == [
        "memory", "compute", "compute", "compute", "memory"]
    assert least[1][0] == pytest.approx(87.2e-6, rel=1e-2)
    assert least[4][0] == pytest.approx(44.8e-6, rel=1e-2)


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v5e"])
def test_peaks_by_device_kind(kind):
    p = shapes.peaks(kind)
    assert p["matmul_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        shapes.peaks("cpu")
