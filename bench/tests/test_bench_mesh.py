"""`kernel_roofline_share` on a four-chip cell: every bucket call runs
each layer once on every chip, at the bucket's per-chip batch."""
import importlib.util
import json
import pathlib

import pytest

from bench import shapes
from bench import trace_reduce as tr

BENCH = pathlib.Path(__file__).parents[1]
W = tr.WORKER_THREAD


def _reader(name):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _mesh_trace(cfg, truth, chips=4, calls=10, drop=None):
    """Overlapping b256 calls; on chip ``c`` each call's kernels start
    0.2 ms x ``c`` later (the planes' clocks and queues differ) and take
    the least time of a 64-row layer over ``truth``; ``drop`` = (call,
    chip) leaves that call's last kernel out on that chip."""
    peak = shapes.peaks("TPU v5 lite")
    least = [shapes.least_seconds(c, peak)[0]
             for c in shapes.layer_costs(cfg, 256 // chips)]
    dur = [t / truth for t in least]
    period = 4.0 * sum(dur)
    spans, ops = [], {c: [] for c in range(chips)}
    for i in range(calls):
        t = 0.01 + i * period
        spans.append(("dispatch b256", W, t, t + 2.0 * period,
                      {"id": i + 1, "bucket": 256}))
        for c in range(chips):
            s = t + 0.1 * period + 2e-4 * c
            for layer, d in enumerate(dur):
                if (i, c) != drop or layer < len(dur) - 1:
                    ops[c].append(
                        (f"deconv2d_l{layer}_halo_reverse_loop f32[64]",
                         s, s + d))
                s += d
    return spans, ops


@pytest.mark.parametrize("truth", [0.1, 0.4])
def test_kernel_roofline_share_on_four_chips(truth):
    cfg = json.loads((BENCH / "configs" / "celeba.json").read_text())
    spans, ops = _mesh_trace(cfg, truth)
    run = type("Run", (), dict(trace=tr.Traced(0.0, 10.0, ops, spans, {}),
                               cfg=cfg, chips=4, shapes=shapes,
                               device_kind="TPU v5 lite"))
    share = _reader("kernel_roofline_share").read(run)
    assert share == pytest.approx(100.0 * truth, rel=1e-6)


def test_a_call_missing_a_kernel_on_one_chip_is_left_out():
    cfg = json.loads((BENCH / "configs" / "celeba.json").read_text())
    whole = _mesh_trace(cfg, 0.4, calls=3)
    spans, ops = _mesh_trace(cfg, 0.4, calls=3, drop=(1, 2))
    reader = _reader("kernel_roofline_share")

    def read(spans, ops, keep):
        sub = [s for s in spans if s[4]["id"] in keep]
        run = type("Run", (), dict(
            trace=tr.Traced(0.0, 10.0, ops, sub, {}), cfg=cfg, chips=4,
            shapes=shapes, device_kind="TPU v5 lite"))
        return reader.read(run)

    # the share of calls 1 and 3 alone, with call 2 whole, equals the
    # share with call 2 missing a kernel on chip 2: that call is left out
    assert read(spans, ops, {1, 2, 3}) == pytest.approx(
        read(*whole, {1, 2, 3}), rel=1e-6)
    assert read(spans, ops, {2}) is None
