"""Readers of the engine's spans when bucket calls overlap: two launched
calls interleave their phases on one thread, waves carry ``overlapped``,
and a call's kernels may run inside the next call's span."""
import importlib.util
import json
import pathlib

import pytest

from bench import shapes
from bench import trace_reduce as tr
from bench.phases import launch_seconds, phase_seconds

BENCH = pathlib.Path(__file__).parents[1]
W = tr.WORKER_THREAD


def _reader(name):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(spans, ops=None, **kw):
    return type("Run", (), dict(trace=tr.Traced(0.0, 10.0, ops or {}, spans,
                                                {}), **kw))


def test_phases_of_two_overlapping_calls_pair_by_parent():
    """Call 2 is launched between call 1's launch and finish: the phases
    of both interleave on the worker thread and pair by ``parent``."""
    spans = [
        ("dispatch b64", W, 0.0000, 0.0030, {"id": 1, "parent": 10}),
        ("dispatch.upload", W, 0.0000, 0.0004, {"id": 2, "parent": 1}),
        ("dispatch.call", W, 0.0004, 0.0006, {"id": 3, "parent": 1}),
        ("dispatch b64", W, 0.0006, 0.0040, {"id": 4, "parent": 11}),
        ("dispatch.upload", W, 0.0006, 0.0011, {"id": 5, "parent": 4}),
        ("dispatch.call", W, 0.0011, 0.0013, {"id": 6, "parent": 4}),
        ("dispatch.wait", W, 0.0013, 0.0020, {"id": 7, "parent": 1}),
        ("dispatch.copy_back", W, 0.0020, 0.0030, {"id": 8, "parent": 1}),
        ("dispatch.wait", W, 0.0030, 0.0036, {"id": 9, "parent": 4}),
        ("dispatch.copy_back", W, 0.0036, 0.0040, {"id": 12, "parent": 4}),
    ]
    calls = phase_seconds(spans)
    assert set(calls) == {1, 4}
    assert calls[1] == pytest.approx({
        "dispatch.upload": 0.0004, "dispatch.call": 0.0002,
        "dispatch.wait": 0.0007, "dispatch.copy_back": 0.0010})
    assert calls[4] == pytest.approx({
        "dispatch.upload": 0.0005, "dispatch.call": 0.0002,
        "dispatch.wait": 0.0006, "dispatch.copy_back": 0.0004})
    assert sorted(launch_seconds(spans)) == pytest.approx([0.0006, 0.0007])
    run = _run(spans)
    for kind in ("bulk", "stream"):
        assert _reader(f"launch_p50_ms.{kind}").read(run) == pytest.approx(
            0.6)
        assert _reader(f"copy_back_p50_ms.{kind}").read(run) == (
            pytest.approx(0.4))


@pytest.mark.parametrize("kind", ["bulk", "stream"])
def test_wave_overlap_share(kind):
    reader = _reader(f"wave_overlap_share.{kind}")
    waves = [("wave_dispatch", W, 0.001 * i, 0.001 * i + 0.003,
              {"id": i, "wave": i, "overlapped": i % 4 != 0})
             for i in range(8)]
    other = [("dispatch b64", W, 0.0, 0.003, {"id": 99})]
    assert reader.read(_run(waves + other)) == pytest.approx(75.0)
    # a program whose waves carry no ``overlapped`` arg: no reading
    bare = [(n, th, s, e, {"wave": a["wave"]}) for n, th, s, e, a in waves]
    assert reader.read(_run(bare + other)) is None
    assert reader.read(_run(other)) is None


def test_kernel_roofline_share_with_kernels_in_the_next_call():
    """Back-to-back b64 calls whose spans overlap: each call's last two
    kernels run after the next call's span began, so the reader puts
    them there.  Every call but the first still holds one kernel of each
    layer, and the share reads the truth within a point."""
    cfg = json.loads((BENCH / "configs" / "celeba.json").read_text())
    kind = "TPU v5 lite"
    peak = shapes.peaks(kind)
    least = [shapes.least_seconds(c, peak)[0]
             for c in shapes.layer_costs(cfg, 64)]
    truth = 40.0
    dur = [t / (truth / 100.0) for t in least]
    period = 1.1 * sum(dur)
    spans, ops, spilled = [], [], 0
    for i in range(12):
        t = 0.01 + i * period
        spans.append(("dispatch b64", W, t, t + 2.5 * period,
                      {"id": i + 1, "bucket": 64}))
        s = t + 0.5 * period
        for layer, d in enumerate(dur):
            ops.append((f"deconv2d_l{layer}_halo_reverse_loop f32[64]",
                        s, s + d))
            spilled += s + 0.5 * d > t + period   # in the next call's span
            s += d
    assert spilled == 2 * 12
    run = _run(spans, {0: ops}, cfg=cfg, chips=1, shapes=shapes,
               device_kind=kind)
    share = _reader("kernel_roofline_share").read(run)
    assert share == pytest.approx(truth, abs=1.0)
