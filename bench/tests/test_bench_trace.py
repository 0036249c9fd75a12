"""The reduction from a trace to the per-layer metrics, on the CPU."""
import pytest

from bench import trace_reduce as tr


def test_union_merges_overlaps():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5),
                                                               (3, 4)]
    assert tr.union([]) == []


def test_busy_idle_and_attribution():
    ops = {0: [("fusion.1", 0.5, 1.5), ("deconv2d_x.3", 1.0, 2.0),
               ("copy.2", 3.0, 4.0), ("deconv2d_x.7", 9.5, 11.0)],
           1: [("fusion.1", 0.0, 20.0)]}
    spans = [("wave_dispatch", tr.WORKER_THREAD, 2.0, 5.0, {}),
             ("dispatch b8", tr.WORKER_THREAD, 2.1, 4.9, {}),
             ("submit", "client", 6.0, 6.5, {})]
    t = tr.Traced(0.0, 10.0, ops, spans, {})
    # chip 0 busy 0.5-2, 3-4, 9.5-10 (clipped); chip 1 the whole window
    assert t.busy[0] == [(0.5, 2.0), (3.0, 4.0), (9.5, 10.0)]
    assert t.busy_s == pytest.approx((3.0 + 10.0) / 2)
    assert t.idle_gaps() == [(0.0, 0.5), (2.0, 3.0), (4.0, 9.5)]
    assert t.host_doing(2.5) == "wave_dispatch>dispatch b8"
    assert t.host_doing(6.2) == "no span (worker waits)"
    b = t.breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(11.0)]
    assert dict(b["idle_gaps"]) == {
        "no span (worker waits)": pytest.approx(6.0),
        "wave_dispatch>dispatch b8": pytest.approx(1.0)}
    assert t.span_durations(r"dispatch b\d+") == [pytest.approx(2.8)]


def test_op_names_from_hlo_text():
    assert tr.op_family("deconv2d_halo_reverse_loop.12") == \
        "deconv2d_halo_reverse_loop"
    assert tr.op_family("copy-start") == "copy-start"
    hlo = ("%deconv2d_halo_reverse_loop.6 = f32[64,8,8,512]{3,2,1,0:T(8,128)"
           "S(1)} custom-call(f32[64,6,8,1024]{3,2,1,0:T(8,128)S(1)} %pad.18)")
    assert tr.short_name(hlo) == \
        "deconv2d_halo_reverse_loop f32[64,8,8,512]"
    assert tr.short_name("%copy.2 = f32[64,64,64,3]{2,1,3,0:T(8,128)} "
                         "copy(f32[64,64,64,3]{3,2,1,0} %bitcast.8)") == \
        "copy f32[64,64,64,3]"


def test_recorded_chip_trace():
    """A 0.3 s profile of `celeba-bulk` recorded on a TPU v5 lite, with the
    program's spans: the reduction finds the device, the kernels and the
    serving thread, and the shares stay inside their ranges."""
    import glob
    import json
    import pathlib

    from bench import shapes

    data = pathlib.Path(__file__).parent / "data" / "celeba_bulk_trace"
    meta = json.loads((data / "spans.json").read_text())
    path = glob.glob(str(data / "*.xplane.pb"))[0]
    t = tr.reduce(path, meta["t_a"], meta["t_b"], meta["t_a"],
                  meta["chrome"])
    assert list(t.ops) == [0]
    assert 0 < t.busy_s < t.window_s
    kernels = [o for o in t.ops[0]
               if o[0].startswith("deconv2d_halo_reverse_loop")]
    calls = t.span_durations(r"dispatch b64")
    assert len(kernels) >= 5 * len(calls) > 0
    names = [name for name, _ in t.breakdown()["device_ops"]]
    assert "deconv2d_halo_reverse_loop f32[64,8,8,512]" in names
    assert any(k.startswith("wave_dispatch>generate>dispatch b64")
               for k, _ in t.breakdown()["idle_gaps"])

    cfg = json.loads((data.parents[2] / "configs" / "celeba.json")
                     .read_text())
    run = type("Run", (), dict(trace=t, cfg=cfg, chips=1, shapes=shapes,
                               device_kind="TPU v5 lite", records=[]))
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "roofline", data.parents[2] / "metrics" / "kernel_roofline_share.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    share = mod.read(run)
    assert 5.0 < share < 100.0
