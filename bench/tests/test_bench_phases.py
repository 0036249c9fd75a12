"""The engine's phase spans and their readers, on the CPU."""
import pytest

from bench import trace_reduce as tr


def _reader(name):
    import importlib.util
    import pathlib

    path = pathlib.Path(__file__).parents[1] / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _phased_call(cid, t, upload, call, wait, copy_back, parent=None):
    """A ``dispatch b8`` span with id ``cid`` at ``t`` and its four phases."""
    w = tr.WORKER_THREAD
    spans, s = [], t
    for name, d in (("dispatch.upload", upload), ("dispatch.call", call),
                    ("dispatch.wait", wait),
                    ("dispatch.copy_back", copy_back)):
        spans.append((name, w, s, s + d, {"id": 100 * cid + len(spans),
                                          "parent": cid}))
        s += d
    args = {"bucket": 8, "id": cid}
    if parent is not None:
        args["parent"] = parent
    return [("dispatch b8", w, t, s, args)] + spans


@pytest.mark.parametrize("kind", ["bulk", "stream"])
def test_phase_readers_on_synthetic_spans(kind):
    spans = (_phased_call(1, 0.0, 0.001, 0.002, 0.004, 0.003)
             + _phased_call(2, 1.0, 0.002, 0.002, 0.001, 0.005)
             + _phased_call(3, 2.0, 0.001, 0.001, 0.001, 0.001))
    # a phase whose call began before the window is not paired
    spans.append(("dispatch.upload", tr.WORKER_THREAD, 3.0, 3.5,
                  {"id": 999, "parent": 77}))
    run = type("Run", (), {"trace": tr.Traced(0.0, 10.0, {}, spans, {})})
    copy_back = _reader(f"copy_back_p50_ms.{kind}").read(run)
    launch = _reader(f"launch_p50_ms.{kind}").read(run)
    assert copy_back == pytest.approx(3.0)        # of 3, 5, 1 ms
    assert launch == pytest.approx(3.0)           # of 3, 4, 2 ms
    t = tr.Traced(0.0, 10.0, {0: [("k", 0.0005, 0.0008)]}, spans, {})
    assert t.host_doing(0.0045) == "dispatch b8>dispatch.wait"


@pytest.mark.parametrize("kind", ["bulk", "stream"])
def test_phase_readers_find_nothing_without_phases(kind):
    """A program that records only ``dispatch b<k>`` (no ids, no phases)
    gives no reading, and no error."""
    spans = [("dispatch b8", tr.WORKER_THREAD, 0.0, 0.003,
              {"bucket": 8})]
    run = type("Run", (), {"trace": tr.Traced(0.0, 10.0, {}, spans, {})})
    assert _reader(f"copy_back_p50_ms.{kind}").read(run) is None
    assert _reader(f"launch_p50_ms.{kind}").read(run) is None


def test_recorded_chip_trace_with_phases():
    """A 0.3 s profile of `celeba-bulk` recorded on a TPU v5 lite with the
    engine's phase spans: every bucket call holds its four phases, the
    layers' kernels and the step carry their names, the phase readers
    read, and each call's profiler annotation on the host plane starts
    where its span does, through the one `bench.sync` point."""
    import glob
    import json
    import pathlib
    import statistics

    from jax.profiler import ProfileData

    from bench.phases import PHASES, phase_seconds

    data = pathlib.Path(__file__).parent / "data" / "celeba_bulk_phases_trace"
    meta = json.loads((data / "spans.json").read_text())
    path = glob.glob(str(data / "*.xplane.pb"))[0]
    t = tr.reduce(path, meta["t_a"], meta["t_b"], meta["t_a"],
                  meta["chrome"])
    calls = phase_seconds(t.spans)
    assert len(calls) > 10
    assert all(set(p) == set(PHASES) for p in calls.values())
    ops = {name.split(" ")[0] for name, _ in t.breakdown()["device_ops"]}
    assert {f"deconv2d_l{i}_halo_reverse_loop" for i in range(5)} <= ops
    assert any(k.startswith("wave_dispatch>generate>dispatch b64>dispatch.")
               for k, _ in t.breakdown()["idle_gaps"])
    run = type("Run", (), {"trace": t})
    dispatch = t.span_percentile_ms(r"dispatch b\d+", 50)
    for name in ("copy_back_p50_ms.bulk", "launch_p50_ms.bulk"):
        assert 0.0 < _reader(name).read(run) < dispatch

    _, sync_ns, _ = tr.read_planes(path)
    off = sync_ns * 1e-9 - meta["t_a"]
    host, modules = [], []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if plane.name.startswith("/host:") and e.name == "dispatch b64":
                    host.append(e.start_ns * 1e-9 - off)
                elif line.name == "XLA Modules":
                    modules.append(e.name)
    assert modules and all(m.startswith("jit_serve_celeba_fp32_b64")
                           for m in modules)
    spans = [s for n, _, s, _, _ in t.spans if n == "dispatch b64"]
    gaps = [min(abs(h - s) for h in host) for s in spans]
    assert statistics.median(gaps) < 20e-6 and max(gaps) < 100e-6
