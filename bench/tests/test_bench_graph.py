"""The U-Net cell's sizes, plain reference and metric readers, on the CPU."""
import copy
import importlib.util
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import graph_trace, shapes, shapes_graph
from bench import run as bench_run
from bench import trace_reduce as tr
from bench.traffic import Record

BENCH = pathlib.Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data" / "unet_bulk_trace"


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = load(BENCH / "references" / "unet.py", "unet_reference")


def metric(name):
    return load(BENCH / "metrics" / f"{name}.py",
                "metric_" + name.replace(".", "_"))


def tiny_cfg():
    """pix2pix-unet256's layout at 32x32 with ngf 8 and 5 downs (down to
    1x1), float32 dot operands as the CPU computes them."""
    widths = [8, 16, 32, 64, 64]
    layers, c = [], 3
    for k, w in enumerate(widths):
        layers.append(dict(kind="conv", c_in=c, c_out=w, kernel=4, stride=2,
                           padding=1, norm=None if k in (0, 4) else "batch",
                           skip=None, in_activation=None,
                           activation=None if k == 4 else "leaky_relu",
                           bias=k == 0))
        c = w
    for j in range(5):
        last = j == 4
        layers.append(dict(kind="deconv",
                           c_in=c if j == 0 else 2 * widths[4 - j],
                           c_out=3 if last else widths[3 - j], kernel=4,
                           stride=2, padding=1,
                           norm=None if last else "batch",
                           skip=None if j == 0 else 4 - j,
                           in_activation="relu",
                           activation="tanh" if last else None, bias=last))
    cfg = copy.deepcopy(config("pix2pix-unet256"))
    cfg.update(in_hw=32, img_hw=32, layers=layers, dot_operands="float32")
    return cfg


def test_published_sizes():
    cfg = config("pix2pix-unet256")
    assert shapes_graph.flops_per_row(cfg) == pytest.approx(12.10e9,
                                                            rel=1e-3)
    assert shapes_graph.weight_count(cfg) == pytest.approx(54.4e6, rel=1e-3)
    costs = shapes_graph.layer_costs(cfg, 1)
    names = [c["name"] for c in costs]
    assert names[:8] == [f"conv2d_l{i}" for i in range(8)]
    assert names[8] == "deconv2d_l8" and names[-1] == "deconv2d_l15"
    assert sum(c["kind"] == "join" for c in costs) == 7
    # two thirds of the operations in the decoder
    by = {k: sum(c["flops"] for c in costs if c["kind"] == k)
          for k in ("conv", "deconv")}
    assert by["deconv"] / (by["conv"] + by["deconv"]) == pytest.approx(
        2 / 3, abs=0.01)
    # the 3-channel output layer moves far more bytes than it computes
    peak = shapes.peaks("TPU v5 lite")
    y = costs[-1]
    assert y["bytes"] / peak["hbm_bytes_per_s"] > \
        y["flops"] / peak["matmul_flops_per_s"]


def lax_forward(cfg, params, x):
    """The generator from XLA's convolutions: a transposed convolution is
    the convolution of the stride-dilated input, padded by K-1-P, with the
    spatially flipped kernel."""
    dn = ("NHWC", "HWIO", "NHWC")
    outs = []
    with jax.default_matmul_precision("highest"):
        for i, l in enumerate(cfg["layers"]):
            if l["skip"] is not None:
                x = jnp.concatenate([outs[l["skip"]], x], axis=-1)
            if l["in_activation"] == "relu":
                x = jax.nn.relu(x)
            q = params[f"l{i}"]
            k, s, p = l["kernel"], l["stride"], l["padding"]
            if l["kind"] == "conv":
                y = jax.lax.conv_general_dilated(x, q["w"], (s, s),
                                                 [(p, p)] * 2,
                                                 dimension_numbers=dn)
            else:
                y = jax.lax.conv_general_dilated(
                    x, q["w"][::-1, ::-1], (1, 1), [(k - 1 - p,) * 2] * 2,
                    lhs_dilation=(s, s), dimension_numbers=dn)
            y = y + q["b"]
            if l["norm"] == "batch":
                bn = q["bn"]
                y = (y - bn["mean"]) / jnp.sqrt(bn["var"] + 1e-5) \
                    * bn["gamma"] + bn["beta"]
            act = l["activation"]
            x = (jax.nn.leaky_relu(y, 0.2) if act == "leaky_relu"
                 else jnp.tanh(y) if act == "tanh" else y)
            outs.append(x)
    return x


def test_reference_matches_lax_composition():
    cfg = tiny_cfg()
    params = REF.init(cfg, 2**40 + 3)
    x = np.random.RandomState(0).randn(3, 32, 32, 3).astype(np.float32)
    want = np.asarray(lax_forward(cfg, params, x), np.float64)
    got = np.asarray(REF.forward(cfg, params, x), np.float64)
    # float32 throughout, sums in different orders: a few ulps
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
    # the tanh output spans its range rather than sitting at 0 or +-1
    assert 0.2 < np.abs(want).max() < 1.0 and np.abs(want).mean() > 0.01


def fake_run(ops, spans, cfg, records=(), t_b=1.0, t_a=0.0, seconds=None):
    t = tr.Traced(t_a, t_b, {0: ops}, spans, {})
    seconds = t_b if seconds is None else seconds
    return type("Run", (), dict(trace=t, cfg=cfg, chips=1, shapes=shapes,
                                device_kind="TPU v5 lite",
                                records=list(records), seconds=seconds,
                                t_end=seconds))


def steps_trace(cfg, n_steps, batch=4, drop=None):
    """``n_steps`` device steps, each an XLA op of 0.02 s then every
    deconvolution kernel for 0.01 s with a 0.005 s XLA op between two,
    inside one ``dispatch b<batch>`` call each; ``drop`` leaves one kernel
    out of the second step."""
    deconvs = graph_trace.deconv_layers(cfg)
    ops, spans, t = [], [], 0.1
    for k in range(n_steps):
        spans.append((f"dispatch b{batch}", "serve-frontend", t - 0.01,
                      t + 0.2, {"id": k}))
        ops.append(("fusion f32[4,16,16,8]", t, t + 0.02))
        t += 0.02
        for i in deconvs:
            if (k, i) != (1, drop):
                ops.append((f"deconv2d_l{i}_halo_reverse_loop f32[4]", t,
                            t + 0.01))
            ops.append(("compare_select_fusion f32[4]", t + 0.01,
                        t + 0.015))
            t += 0.015
        t += 0.05
    return ops, spans


def test_graph_trace_cuts_steps():
    cfg = tiny_cfg()
    ops, spans = steps_trace(cfg, 4)
    st = graph_trace.steps(fake_run(ops, spans, cfg, t_b=2.0))
    # the first cut begins with the trace and is dropped
    assert len(st) == 3
    for s in st:
        assert s["batch"] == 4
        assert s["kernel_s"] == pytest.approx(5 * 0.01)
        # the first op, the joins after each kernel but the last; the last
        # join belongs to the next step
        assert s["xla_s"] == pytest.approx(0.02 + 5 * 0.005)
    ops, spans = steps_trace(cfg, 4, drop=6)
    assert len(graph_trace.steps(fake_run(ops, spans, cfg, t_b=2.0))) == 2
    # a step covered by calls of two buckets is dropped
    ops, spans = steps_trace(cfg, 4)
    spans.append(("dispatch b8", "serve-frontend", 0.0, 2.0, {"id": 9}))
    assert graph_trace.steps(fake_run(ops, spans, cfg, t_b=2.0)) == []


def test_roofline_share_readers():
    cfg = tiny_cfg()
    ops, spans = steps_trace(cfg, 4)
    run = fake_run(ops, spans, cfg, t_b=2.0)
    least = shapes_graph.least_seconds_by_kind(
        cfg, 4, shapes.peaks("TPU v5 lite"))
    assert metric("deconv_roofline_share.unet").read(run) == pytest.approx(
        100.0 * least["deconv"] / (5 * 0.01))
    assert metric("conv_roofline_share.unet").read(run) == pytest.approx(
        100.0 * (least["conv"] + least["join"]) / (0.02 + 5 * 0.005))
    empty = fake_run([], spans, cfg)
    assert metric("deconv_roofline_share.unet").read(empty) is None
    assert metric("conv_roofline_share.unet").read(empty) is None


def test_mfu_reader():
    """Rows handed back in the 4 s window but outside its profiled second
    (1 s to 2 s), over the 3 s left."""
    cfg = config("pix2pix-unet256")
    recs = []
    for k, done in enumerate((0.25, 0.5, 1.5, 3.5, 4.5, None)):
        r = Record(k, 16, 0.0)
        r.done = done
        recs.append(r)
    run = fake_run([], [], cfg, recs, t_a=1.0, t_b=2.0, seconds=4.0)
    want = 100.0 * 48 * 12.096372736e9 / (3.0 * 197e12)
    assert metric("mfu.unet").read(run) == pytest.approx(want, rel=1e-6)
    assert metric("mfu.unet").read(
        fake_run([], [], cfg, t_a=1.0, t_b=2.0, seconds=4.0)) is None


def test_transfer_reader():
    spans = [("dispatch.upload", "w", 0.1, 0.101, {"bytes": 1e6}),
             ("dispatch.copy_back", "w", 0.2, 0.202, {"bytes": 3e6}),
             ("dispatch.call", "w", 0.3, 0.4, {}),
             # a program without the bytes argument says nothing
             ("dispatch.upload", "w", 0.5, 0.6, {})]
    run = fake_run([], spans, tiny_cfg())
    assert metric("host_transfer_gbps.unet").read(run) == pytest.approx(
        4e6 / 0.003 / 1e9)
    assert metric("host_transfer_gbps.unet").read(
        fake_run([], spans[2:], tiny_cfg())) is None


def tiny_spec():
    spec = bench_run.Spec("unet256-bulk")
    spec.cfg = tiny_cfg()
    spec.cfg["engine"] = dict(spec.cfg["engine"], max_batch=4)
    spec.mix = dict(spec.mix, rows={"dist": "const", "value": 4},
                    check_requests=3)
    return spec


def test_sound_run_is_correct_and_the_control_is_not():
    """A whole tiny run of the cell: set-up, the window through the
    frontend, the check against the reference; the bfloat16 control in
    the program's place fails a limit."""
    line = bench_run.run_cell(tiny_spec(), 2**35 + 11, 0.5, False,
                              time.perf_counter(), controls=("bf16",),
                              log=lambda m: None)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["checked_rows"] == 4 * 4
    assert not bench_run.is_correct(line["control_checks"]["bf16"])


def test_recorded_chip_trace(tmp_path):
    """A 0.3 s profile of `unet256-bulk` recorded on a TPU v5 lite (kept
    gzipped: the runtime's transfer threads fill most of it): the steps
    are cut, the kernels and the XLA part are found, and both shares and
    the host's transfer rate read inside their ranges."""
    import gzip

    meta = json.loads((DATA / "spans.json").read_text())
    path = tmp_path / "unet_bulk.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / "unet_bulk.xplane.pb.gz").read_bytes()))
    t = tr.reduce(str(path), meta["t_a"], meta["t_b"], meta["t_a"],
                  meta["chrome"])
    run = type("Run", (), dict(trace=t, cfg=config("pix2pix-unet256"),
                               chips=1, shapes=shapes,
                               device_kind="TPU v5 lite", records=[]))
    st = graph_trace.steps(run)
    assert len(st) >= 2 and all(s["batch"] == 16 for s in st)
    assert all(s["xla_s"] > 0 and s["kernel_s"] > 0 for s in st)
    for name in ("deconv_roofline_share.unet", "conv_roofline_share.unet"):
        assert 0.0 < metric(name).read(run) <= 100.0
    assert metric("host_transfer_gbps.unet").read(run) > 0.0
