#!/usr/bin/env python3
"""Run one cell of the benchmark on the chip and print its result line.

    python3 bench/run.py --workload celeba-bulk --seed 7 --seconds 10 --trace 0

The cell, its configuration, its traffic mix and its metrics are looked up
by name: the cell in ``BENCHMARK.json``, the configuration in the file it
names, the mix in ``bench/traffic/<traffic>.json``, each metric's reader in
``bench/metrics/<metric>.py``, and the configuration's system and plain
reference in ``bench/systems/`` and ``bench/references/``.

Set-up makes the weights from the seed, builds the system and warms every
bucket the mix can reach; then the window runs for ``--seconds``.  With
``--trace 1`` a few seconds inside the window are profiled and the
per-layer metrics are read from that trace and from the program's spans;
without it the end-to-end metrics are printed.  After the window the
system is freed and a sample of its answers, with the longest request in
it, is compared with the plain reference.  The numbers compared are
printed beside their limits, last on standard error and last in the
result line.

A run that finds no TPU, fewer chips than the cell asks for, or a device
kind missing from ``bench/peaks.json`` exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()   # set-up is timed from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
# the compile cache and the autotune cache live at fixed paths inside the
# checkout, so that the next run there finds them and nothing is shared
# with another checkout
COMPILE_CACHE = ROOT / ".jax_cache"
AUTOTUNE_CACHE = ROOT / ".autotune_cache.json"

TRACE_LEAD_S = 1.0     # steady traffic before the profiler starts
TRACE_SPAN_S = 2.0     # profiled part of the window; its spans fit the program's buffer
CHECK_BLOCK_ROWS = 64  # the reference runs over blocks of this many rows
# The control: the plain reference in the program's place, computed one
# step below the configuration's stated precision, in bfloat16 throughout.
CONTROLS = {"bf16": {"storage": "bfloat16", "operands": "bfloat16"}}


class BenchError(RuntimeError):
    """The run cannot produce a valid result."""


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise BenchError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Spec:
    """A cell with its configuration, mix and metric entries."""

    def __init__(self, workload: str):
        bm = json.loads((ROOT / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in bm["workloads"]}
        if workload not in cells:
            raise BenchError(f"unknown workload {workload!r}; "
                             f"BENCHMARK.json has {sorted(cells)}")
        self.cell = cells[workload]
        self.name = workload
        self.chips = int(self.cell["chips"])
        entry = {c["name"]: c for c in bm["configs"]}[self.cell["config"]]
        self.cfg = json.loads((ROOT / entry["file"]).read_text())
        self.mix = json.loads(
            (BENCH / "traffic" / f"{self.cell['traffic']}.json").read_text())

        def mine(m):
            return workload in m.get("workloads", [workload])

        self.end_to_end = [m for m in bm["end_to_end"] if mine(m)]
        self.per_layer = [m for m in bm["per_layer"] if mine(m)]


class CompileCounter:
    """Counts JAX's compile and compile-cache events by name."""

    NAMES = ("/jax/core/compile/backend_compile_duration",
             "/jax/compilation_cache/cache_retrieval_time_sec",
             "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax

        self.counts: Dict[str, int] = {n: 0 for n in self.NAMES}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in self.counts:
            self.counts[event] += 1

    def snapshot(self) -> Dict[str, int]:
        return dict(self.counts)


@contextlib.contextmanager
def set_up_objects_frozen(log=print):
    """Collect, then move every object set-up made (JAX's traced and
    compiled state above all) out of the collector's reach for the
    window: a full collection over them stalls every thread of the
    process, the serving thread included, for tens of milliseconds."""
    t = time.perf_counter()
    gc.collect()
    gc.freeze()
    log(f"[gc] full_collect_ms={(time.perf_counter() - t) * 1e3:.3f} "
        f"frozen_objects={gc.get_freeze_count()}")
    try:
        yield
    finally:
        gc.unfreeze()


class RunView:
    """What a metric reader may read: the cell, the window's requests,
    and, in a traced run, the trace and the program's spans."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def device_info(devices) -> Dict:
    import jax

    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    d0 = jax.devices()[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


def reference_rows(cfg, ref, params, z, **precision):
    """The plain reference's images for ``z``, in blocks of rows."""
    import jax
    import numpy as np

    fwd = jax.jit(lambda p, x: ref.forward(cfg, p, x, **precision))
    pad = -len(z) % CHECK_BLOCK_ROWS
    zp = np.concatenate([z, np.zeros((pad,) + z.shape[1:], z.dtype)])
    r = np.concatenate([
        np.asarray(fwd(params, zp[i:i + CHECK_BLOCK_ROWS]).astype("float32"))
        for i in range(0, len(zp), CHECK_BLOCK_ROWS)])
    return r[:len(z)].astype(np.float64)


def gaps(y, r, limits) -> Dict[str, Dict[str, float]]:
    """``max_gap`` (the widest gap) and ``rms_gap`` of answers ``y``
    against the reference ``r``, each relative to the reference."""
    import numpy as np

    d = np.abs(y - r)
    return {"max_gap": {"value": float(d.max() / np.abs(r).max()),
                        "limit": limits["max_gap"]},
            "rms_gap": {"value": float(np.sqrt((d ** 2).mean()
                                               / (r ** 2).mean())),
                        "limit": limits["rms_gap"]}}


def check_answers(spec: Spec, ref, seed: int, sample: List[tuple],
                  lost: int, controls=()):
    """Compare the sampled answers with the plain reference; returns each
    number compared with its limit, the rows compared, and the same
    numbers for each named control put in the answers' place."""
    import numpy as np

    cfg = spec.cfg
    checks = {"lost_requests": {"value": lost, "limit": 0},
              "bad_shapes": {"value": 0, "limit": 0}}
    want = (cfg["img_hw"], cfg["img_hw"], cfg["img_c"])
    zs, ys = [], []
    for _, z, y in sample:
        if y.shape != (len(z),) + want or not np.isfinite(y).all():
            checks["bad_shapes"]["value"] += 1
            continue
        zs.append(z)
        ys.append(y)
    checks["empty_sample"] = {"value": int(not zs), "limit": 0}
    if not zs:
        return checks, 0, {}
    z = np.concatenate(zs)
    params = ref.init(cfg, seed)
    r = reference_rows(cfg, ref, params, z)
    checks.update(gaps(np.concatenate(ys).astype(np.float64), r,
                       cfg["limits"]))
    control_checks = {
        name: gaps(reference_rows(cfg, ref, params, z, **CONTROLS[name]), r,
                   cfg["limits"])
        for name in controls}
    return checks, len(z), control_checks


def is_correct(checks: Dict[str, Dict[str, float]]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def run_cell(spec: Spec, seed: int, seconds: float, trace: bool,
             t_process: float, precision: Optional[str] = None,
             controls=(), log=print) -> Dict:
    """Set up, run the window, check the answers; returns the result
    line's object.  Makes no check of the platform: `main` does.
    ``precision`` runs the program's own path of that precision in the
    configured one's place; ``controls`` names entries of `CONTROLS` whose
    numbers are read too, under ``control_checks``."""
    import jax

    from bench import shapes, traffic

    cfg, mix = spec.cfg, spec.mix
    compiles = CompileCounter()
    ref = load_module(BENCH / "references" / f"{cfg['reference']}.py",
                      f"bench_ref_{cfg['reference']}")
    systems = load_module(BENCH / "systems" / f"{cfg['system']}.py",
                          f"bench_sys_{cfg['system']}")

    phases = {"start": time.perf_counter() - t_process}
    params = jax.block_until_ready(ref.init(cfg, seed))
    phases["weights"] = time.perf_counter() - t_process
    system = systems.System(cfg, params, precision=precision)
    phases["system"] = time.perf_counter() - t_process
    if mix["loop"] == "open":
        schedule = traffic.open_schedule(mix, seconds, seed)
        sizes = sorted(set(int(r) for r in schedule[1]))
    else:
        schedule = None
        sizes = traffic.row_support(mix["rows"])
    buckets = system.warm(sizes)
    phases["warm"] = time.perf_counter() - t_process
    inputs = traffic.Inputs(seed, system.row_shape, max(sizes))
    sampler = traffic.Sampler(int(mix["check_requests"]), seed)
    engine_compiles = system.compiles()
    setup_counts = compiles.snapshot()
    with set_up_objects_frozen(log):
        t0, records, traced = window(system, mix, schedule, inputs, sampler,
                                     seconds, trace, log)
    setup_s = t0 - t_process
    log(f"[setup] setup_s={setup_s:.4f} buckets={buckets} "
        f"seconds_since_start={ {k: round(v, 3) for k, v in phases.items()} } "
        f"program_compiles={engine_compiles} "
        + " ".join(f"{k.rsplit('/', 1)[-1]}={v}"
                   for k, v in setup_counts.items()))
    window_counts = {k: v - setup_counts[k]
                     for k, v in compiles.snapshot().items()}
    in_window = {k: v for k, v in window_counts.items() if v}
    late = [r.sent - r.due for r in records if r.sent is not None]
    quarters = [0] * 4
    for r in records:
        if r.done is not None and t0 <= r.done < t0 + seconds:
            quarters[int(4 * (r.done - t0) / seconds)] += r.rows
    log(f"[window] seconds={seconds} requests={len(records)} "
        f"rows_per_s_by_quarter={[4 * q / seconds for q in quarters]} "
        f"dispatch_mean_ms={system.dispatch_mean_ms()} "
        f"compiles_in_window={sum(in_window.values())} {in_window} "
        f"program_compiles_in_window={system.compiles() - engine_compiles} "
        f"generator_late_p50_ms={traffic.percentile(late, 50) * 1e3:.4f} "
        f"generator_late_max_ms={max(late, default=0.0) * 1e3:.4f}")

    device = device_info(system.devices())
    device_kind = device["kind"]
    system.close()
    sample = sampler.sample()
    del system, params
    gc.collect()

    refused = sum(1 for r in records
                  if r.error and r.error.startswith("submit:"))
    lost = sum(1 for r in records
               if r.error and r.error.startswith("result:"))
    checks, checked_rows, control_checks = check_answers(
        spec, ref, seed, sample, lost, controls)

    view = RunView(
        cfg=cfg, seconds=seconds, setup_s=setup_s, records=records,
        t_end=t0 + seconds, chips=spec.chips, trace=traced, shapes=shapes,
        device_kind=device_kind)
    metrics, breakdown = {}, None
    for m in (spec.per_layer if trace else spec.end_to_end):
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                             "bench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(view)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if traced is not None:
        device["busy_s"] = traced.busy_s
        device["window_s"] = traced.window_s
        breakdown = traced.breakdown()
    line = {"correct": is_correct(checks), "attempted": len(records),
            "failed": refused + lost, "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checked_rows"] = checked_rows
    if controls:
        line["control_checks"] = control_checks
    line["checks"] = checks
    return line


def window(system, mix, schedule, inputs, sampler, seconds: float,
           trace: bool, log=print):
    """Drive the mix from now for ``seconds`` (an open loop: its
    schedule); returns (start, records, reduced trace or None)."""
    from bench import traffic

    out: Dict[str, list] = {}
    t0 = time.perf_counter()

    def drive():
        if schedule is None:
            out["records"] = traffic.run_closed(
                mix, system.submit, system.result, inputs, sampler, t0,
                seconds)
        else:
            out["records"] = traffic.run_open(
                mix, system.submit, system.result, inputs, sampler, t0,
                schedule)

    traffic_thread = threading.Thread(target=drive, name="bench-traffic")
    traffic_thread.start()
    traced = None
    try:
        if trace:
            traced = profile_window(system, t0, seconds, log)
    finally:
        traffic_thread.join()
    return t0, out["records"], traced


def profile_window(system, t0: float, seconds: float, log=print):
    """Profile part of the window with JAX's profiler and the program's
    span tracer; returns the reduced trace."""
    import jax

    from bench import trace_reduce

    lead = min(TRACE_LEAD_S, 0.1 * seconds)
    span = min(TRACE_SPAN_S, seconds - lead)
    time.sleep(max(0.0, t0 + lead - time.perf_counter()))
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1   # annotations only, not the runtime's
        jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            tracer = system.tracer
            tracer.clear()
            tracer.enable()
            with jax.profiler.TraceAnnotation(trace_reduce.SYNC):
                t_a = time.perf_counter()
            time.sleep(span)
            t_b = time.perf_counter()
            tracer.disable()
        finally:
            jax.profiler.stop_trace()
        chrome = tracer.to_chrome()
        n_spans = len(tracer)
        tracer.clear()
        if n_spans >= tracer.capacity:
            raise BenchError(f"span buffer full ({tracer.capacity} events): "
                             "spans were dropped from the traced window")
        files = sorted(glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not files:
            raise BenchError("the profiler wrote no profile")
        traced = trace_reduce.reduce(files[-1], t_a, t_b, t_a, chrome)
    log(f"[trace] window_s={traced.window_s:.4f} busy_s={traced.busy_s:.6f} "
        f"spans={n_spans} planes={traced.planes}")
    return traced


def use_checkout_caches() -> None:
    """Put the compile cache and the autotune cache at their fixed paths
    in the checkout, and empty the autotune cache: tiles then come from
    the autotune model alone, and a second run finds every program in the
    compile cache.  Call before anything imports JAX."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(COMPILE_CACHE)
    os.environ["REPRO_AUTOTUNE_CACHE"] = str(AUTOTUNE_CACHE)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from repro.kernels import autotune
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    autotune.clear_cache()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = Spec(args.workload)
    use_checkout_caches()
    import jax

    from bench import shapes

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < spec.chips:
        print(f"bench: {args.workload} needs {spec.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    shapes.peaks(devices[0].device_kind)   # an unknown kind raises

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    line = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                    T_PROCESS, log=log)
    log(f"[check] rows compared with the reference: {line['checked_rows']}")
    for name, c in line["checks"].items():
        log(f"[check] {name}={c['value']!r} limit={c['limit']!r}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
