"""Unified observability layer: typed metrics registry (streaming
mean/std/CV vs numpy ground truth), span tracing (nesting, cross-thread
begin/end, disabled-path zero allocation, ring bound), the Chrome/
Perfetto exporter round-trip, and the dual-write contract — the typed
registry and the legacy ``stats()``/``bucket_stats`` dicts are written
at the same sites, so they must agree exactly, single- or
multi-threaded.  Ends with the Table II reporter and a full
admission -> queue -> dispatch -> collect trace from a live frontend."""
import json
import threading

import numpy as np
import pytest
from test_fault_serving import TINY, tiny_setup, tmp_cache  # noqa: F401

from repro.obs import clock, trace
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               MetricTypeError, log_buckets)
from repro.obs.report import table2_rows
from repro.serve import (AsyncServeFrontend, DcnnServeEngine, EngineConfig,
                         TenantClass)


# ---------------------------------------------------------------------------
# metrics: statistics vs numpy, labels, registry
# ---------------------------------------------------------------------------
def test_histogram_stats_match_numpy():
    rng = np.random.RandomState(7)
    samples = rng.gamma(2.0, 0.01, size=500)
    h = Histogram("t")
    for s in samples:
        h.observe(float(s), net="a", bucket=4)
    st = h.summary(net="a", bucket=4)
    assert st["count"] == 500
    assert st["mean"] == pytest.approx(samples.mean(), rel=1e-9)
    assert st["std"] == pytest.approx(samples.std(), rel=1e-6)
    assert st["cv"] == pytest.approx(samples.std() / samples.mean(), rel=1e-6)
    assert st["min"] == pytest.approx(samples.min())
    assert st["max"] == pytest.approx(samples.max())
    # near-constant samples: cancellation must clamp, not go sqrt(-eps)
    h2 = Histogram("t2")
    for _ in range(100):
        h2.observe(0.123456789)
    assert h2.summary()["std"] == pytest.approx(0.0, abs=1e-9)


def test_histogram_merged_summary_pools_across_labels():
    rng = np.random.RandomState(3)
    a, b = rng.rand(40) + 1.0, rng.rand(60) + 2.0
    h = Histogram("t")
    for s in a:
        h.observe(float(s), net="x", bucket=2)
    for s in b:
        h.observe(float(s), net="x", bucket=4)
    pooled = np.concatenate([a, b])
    st = h.merged_summary(net="x")
    assert st["count"] == 100
    assert st["mean"] == pytest.approx(pooled.mean())
    assert st["std"] == pytest.approx(pooled.std(), rel=1e-6)
    # exact-match summary unaffected by the sibling series
    assert h.summary(net="x", bucket=2)["count"] == 40
    assert h.label_values("bucket") == ["2", "4"]


def test_log_buckets_and_quantile_error_bound():
    bounds = log_buckets(1e-5, 100.0, 40)
    assert bounds[0] == pytest.approx(1e-5) and bounds[-1] == pytest.approx(
        100.0)
    assert len(bounds) == 281
    ratios = np.asarray(bounds[1:]) / np.asarray(bounds[:-1])
    assert np.allclose(ratios, 10 ** (1 / 40))
    rng = np.random.RandomState(11)
    samples = rng.lognormal(np.log(3e-3), 0.8, size=2000)
    h = Histogram("lat", buckets=bounds)
    for v in samples[:1000]:
        h.observe(float(v), tenant="a", precision="fp32")
    for v in samples[1000:]:
        h.observe(float(v), tenant="a", precision="int8")
    srt = np.sort(samples)
    for q in (0.01, 0.5, 0.9, 0.99, 1.0):
        want = srt[max(0, int(np.ceil(q * len(srt))) - 1)]   # nearest rank
        got = h.quantile(q, tenant="a")
        assert abs(got - want) / want <= 10 ** (1 / 80) - 1 + 1e-12, q
    assert samples.min() <= h.quantile(0.001, tenant="a")
    assert h.quantile(1.0, tenant="a") <= samples.max()
    assert h.quantile(0.5, tenant="b") is None
    # one repeated value is read back exactly (min and max clamp)
    h2 = Histogram("one", buckets=bounds)
    for _ in range(5):
        h2.observe(0.0123)
    assert h2.quantile(0.5) == pytest.approx(0.0123)


def test_histogram_bucket_counts_and_bounds_validation():
    h = Histogram("t", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    (row,) = h.snapshot()["series"]
    assert row["bucket_counts"] == [1, 1, 1, 1]   # last = overflow
    with pytest.raises(ValueError):
        Histogram("bad", buckets=(1.0, 1.0, 2.0))
    with pytest.raises(ValueError):
        Histogram("bad", buckets=(2.0, 1.0))


def test_counter_and_gauge_semantics():
    c = Counter("c")
    c.inc(tenant="a", outcome="ok")
    c.inc(2, tenant="a", outcome="shed")
    c.inc(tenant="b", outcome="ok")
    assert c.value(tenant="a", outcome="ok") == 1
    assert c.total(tenant="a") == 3       # label-subset sum
    assert c.total() == 4
    assert c.value(tenant="zzz") == 0
    with pytest.raises(ValueError):
        c.inc(-1)
    g = Gauge("g")
    assert g.value(dev="all") is None
    g.set(8, dev="all")
    g.set(4, dev="all")                   # last write wins
    assert g.value(dev="all") == 4


def test_registry_get_or_create_and_type_conflict():
    reg = MetricsRegistry()
    c1 = reg.counter("x", "first help wins")
    assert reg.counter("x") is c1
    with pytest.raises(MetricTypeError):
        reg.gauge("x")
    reg.histogram("h")
    assert reg.names() == ["h", "x"]
    assert reg.get("nope") is None


def test_registry_snapshot_json_round_trip():
    reg = MetricsRegistry()
    reg.counter("c").inc(3, net="a", bucket=4)
    reg.gauge("g").set(1.5)
    reg.histogram("h").observe(0.25, net="a")
    doc = json.loads(json.dumps(reg.snapshot()))
    assert doc["c"]["type"] == "counter"
    # int label values stringify on the way in, so the round trip is exact
    assert doc["c"]["series"] == [
        {"labels": {"net": "a", "bucket": "4"}, "value": 3}]
    assert doc["h"]["series"][0]["count"] == 1
    assert doc["h"]["bounds"] == list(Histogram.DEFAULT_BUCKETS)


def test_registry_threaded_writes_lose_nothing():
    reg = MetricsRegistry()
    n, threads = 200, 8

    def work(i):
        c = reg.counter("ops")           # get-or-create raced deliberately
        h = reg.histogram("lat")
        for k in range(n):
            c.inc(worker=i % 2)
            h.observe(0.001 * (k + 1))

    ts = [threading.Thread(target=work, args=(i,)) for i in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert reg.counter("ops").total() == n * threads
    assert reg.histogram("lat").summary()["count"] == n * threads


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------
def test_disabled_tracer_is_free_and_silent():
    t = trace.Tracer(enabled=False)
    assert t.span("a") is t.span("b")     # shared null object, no alloc
    with t.span("a"):
        pass
    t.complete("x", 0.0, 1.0)
    t.instant("y")
    t.end(t.begin("z"))
    assert len(t) == 0 and not t.enabled


def test_span_nesting_records_in_exit_order():
    t = trace.Tracer(enabled=True)
    with t.span("outer", rows=4):
        with t.span("inner"):
            pass
    inner, outer = t.events()
    assert inner["name"] == "inner" and outer["name"] == "outer"
    assert outer["ts"] <= inner["ts"]
    assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]
    # each span gets an id; the inner one names the outer as its parent
    assert outer["args"] == {"rows": 4, "id": outer["args"]["id"]}
    assert inner["args"] == {"id": inner["args"]["id"],
                             "parent": outer["args"]["id"]}
    assert inner["args"]["id"] != outer["args"]["id"]


def test_span_records_exception_class():
    t = trace.Tracer(enabled=True)
    with pytest.raises(RuntimeError):
        with t.span("boom"):
            raise RuntimeError("x")
    (ev,) = t.events()
    assert ev["args"]["error"] == "RuntimeError"


def test_begin_end_attributes_to_begin_thread():
    t = trace.Tracer(enabled=True)
    with t.span("marker"):               # pin the main thread's display tid
        pass
    h = t.begin("queue_wait", rid=1)
    worker = threading.Thread(target=lambda: t.end(h, outcome="dispatched"),
                              name="worker-0")
    worker.start()
    worker.join()
    marker, qw = t.events()
    assert qw["tid"] == marker["tid"]    # begin thread, not worker
    assert qw["args"] == {"rid": 1, "outcome": "dispatched",
                          "id": qw["args"]["id"]}
    assert qw["args"]["id"] != marker["args"]["id"]
    assert qw["dur"] >= 0


def test_begin_is_a_profiler_annotation_only_when_asked():
    """A begin/end span ended on another thread (``queue_wait``) is no
    profiler annotation, which would land on the ending thread's line;
    one begun with ``annotate`` is, and neither records ``annotate``."""
    t = trace.Tracer(enabled=True)
    plain = t.begin("queue_wait", rid=1)
    marked = t.begin("wave_dispatch", annotate=True, wave=0)
    assert plain.annotation is None and marked.annotation is not None
    t.end(marked)
    t.end(plain)
    assert [(e["name"], sorted(e["args"])) for e in t.events()] == [
        ("wave_dispatch", ["id", "wave"]), ("queue_wait", ["id", "rid"])]


def test_ring_buffer_keeps_newest():
    t = trace.Tracer(capacity=4, enabled=True)
    for i in range(10):
        t.instant(f"e{i}")
    assert len(t) == 4
    assert [e["name"] for e in t.events()] == ["e6", "e7", "e8", "e9"]


def test_ring_buffer_counts_what_it_drops():
    t = trace.Tracer(capacity=4, enabled=True)
    for i in range(3):
        t.instant(f"e{i}")
    assert t.dropped == 0
    for i in range(7):
        with t.span(f"s{i}"):
            pass
    assert len(t) == 4 and t.dropped == 6
    t.clear()
    assert t.dropped == 0 and len(t) == 0


def test_complete_and_spans_on_other_threads_link_parents():
    t = trace.Tracer(enabled=True)

    def elsewhere():
        with t.span("elsewhere"):
            pass

    with t.span("outer"):
        t0 = clock.now()
        t.complete("retro", t0, t0)
        th = threading.Thread(target=elsewhere)
        th.start()
        th.join(timeout=10)
        assert not th.is_alive()
    by = {e["name"]: e["args"] for e in t.events()}
    assert by["retro"]["parent"] == by["outer"]["id"]
    assert "parent" not in by["elsewhere"]   # other thread, no open span
    assert "parent" not in by["outer"]


def test_perfetto_export_round_trip(tmp_path):
    t = trace.Tracer(enabled=True)
    t0 = clock.now()
    t.complete("dispatch b4", t0, t0 + 0.25, bucket=4)
    t.instant("retry", attempt=1)
    path = tmp_path / "trace.json"
    assert t.export(str(path)) == 2      # non-meta events
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    metas = [e for e in evs if e["ph"] == "M"]
    assert {"process_name", "thread_name"} <= {e["name"] for e in metas}
    (x,) = [e for e in evs if e["ph"] == "X"]
    assert x["dur"] == pytest.approx(0.25 * 1e6, rel=1e-6)   # microseconds
    (i,) = [e for e in evs if e["ph"] == "i"]
    assert i["s"] == "t"
    assert all({"ph", "name", "pid", "tid"} <= set(e) for e in evs)
    assert all("ts" in e for e in evs if e["ph"] != "M")


def test_clock_is_monotonic():
    ts = [clock.now() for _ in range(100)]
    assert all(b >= a for a, b in zip(ts, ts[1:]))


# ---------------------------------------------------------------------------
# dual-write contract + reporter, against live engines
# ---------------------------------------------------------------------------
def test_engine_registry_matches_bucket_stats(tmp_cache, tiny_setup):
    params, z, _ = tiny_setup
    reg = MetricsRegistry()
    eng = DcnnServeEngine.from_config(
        EngineConfig(model=TINY, backend="pallas", buckets=(2, 4),
                     warmup=True),
        params, metrics=reg)
    for _ in range(3):
        eng.generate(z)                   # 4 rows -> one b4 call
        eng.generate(z[:2])               # one b2 call
    hist = reg.histogram("engine.dispatch_seconds")
    for bucket, bs in eng.bucket_stats.items():
        # unregistered towers carry their cfg name as the workload label
        st = hist.summary(net=TINY.name, workload=TINY.name,
                          precision="fp32", bucket=bucket)
        assert st["count"] == bs["calls"]
        assert st["total"] == pytest.approx(bs["seconds"])
        mean = bs["seconds"] / bs["calls"]
        var = max(bs["sumsq_seconds"] / bs["calls"] - mean * mean, 0.0)
        assert st["std"] == pytest.approx(np.sqrt(var), abs=1e-12)
    assert reg.counter("engine.generate_calls").total() == 6
    assert reg.counter("engine.images").total() == 3 * 4 + 3 * 2
    assert reg.gauge("engine.device_count").value(
        net=TINY.name, workload=TINY.name,
        precision="fp32") == eng.n_devices

    rows = table2_rows(reg)
    by_bucket = {r["bucket"]: r for r in rows}
    assert set(by_bucket) == {2, 4, "all"}
    assert by_bucket[4]["calls"] == eng.bucket_stats[4]["calls"]
    assert by_bucket[4]["tainted_calls"] == 0
    assert by_bucket["all"]["calls"] == sum(
        bs["calls"] for bs in eng.bucket_stats.values())
    assert by_bucket["all"]["img_per_s"] > 0


def test_table2_rollup_weights_cv_by_calls():
    reg = MetricsRegistry()
    h = reg.histogram("engine.dispatch_seconds")
    for v in (1.0, 1.0, 1.0):                       # b2: cv == 0
        h.observe(v, net="n", precision="fp32", bucket=2)
    for v in (1.0, 3.0):                            # b4: cv == 0.5
        h.observe(v, net="n", precision="fp32", bucket=4)
    reg.counter("engine.tainted_calls").inc(
        net="n", precision="fp32", bucket=4)
    rows = table2_rows(reg)
    by_bucket = {r["bucket"]: r for r in rows}
    assert by_bucket[2]["cv"] == pytest.approx(0.0)
    assert by_bucket[4]["cv"] == pytest.approx(0.5)
    assert by_bucket[4]["tainted_calls"] == 1
    # rollup cv is the calls-weighted average, NOT pooled moments (which
    # would read ~0.47 here from the bucket-mean spread alone)
    assert by_bucket["all"]["cv"] == pytest.approx((0 * 3 + 0.5 * 2) / 5)
    assert by_bucket["all"]["mean_s"] == pytest.approx((3.0 + 4.0) / 5)


def test_table2_empty_registry_is_empty():
    assert table2_rows(MetricsRegistry()) == []


def test_frontend_registry_matches_stats(tmp_cache, tiny_setup):
    """Concurrent submitters: the typed counters and the legacy tenant
    dicts are incremented at the same sites under the same locks, so
    after the dust settles they agree exactly."""
    params, z, _ = tiny_setup
    reg = MetricsRegistry()
    engines = {"fp32": DcnnServeEngine.from_config(
        EngineConfig(model=TINY, backend="pallas", buckets=(2, 4),
                     warmup=True),
        params, metrics=reg)}
    fe = AsyncServeFrontend(engines, [TenantClass("default", slo_ms=None)],
                            metrics=reg)
    try:
        rids = []
        rlock = threading.Lock()

        def client(i):
            rid = fe.submit(z[: 1 + i % 4], "default")
            with rlock:
                rids.append(rid)

        ts = [threading.Thread(target=client, args=(i,)) for i in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        for rid in rids:
            fe.result(rid, timeout_s=120)
        st = fe.stats()["tenants"]["default"]
        req = fe.metrics.counter("frontend.requests")
        assert req.value(tenant="default", outcome="admitted") == 8
        assert req.value(tenant="default", outcome="completed") == 8
        assert st["admitted"] == 8 and st["completed"] == 8
        lat = fe.metrics.histogram("frontend.request_latency_seconds")
        lsum = lat.merged_summary(tenant="default")
        assert lsum["count"] == 8
        assert lsum["mean"] == pytest.approx(st["mean_ms"] / 1e3, rel=1e-6)
        assert lsum["cv"] == pytest.approx(st["cv"], rel=1e-6)
        # percentiles come from the histogram, inside the observed range
        assert st["p50_ms"] == pytest.approx(
            lat.quantile(0.5, tenant="default") * 1e3)
        assert (lsum["min"] * 1e3 <= st["p50_ms"] <= st["p99_ms"]
                <= lsum["max"] * 1e3)
        fe.reset_stats()
        assert req.total() == 0
        assert fe.stats()["tenants"]["default"]["admitted"] == 0
        assert "p50_ms" not in fe.stats()["tenants"]["default"]
        # engine series are cumulative state, not per-window statistics
        assert fe.metrics.counter("engine.generate_calls").total() > 0
    finally:
        fe.close()


def test_trace_covers_request_lifecycle(tmp_cache, tiny_setup, tmp_path):
    """One traced request renders the full admission -> queue wait ->
    wave dispatch -> per-bucket kernel -> collect timeline."""
    params, z, _ = tiny_setup
    engines = {"fp32": DcnnServeEngine.from_config(
        EngineConfig(model=TINY, backend="pallas", buckets=(4,),
                     warmup=True),
        params)}
    fe = AsyncServeFrontend(engines, [TenantClass("default", slo_ms=None)])
    trace.enable(clear=True)
    try:
        rid = fe.submit(z, "default")
        fe.result(rid, timeout_s=120)
    finally:
        trace.disable()
        fe.close()
    path = tmp_path / "t.json"
    tracer = trace.get_tracer()
    assert tracer.export(str(path)) == len(tracer.events())
    names = [e["name"] for e in tracer.events()]
    for expected in ("submit", "queue_wait", "wave_dispatch", "dispatch b4",
                     "generate", "collect"):
        assert any(n == expected for n in names), (expected, names)
    doc = json.loads(path.read_text())
    by_name = {}
    for ev in doc["traceEvents"]:
        if ev["ph"] == "X":
            by_name.setdefault(ev["name"], ev)
    # the kernel call nests inside the wave dispatch on the timeline
    wave, disp = by_name["wave_dispatch"], by_name["dispatch b4"]
    assert wave["ts"] <= disp["ts"]
    assert wave["ts"] + wave["dur"] >= disp["ts"] + disp["dur"]
    qw = by_name["queue_wait"]
    assert qw["args"]["outcome"] == "dispatched"
    assert qw["ts"] + qw["dur"] <= disp["ts"] + disp["dur"]


# ---------------------------------------------------------------------------
# the engine's bucket call, phase by phase
# ---------------------------------------------------------------------------
PHASES = ("dispatch.upload", "dispatch.call", "dispatch.wait",
          "dispatch.copy_back")


def test_traced_generate_splits_each_bucket_call_into_phases(tmp_cache,
                                                             tiny_setup):
    """Each bucket call is a ``dispatch b<k>`` span holding upload, call,
    wait and copy back, in that order, on the same thread, inside it,
    naming it as parent and not overlapping each other."""
    params, z, ref = tiny_setup
    eng = DcnnServeEngine.from_config(
        EngineConfig(model=TINY, backend="pallas", buckets=(2, 4),
                     warmup=True), params)
    zz = np.concatenate([z, z[:2]])          # 6 rows -> one b4, one b2 call
    tracer = trace.enable(clear=True)
    try:
        out = eng.generate(zz)
    finally:
        trace.disable()
    np.testing.assert_allclose(out[:4], ref, rtol=1e-5, atol=1e-5)
    evs = tracer.events()
    (gen,) = [e for e in evs if e["name"] == "generate"]
    calls = [e for e in evs if e["name"].startswith("dispatch b")]
    assert sorted(c["name"] for c in calls) == ["dispatch b2", "dispatch b4"]
    for call in calls:
        assert call["args"]["parent"] == gen["args"]["id"]
        assert call["args"]["steady"] is True
        phases = sorted((e for e in evs
                         if e["args"].get("parent") == call["args"]["id"]),
                        key=lambda e: e["ts"])
        assert tuple(e["name"] for e in phases) == PHASES
        end = call["ts"] + call["dur"]
        prev_end = call["ts"]
        for ph in phases:
            assert ph["tid"] == call["tid"]
            assert ph["ts"] >= prev_end           # in order, no overlap
            prev_end = ph["ts"] + ph["dur"]
            assert prev_end <= end
            assert ph["cat"] == "engine"


def test_untraced_dispatch_stays_one_expression(tmp_cache, tiny_setup,
                                                monkeypatch):
    """With tracing off nothing is recorded, the split path is never
    entered, no span object is built, and no wait sits between the call
    and the copy back: the launch is the upload, the call and the copy's
    enqueue, the finish one ``np.asarray``."""
    from repro.serve import engine as engine_mod

    params, z, ref = tiny_setup
    eng = DcnnServeEngine.from_config(
        EngineConfig(model=TINY, backend="pallas", buckets=(4,),
                     warmup=True), params)
    tracer = trace.get_tracer()
    trace.disable()
    tracer.clear()

    def split_path(*a, **k):
        raise AssertionError("split path taken with tracing off")

    waits = []
    for name in ("_traced_launch", "_traced_finish"):
        monkeypatch.setattr(DcnnServeEngine, name, split_path)
    for name in ("span", "begin", "complete", "instant"):
        monkeypatch.setattr(trace.Tracer, name, split_path)
    monkeypatch.setattr(engine_mod.jax, "block_until_ready",
                        lambda x: waits.append(1) or x)
    out = eng.generate(z)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    assert waits == []
    assert len(tracer) == 0 and tracer.dropped == 0
    assert eng.bucket_stats[4]["calls"] == 1


def test_queue_wait_and_wave_dispatch_share_a_wave_id(tmp_cache, tiny_setup):
    """A request's ``queue_wait`` ends naming the wave that took it; the
    wave's ``wave_dispatch`` span carries the same id, and the engine's
    ``generate`` under it names it as parent."""
    params, z, _ = tiny_setup
    engines = {"fp32": DcnnServeEngine.from_config(
        EngineConfig(model=TINY, backend="pallas", buckets=(4,),
                     warmup=True), params)}
    fe = AsyncServeFrontend(engines, [TenantClass("default", slo_ms=None)])
    tracer = trace.enable(clear=True)
    try:
        rids = []
        for _ in range(3):
            rids.append(fe.submit(z, "default"))
            fe.result(rids[-1], timeout_s=120)
    finally:
        trace.disable()
        fe.close()
    evs = tracer.events()
    waits = {e["args"]["rid"]: e["args"]["wave"] for e in evs
             if e["name"] == "queue_wait"}
    waves = {e["args"]["wave"]: e for e in evs
             if e["name"] == "wave_dispatch"}
    assert set(waits) == set(rids)
    assert sorted(waits.values()) == sorted(waves) and len(waves) == 3
    for e in evs:
        if e["name"] == "generate":
            assert e["args"]["parent"] in {w["args"]["id"]
                                           for w in waves.values()}


def test_scoped_spans_land_on_the_profilers_host_plane(tmp_cache,
                                                       tiny_setup, tmp_path):
    """With a ``jax.profiler`` trace running, the engine's spans are also
    profiler annotations: they appear by name on a host plane."""
    import glob
    import os

    import jax
    from jax.profiler import ProfileData

    params, z, _ = tiny_setup
    eng = DcnnServeEngine.from_config(
        EngineConfig(model=TINY, backend="pallas", buckets=(4,),
                     warmup=True), params)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    trace.enable(clear=True)
    try:
        eng.generate(z)
    finally:
        trace.disable()
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(e.name for e in line.events)
    assert {"generate", "dispatch b4", "dispatch.upload", "dispatch.call",
            "dispatch.wait", "dispatch.copy_back"} <= names
