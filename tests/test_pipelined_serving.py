"""Pipelined bucket calls: `DcnnServeEngine.launch` / `PendingGenerate`
and the frontend worker that keeps up to two waves launched.  Answers
stay those of the serial path, bit for bit; at most two waves are ever in
flight; faults with a wave in flight resolve every request and requeue
only the wave that failed.  Multi-device cases run in a subprocess
(`test_dist_multidevice.run_sub`)."""
import threading
import time

import numpy as np
import pytest
from test_dist_multidevice import run_sub
from test_fault_serving import TINY, _TINY_SUB, tiny_setup, tmp_cache  # noqa: F401

from repro.dist.inject import FaultInjector, TransientFailure
from repro.obs import clock
from repro.serve import (AsyncServeFrontend, DcnnServeEngine, EngineConfig,
                         TenantClass)


def _engine(params, buckets=(4,), injector=None, **over):
    return DcnnServeEngine.from_config(
        EngineConfig(model=TINY, backend="pallas", buckets=buckets,
                     warmup=True, **over),
        params, fault_injector=injector)


def _frontend(eng, **kw):
    return AsyncServeFrontend({"fp32": eng},
                              [TenantClass("default", slo_ms=None)], **kw)


def _record_launches(eng, monkeypatch):
    """Wrap ``eng.launch``: returns the list of launched batches and a
    dict with the most pending launches seen at once."""
    launch = eng.launch
    batches, seen = [], {"live": 0, "peak": 0}

    def recording(z, parent=None):
        pending = launch(z, parent=parent)
        batches.append(np.array(z))
        seen["live"] += 1
        seen["peak"] = max(seen["peak"], seen["live"])
        result = pending.result

        def counted():
            try:
                return result()
            finally:
                seen["live"] -= 1

        pending.result = counted
        return pending

    monkeypatch.setattr(eng, "launch", recording)
    return batches, seen


def _serial_answers(params, batches, zs, buckets):
    """Each request's rows of the serial `generate` of the batch that
    carried it, on a fresh engine."""
    ref = _engine(params, buckets=buckets)
    outs = [ref.generate(b) for b in batches]
    want = []
    for z in zs:
        for b, y in zip(batches, outs):
            hits = [o for o in range(len(b) - len(z) + 1)
                    if np.array_equal(b[o:o + len(z)], z)]
            if hits:
                want.append(y[hits[0]:hits[0] + len(z)])
                break
        else:
            raise AssertionError("a request's rows were never launched")
    return want


def _failing_images(exc_type):
    class _Failing:
        """A launched call whose images never reach the host."""

        def copy_to_host_async(self):
            pass

        def __array__(self, *a, **k):
            raise exc_type("device-to-host copy failed")

    return _Failing()


def _fail_call(eng, monkeypatch, at: int, exc_type=RuntimeError):
    """Make the ``at``-th launched bucket call (0-based) fail at finish."""
    get_fn = eng._get_fn
    count = {"n": 0}

    def patched(bucket):
        fn = get_fn(bucket)

        def maybe_failing(p, x):
            i = count["n"]
            count["n"] += 1
            return _failing_images(exc_type) if i == at else fn(p, x)

        return maybe_failing

    monkeypatch.setattr(eng, "_get_fn", patched)


# ---------------------------------------------------------------------------
# engine: launch / finish
# ---------------------------------------------------------------------------
def test_launch_result_equals_generate_and_finishes_in_order(tmp_cache,
                                                             tiny_setup):
    params, z, _ = tiny_setup
    eng = _engine(params, buckets=(2, 4))
    zz = np.concatenate([z, z[:1], z[::-1]])        # 9 rows: 4 + 4 + 1
    want_a, want_b = eng.generate(zz), eng.generate(z[:3])
    pa, pb = eng.launch(zz), eng.launch(z[:3])
    assert len(eng._launched) == len(pa.calls) + len(pb.calls) == 4
    np.testing.assert_array_equal(pa.result(), want_a)
    assert len(eng._launched) == len(pb.calls)
    np.testing.assert_array_equal(pb.result(), want_b)
    assert eng._launched == []
    assert pa.result() is pa.result()               # finished once
    assert eng.stats["generate_calls"] == 4
    assert eng.stats["padded_images"] == 2 * (1 + 1)


def test_occupancy_never_exceeds_launch_to_result(tmp_cache, tiny_setup):
    """A call's timing sample runs from the later of its launch and the
    previous call's finish, so a call that queued behind another is
    timed for its own occupancy only."""
    params, z, _ = tiny_setup
    eng = _engine(params)
    t0 = clock.now()
    p1 = eng.launch(z)
    t1 = clock.now()
    p2 = eng.launch(z)
    t2 = clock.now()
    p1.result()
    t3 = clock.now()
    p2.result()
    t4 = clock.now()
    assert 0 < p1.seconds <= t3 - t0
    assert 0 < p2.seconds <= t4 - t1
    assert p2.seconds <= t4 - t2          # starts at p1's finish, not before
    bs = eng.bucket_stats[4]
    assert bs["calls"] == 2
    assert bs["seconds"] == pytest.approx(p1.seconds + p2.seconds)


def test_occupancy_clock_is_shared_by_engines_on_one_device(tmp_cache,
                                                            tiny_setup):
    """The frontend overlaps waves of its fp32 and int8 engines on one
    device.  An int8 call launched behind an fp32 call is timed from the
    fp32 call's finish: the host's time before that finish (here a
    sleep) is not the int8 call's, so it is no straggler, and its sample
    stays within launch-to-result."""
    params, z, _ = tiny_setup
    eng32 = _engine(params)
    eng8 = _engine(params, precision="int8")
    assert eng8._clock is eng32._clock
    for _ in range(4):              # int8's straggler baseline, past warmup
        eng8.generate(z)
    p32 = eng32.launch(z)
    t1 = clock.now()
    p8 = eng8.launch(z)
    time.sleep(0.25)
    t2 = clock.now()
    p32.result()
    p8.result()
    t3 = clock.now()
    assert 0 < p8.seconds <= t3 - t2      # from the fp32 call's finish on
    assert p8.seconds <= t3 - t1
    assert eng8.fault_stats["stragglers"] == 0
    assert eng8.bucket_stats[4]["calls"] == 5
    assert eng8._launched == [] and eng32._launched == []


def test_finish_failure_reruns_the_call(tmp_cache, tiny_setup, monkeypatch):
    """A call whose images fail on their way back runs again through the
    guarded path; the caller sees the right images."""
    params, z, _ = tiny_setup
    eng = _engine(params)
    want = eng.generate(z)
    _fail_call(eng, monkeypatch, at=0)
    p = eng.launch(z)
    np.testing.assert_array_equal(p.result(), want)
    assert p.retried
    assert eng.fault_stats["finish_failures"] == 1
    assert eng._launched == []


# ---------------------------------------------------------------------------
# frontend: two waves in flight
# ---------------------------------------------------------------------------
def test_frontend_answers_equal_serial_generate(tmp_cache, tiny_setup,
                                                monkeypatch):
    """Mixed sizes from 1 to 150 rows, queued before the worker starts so
    waves overlap: each request gets, bit for bit and in its own order,
    the rows serial `generate` gives for the batch that carried it."""
    params, _, _ = tiny_setup
    buckets = (2, 8, 32)
    eng = _engine(params, buckets=buckets)
    batches, seen = _record_launches(eng, monkeypatch)
    rng = np.random.RandomState(3)
    zs = [rng.randn(n, TINY.z_dim).astype(np.float32)
          for n in (1, 7, 150, 33, 2, 64, 90, 5, 1, 12)]
    fe = _frontend(eng, max_queue_rows=1024, start=False)
    try:
        rids = [fe.submit(z, "default") for z in zs]
        fe.start()
        outs = [fe.result(r, timeout_s=300) for r in rids]
        overlapped = fe.metrics.counter("frontend.waves_overlapped").total()
    finally:
        fe.close()
    want = _serial_answers(params, batches, zs, buckets)
    for z, out, w in zip(zs, outs, want):
        assert out.shape == (len(z), TINY.img_hw, TINY.img_hw, TINY.img_c)
        np.testing.assert_array_equal(out, w)
    assert overlapped > 0
    assert seen["peak"] == 2


def test_never_more_than_two_waves_in_flight(tmp_cache, tiny_setup,
                                             monkeypatch):
    params, z, ref = tiny_setup
    eng = _engine(params)
    _, seen = _record_launches(eng, monkeypatch)
    fe = _frontend(eng, start=False)
    try:
        rids = [fe.submit(z, "default") for _ in range(8)]
        fe.start()
        for r in rids:
            np.testing.assert_allclose(fe.result(r, timeout_s=300), ref,
                                       rtol=2e-3, atol=2e-3)
    finally:
        fe.close()
    assert seen["peak"] == 2
    assert seen["live"] == 0


def test_drain_waits_for_both_waves(tmp_cache, tiny_setup):
    params, z, _ = tiny_setup
    fe = _frontend(_engine(params), start=False)
    try:
        rids = [fe.submit(z, "default") for _ in range(3)]
        fe.start()
        fe.drain(timeout_s=300)
        st = fe.stats()
        assert st["queue_rows"] == 0 and st["inflight_rows"] == 0
        for r in rids:                       # resolved: no wait needed
            assert fe.result(r, timeout_s=0).shape[0] == len(z)
    finally:
        fe.close()


def test_waves_overlapped_counts_closed_loop_only(tmp_cache, tiny_setup):
    """Four closed-loop clients keep a wave queued behind the one in
    flight, so waves overlap; one request at a time never does."""
    params, z, _ = tiny_setup
    eng = _engine(params)
    fe = _frontend(eng)
    try:
        for _ in range(4):
            fe.result(fe.submit(z, "default"), timeout_s=300)
        assert fe.metrics.counter("frontend.waves_overlapped").total() == 0

        def client():
            for _ in range(4):
                fe.result(fe.submit(z, "default"), timeout_s=300)

        ts = [threading.Thread(target=client) for _ in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert fe.metrics.counter("frontend.waves_overlapped").value(
            precision="fp32") > 0
        assert fe.stats()["tenants"]["default"]["completed"] == 20
    finally:
        fe.close()


# ---------------------------------------------------------------------------
# faults with a wave in flight
# ---------------------------------------------------------------------------
def test_transient_failure_at_next_launch_keeps_wave_in_flight(
        tmp_cache, tiny_setup):
    """Wave N+1's launch fails typed while wave N is in flight: N is
    answered, only N+1's request is requeued, and it completes later."""
    params, z, _ = tiny_setup
    want = _engine(params).generate(z)
    inj = FaultInjector([TransientFailure(at_call=1)])
    eng = _engine(params, injector=inj, max_retries=0)
    fe = _frontend(eng, start=False)
    try:
        rids = [fe.submit(z, "default") for _ in range(3)]
        fe.start()
        for r in rids:
            np.testing.assert_array_equal(fe.result(r, timeout_s=300), want)
        st = fe.stats()["tenants"]["default"]
        assert st["requeued"] == 1 and st["completed"] == 3
        assert st["shed_requeue"] == 0
    finally:
        fe.close()
    assert inj.log[0][0] == 1
    assert eng.fault_stats["transient_failures"] == 1


def test_failure_at_finish_requeues_only_that_wave(tmp_cache, tiny_setup,
                                                   monkeypatch):
    """Wave N+1's images fail on their way back and its re-run fails too:
    N+1's request alone is requeued; every request completes."""
    params, z, _ = tiny_setup
    want = _engine(params).generate(z)
    # launches: call 0 wave 0, call 1 wave 1 (fails at finish), call 2
    # wave 2, call 3 wave 1's re-run (fails typed, no retries)
    inj = FaultInjector([TransientFailure(at_call=3)])
    eng = _engine(params, injector=inj, max_retries=0)
    _fail_call(eng, monkeypatch, at=1)
    fe = _frontend(eng, start=False)
    try:
        rids = [fe.submit(z, "default") for _ in range(3)]
        fe.start()
        for r in rids:
            np.testing.assert_array_equal(fe.result(r, timeout_s=300), want)
        st = fe.stats()["tenants"]["default"]
        assert st["requeued"] == 1 and st["completed"] == 3
    finally:
        fe.close()
    assert eng.fault_stats["finish_failures"] == 1
    assert [i for i, _ in inj.log] == [3]
    assert eng._launched == []


def test_device_loss_with_a_wave_in_flight(tmp_path):
    """On 8 host devices, wave N+1's launch loses half of them while wave
    N is in flight: N finishes on the mesh that ran it, with the images
    that mesh gave before, the engine remeshes, and every later request
    resolves with the images a healthy 4-device engine gives."""
    cache = str(tmp_path / "at.json")
    out = run_sub(_TINY_SUB + f"""
        import os
        os.environ["REPRO_AUTOTUNE_CACHE"] = {cache!r}
    """ + """
        import jax, numpy as np
        from repro.dist.fault import elastic_mesh
        from repro.dist.inject import DeviceLoss, FaultInjector
        from repro.launch.mesh import make_serving_mesh
        from repro.models.dcnn import generator_init
        from repro.serve import (AsyncServeFrontend, DcnnServeEngine,
                                 EngineConfig, TenantClass)

        params, _ = generator_init(jax.random.PRNGKey(0), TINY)
        buckets = (1, 2, 4, 8, 16)
        # call 0: the 8-device answer to wave 0's rows; the frontend's
        # wave 0 is call 1, and wave 1's launch (call 2) loses 4 devices
        inj = FaultInjector([DeviceLoss(at_call=2, keep=4)])
        eng = DcnnServeEngine.from_config(
            EngineConfig(model=TINY, backend="pallas",
                         mesh=make_serving_mesh(), buckets=buckets),
            params, fault_injector=inj)
        eng4 = DcnnServeEngine.from_config(
            EngineConfig(model=TINY, backend="pallas",
                         mesh=elastic_mesh(jax.devices()[:4],
                                           model_parallel=1),
                         buckets=buckets), params)
        fe = AsyncServeFrontend({"fp32": eng},
                                [TenantClass("default", slo_ms=None)],
                                start=False)
        rng = np.random.RandomState(0)
        zs = [rng.randn(16, TINY.z_dim).astype(np.float32)
              for _ in range(3)]
        want0 = eng.generate(zs[0])
        rids = [fe.submit(z, "default") for z in zs]
        fe.start()
        try:
            outs = [fe.result(r, timeout_s=600) for r in rids]
            st = fe.stats()
        finally:
            fe.close()
        assert [i for i, _ in inj.log] == [2], inj.log
        assert eng.n_devices == 4 and st["remeshes"] == 1
        assert st["tenants"]["default"]["completed"] == 3
        assert fe.metrics.counter("frontend.waves_overlapped").total() >= 1
        np.testing.assert_array_equal(outs[0], want0)
        for z, out in zip(zs[1:], outs[1:]):
            np.testing.assert_array_equal(out, eng4.generate(z))
        ev = eng.fault_stats["remesh_events"][0]
        assert all(ev["plan_hash_matches"].values()), ev
        print("OK")
    """, timeout=900)
    assert "OK" in out


def test_launched_answers_pass_through_generate(tmp_cache, tiny_setup,
                                                monkeypatch):
    """A launched batch is answered by `generate` as every other is: what
    `generate` returns is what the frontend hands out."""
    params, z, _ = tiny_setup
    eng = _engine(params)
    want = eng.generate(z)
    real = DcnnServeEngine.generate
    monkeypatch.setattr(DcnnServeEngine, "generate",
                        lambda self, zz: real(self, zz) + 1.0)
    fe = _frontend(eng, start=False)
    try:
        rids = [fe.submit(z.copy(), "default") for _ in range(3)]
        fe.start()
        for r in rids:
            np.testing.assert_array_equal(fe.result(r, timeout_s=300),
                                          want + 1.0)
        assert fe.metrics.counter("frontend.waves_overlapped").total() > 0
    finally:
        fe.close()
    assert eng._launched == [] and eng._launched_batches == {}


def test_generate_on_other_arrays_leaves_no_call_in_flight(
        tmp_cache, tiny_setup, monkeypatch):
    """A `generate` that answers from other arrays than the launched one
    (the slices of it here) never finishes that launch: its calls are
    abandoned once the answer is back, not kept in flight for ever."""
    params, z, _ = tiny_setup
    eng = _engine(params)
    real = DcnnServeEngine.generate
    monkeypatch.setattr(
        DcnnServeEngine, "generate",
        lambda self, zz: np.concatenate([real(self, zz[:1]),
                                         real(self, zz[1:])]))
    p = eng.launch(z)
    assert p.result().shape[0] == len(z)
    assert eng._launched == [] and eng._launched_batches == {}
    fe = _frontend(eng, start=False)
    try:
        rids = [fe.submit(z.copy(), "default") for _ in range(3)]
        fe.start()
        for r in rids:
            assert fe.result(r, timeout_s=300).shape[0] == len(z)
    finally:
        fe.close()
    assert eng._launched == [] and eng._launched_batches == {}
