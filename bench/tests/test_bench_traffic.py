"""The traffic generator and the percentile, on the CPU."""
import math
import threading
import time

import numpy as np
import pytest

from bench import traffic

STREAM = {"loop": "open", "rate_per_s": 2000.0, "collectors": 2,
          "rows": {"dist": "bounded_pareto", "shape": 1.2, "lo": 1,
                   "hi": 64}}


def test_same_seed_same_schedule():
    a = traffic.open_schedule(STREAM, 5.0, 2**40 + 11)
    b = traffic.open_schedule(STREAM, 5.0, 2**40 + 11)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def test_other_seed_same_work_other_order():
    due_a, rows_a = traffic.open_schedule(STREAM, 5.0, 1)
    due_b, rows_b = traffic.open_schedule(STREAM, 5.0, 2)
    assert not np.array_equal(rows_a, rows_b)
    np.testing.assert_array_equal(np.sort(rows_a), np.sort(rows_b))
    gaps = [np.sort(np.diff(due, prepend=0.0)) for due in (due_a, due_b)]
    np.testing.assert_allclose(gaps[0], gaps[1], rtol=0, atol=1e-9)
    assert due_a[-1] == pytest.approx(5.0, rel=0.01)


def test_bounded_pareto_rows():
    rows = traffic.row_counts(STREAM["rows"], 100_000)
    assert rows.min() == 1 and rows.max() == 64
    assert rows.mean() == pytest.approx(3.4, rel=0.03)
    assert np.mean(rows >= 32) == pytest.approx(0.009, rel=0.1)


def test_percentile_over_all_requests():
    v = list(range(1, 101))
    assert traffic.percentile(v, 50) == 50
    assert traffic.percentile(v, 95) == 95
    # a failed request counts as later than every served one
    assert traffic.percentile(v[:95] + [math.inf] * 5, 95) == 95
    assert traffic.percentile(v[:94] + [math.inf] * 6, 95) == math.inf


class FakeSystem:
    """Answers each request with its rows times two after ``delay``."""

    def __init__(self, delay=0.0, refuse_every=0):
        self.delay = delay
        self.refuse_every = refuse_every
        self.n = 0
        self.lock = threading.Lock()

    def submit(self, z):
        with self.lock:
            self.n += 1
            if self.refuse_every and self.n % self.refuse_every == 0:
                raise RuntimeError("refused")
        return z

    def result(self, z, timeout_s):
        time.sleep(self.delay)
        return 2 * z


def test_open_loop_sends_on_schedule_and_samples_longest():
    mix = dict(STREAM, rate_per_s=400.0, check_requests=5)
    schedule = traffic.open_schedule(mix, 0.5, 3)
    inputs = traffic.Inputs(3, (4,), 64)
    sampler = traffic.Sampler(5, 3)
    sys_ = FakeSystem(delay=0.001, refuse_every=50)
    t0 = time.perf_counter()
    recs = traffic.run_open(mix, sys_.submit, sys_.result, inputs, sampler,
                            t0, schedule)
    assert len(recs) == len(schedule[0]) == 200
    refused = [r for r in recs if r.error]
    assert len(refused) == 4
    assert all(r.error == "submit:RuntimeError" for r in refused)
    assert all(r.done >= r.sent >= r.due for r in recs if not r.error)
    sample = sampler.sample()
    assert len(sample) == 6
    assert len(sample[0][1]) == max(r.rows for r in recs if not r.error)
    for k, z, y in sample:
        np.testing.assert_array_equal(y, 2 * z)
        np.testing.assert_array_equal(z, inputs.rows(k, len(z)))


def test_closed_loop_keeps_clients_busy_until_close():
    mix = {"loop": "closed", "clients": 3, "check_requests": 4,
           "rows": {"dist": "const", "value": 8}}
    inputs = traffic.Inputs(5, (4,), 8)
    sampler = traffic.Sampler(4, 5)
    sys_ = FakeSystem(delay=0.002)
    t0 = time.perf_counter()
    recs = traffic.run_closed(mix, sys_.submit, sys_.result, inputs, sampler,
                              t0, 0.2)
    assert len(recs) > 3 * 20
    assert sorted(r.k for r in recs) == list(range(len(recs)))
    assert all(r.sent < t0 + 0.2 for r in recs)
    assert all(r.rows == 8 and r.done is not None for r in recs)
    assert len(sampler.sample()) == 5
