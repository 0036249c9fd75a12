"""One general traffic generator for every mix file in ``bench/traffic/``.

A mix file holds parameters only:

* ``loop`` -- ``"closed"``: ``clients`` callers, each sending its next
  request when its last one came back; ``"open"``: requests arrive on a
  Poisson schedule at ``rate_per_s``, whether or not earlier ones are done.
* ``rows`` -- rows per request: ``{"dist": "const", "value": n}`` or
  ``{"dist": "bounded_pareto", "shape": a, "lo": l, "hi": h}`` (rounded to
  whole rows).
* ``collectors`` (open loop) -- threads that wait for answers, so that one
  request that is served late does not delay the clock of the next.
* ``check_requests`` -- how many served requests are kept for the
  comparison with the reference; the longest served request is always
  among them.

The work is the same for every seed.  An open loop's row counts and gaps
are fixed quantiles of their distributions and the seed only shuffles
them; a closed loop's requests all have one size.  The seed draws the
input rows and the order.
"""
from __future__ import annotations

import itertools
import math
import queue
import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np

now = time.perf_counter

POOL_ROWS = 8192          # distinct input rows a run draws its requests from
RESULT_TIMEOUT_S = 60.0   # an answer later than this after the window never came
# A sender that has fallen behind its schedule still sleeps this long
# between requests: the server shares this process and its interpreter
# lock, and a sender that never sleeps starves the serving thread.
BEHIND_SLEEP_S = 1e-4


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def row_counts(spec: Dict, n: int) -> np.ndarray:
    """``n`` request sizes: stratified quantiles of the mix's distribution."""
    dist = spec["dist"]
    if dist == "const":
        return np.full(n, int(spec["value"]), np.int64)
    if dist == "bounded_pareto":
        a, lo, hi = float(spec["shape"]), float(spec["lo"]), float(spec["hi"])
        p = _quantiles(n)
        x = lo / (1.0 - p * (1.0 - (lo / hi) ** a)) ** (1.0 / a)
        return np.clip(np.rint(x), lo, hi).astype(np.int64)
    raise ValueError(f"unknown rows distribution {dist!r}")


def row_support(spec: Dict) -> List[int]:
    """Every request size the distribution can produce."""
    if spec["dist"] == "const":
        return [int(spec["value"])]
    return list(range(int(spec["lo"]), int(spec["hi"]) + 1))


def open_schedule(mix: Dict, seconds: float, seed: int):
    """(due offsets in seconds from the window's start, rows) of an open
    loop: Poisson gaps as stratified exponential quantiles, shuffled by the
    seed, so every seed sends the same multiset of sizes and gaps."""
    rate = float(mix["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng([seed, 1])
    gaps = -np.log1p(-_quantiles(n)) / rate
    rows = row_counts(mix["rows"], n)
    return np.cumsum(rng.permutation(gaps)), rng.permutation(rows)


class Inputs:
    """The seed's latent rows: request ``k`` of ``rows`` rows reads a
    contiguous slice of one pool at a seeded offset."""

    def __init__(self, seed: int, row_shape, max_rows: int):
        rng = np.random.default_rng([seed, 2])
        self.pool = rng.standard_normal(
            (POOL_ROWS + max_rows,) + tuple(row_shape), dtype=np.float32)
        self.offsets = rng.integers(0, POOL_ROWS, size=1 << 16)

    def rows(self, k: int, n: int) -> np.ndarray:
        o = int(self.offsets[k % len(self.offsets)])
        return self.pool[o:o + n]


class Sampler:
    """Seeded reservoir of served requests kept for the reference check,
    plus the longest request served."""

    def __init__(self, size: int, seed: int):
        self.size = size
        self._rng = np.random.default_rng([seed, 3])
        self._seen = 0
        self._kept: List[tuple] = []
        self._longest: Optional[tuple] = None
        self._lock = threading.Lock()

    def offer(self, k: int, z: np.ndarray, y: np.ndarray) -> None:
        with self._lock:
            self._seen += 1
            if self._longest is None or len(z) > len(self._longest[1]):
                self._longest = (k, z.copy(), np.array(y))
                return
            if len(self._kept) < self.size:
                self._kept.append((k, z.copy(), np.array(y)))
                return
            j = int(self._rng.integers(0, self._seen))
            if j < self.size:
                self._kept[j] = (k, z.copy(), np.array(y))

    def sample(self) -> List[tuple]:
        """[(request index, input rows, served rows)], longest first."""
        with self._lock:
            head = [self._longest] if self._longest is not None else []
            return head + sorted(self._kept, key=lambda r: r[0])


class Record:
    """One request: rows, when it was due and sent, when its answer came
    back (None: never) and the error it raised, if any, as
    ``"submit:<type>"`` (refused) or ``"result:<type>"`` (no answer)."""

    __slots__ = ("k", "rows", "due", "sent", "done", "error")

    def __init__(self, k: int, rows: int, due: float):
        self.k, self.rows, self.due = k, rows, due
        self.sent = self.done = None
        self.error: Optional[str] = None


def run_closed(mix: Dict, submit: Callable, result: Callable, inputs: Inputs,
               sampler: Sampler, t0: float, seconds: float) -> List[Record]:
    """``clients`` callers send back to back until the window closes; a
    request sent before the close is waited for."""
    size = int(mix["rows"]["value"])
    t_end = t0 + seconds
    counter = itertools.count()
    records: List[Record] = []

    def client():
        mine = []
        while True:
            t = now()
            if t >= t_end:
                break
            k = next(counter)
            rec = Record(k, size, t)
            rec.sent = t
            z = inputs.rows(k, size)
            try:
                handle = submit(z)
            except Exception as e:  # a refused request is counted
                rec.error = "submit:" + type(e).__name__
                mine.append(rec)
                continue
            try:
                y = result(handle, RESULT_TIMEOUT_S)
            except Exception as e:  # an answer that never came
                rec.error = "result:" + type(e).__name__
            else:
                rec.done = now()
                sampler.offer(k, z, y)
            mine.append(rec)
        records.extend(mine)

    threads = [threading.Thread(target=client, name=f"bench-client-{i}")
               for i in range(int(mix["clients"]))]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    return records


def run_open(mix: Dict, submit: Callable, result: Callable, inputs: Inputs,
             sampler: Sampler, t0: float, schedule) -> List[Record]:
    """Send each request at its due time; collector threads wait for the
    answers in order of sending."""
    due, rows = schedule
    # each record is made as its request goes out: making them all first
    # would hold back the window's first requests by tens of milliseconds
    records: List[Record] = []
    sent: "queue.Queue" = queue.Queue()
    stop_at = t0 + float(due[-1]) + RESULT_TIMEOUT_S

    def collector():
        while True:
            item = sent.get()
            if item is None:
                return
            rec, handle, z = item
            try:
                y = result(handle, max(0.0, stop_at - now()))
            except Exception as e:
                rec.error = "result:" + type(e).__name__
                continue
            rec.done = now()
            sampler.offer(rec.k, z, y)

    n_coll = int(mix.get("collectors", 1))
    threads = [threading.Thread(target=collector, name=f"bench-collect-{i}")
               for i in range(n_coll)]
    for th in threads:
        th.start()
    try:
        for k, (d, r) in enumerate(zip(due.tolist(), rows.tolist())):
            rec = Record(k, r, t0 + d)
            records.append(rec)
            time.sleep(max(rec.due - now(), BEHIND_SLEEP_S))
            z = inputs.rows(rec.k, rec.rows)
            rec.sent = now()
            try:
                handle = submit(z)
            except Exception as e:
                rec.error = "submit:" + type(e).__name__
                continue
            sent.put((rec, handle, z))
    finally:
        for _ in threads:
            sent.put(None)
        for th in threads:
            th.join()
    return records


def latency_percentile_ms(records: List[Record], q: float) -> Optional[float]:
    """Nearest-rank percentile of the latency, in ms, from each request's
    due time to the return of its answer, over every request; a request
    that failed counts as later than all others (None if that puts the
    percentile among the failures)."""
    lat = [(r.done - r.due) * 1e3 if r.done is not None else math.inf
           for r in records]
    v = percentile(lat, q)
    return v if math.isfinite(v) else None


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (the smallest value with at least ``q``% of
    the values at or below it); ``inf`` entries stand for requests that
    failed."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        return math.nan
    i = max(0, int(math.ceil(q / 100.0 * v.size)) - 1)
    return float(v[i])
