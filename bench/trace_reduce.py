"""Reduce a JAX profiler trace and the program's spans to what the
per-layer metrics read.

* Device operations are the events on each TPU plane's ``XLA Ops`` line.
  Busy time is the union of their intervals inside the traced window,
  averaged over the chips that ran anything; the idle share is one minus
  busy over the window.
* An op is named by its HLO instruction without its number, and its
  result type (`short_name`), so a kernel's ops start with its name.
* The host clock of the program's spans (``time.perf_counter``) is put on
  the trace's clock by one `jax.profiler.TraceAnnotation`, `SYNC`, whose
  start was read on both.  A device idle gap is attributed to the spans of
  the serving thread that cover its middle.
"""
from __future__ import annotations

import collections
import re
from typing import Dict, List, Optional, Tuple

SYNC = "bench.sync"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
WORKER_THREAD = "serve-frontend"
TOP = 10

Interval = Tuple[float, float]


def union(intervals: List[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clip(a: float, b: float, lo: float, hi: float) -> float:
    return max(0.0, min(b, hi) - max(a, lo))


def op_family(name: str) -> str:
    """An operation's name without XLA's numeric suffix (``fusion.12``)."""
    return re.sub(r"[.:]\d+$", "", name)


def short_name(event_name: str) -> str:
    """A TPU op event is named by its HLO text, ``%name.N = type op(...)``:
    keep the name without its number and the result's type without its
    layout (``deconv2d_halo_reverse_loop f32[64,8,8,512]``)."""
    if not event_name.startswith("%"):
        return event_name
    head, _, rest = event_name[1:].partition(" = ")
    return f"{op_family(head)} {rest.split(' ', 1)[0].split('{', 1)[0]}"


class Traced:
    """The traced window, in seconds on the host clock of the spans.

    ``ops[d]`` lists ``(name, start, end)`` of chip ``d``'s operations that
    overlap the window; ``spans`` are the program's complete spans that
    started inside it, as ``(name, thread, start, end, args)``."""

    def __init__(self, t_a: float, t_b: float, ops: Dict[int, list],
                 spans: list, planes: Dict[str, Dict[str, int]]):
        self.t_a, self.t_b = t_a, t_b
        self.window_s = t_b - t_a
        self.ops = ops
        self.spans = spans
        self.planes = planes
        self.busy = {d: union([(max(s, t_a), min(e, t_b)) for _, s, e in v
                               if e > t_a and s < t_b])
                     for d, v in ops.items()}
        active = [b for b in self.busy.values() if b]
        self.busy_s = (sum(e - s for b in active for s, e in b)
                       / len(active)) if active else 0.0

    def span_durations(self, name_re: str) -> List[float]:
        pat = re.compile(name_re)
        return [e - s for n, _, s, e, _ in self.spans if pat.fullmatch(n)]

    def span_percentile_ms(self, name_re: str, q: float) -> Optional[float]:
        """Nearest-rank percentile, in ms, of the durations of the spans
        whose name matches ``name_re``; None if there are none."""
        from bench.traffic import percentile

        d = self.span_durations(name_re)
        return percentile(d, q) * 1e3 if d else None

    def idle_gaps(self) -> List[Tuple[float, float]]:
        """Gaps between busy intervals of the first chip in the window."""
        d = min(self.busy) if self.busy else None
        busy = self.busy.get(d, [])
        edges = [self.t_a] + [x for iv in busy for x in iv] + [self.t_b]
        return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]

    def host_doing(self, t: float) -> str:
        """The serving thread's spans covering ``t``, outermost first."""
        cover = sorted(((s, -e, n) for n, th, s, e, _ in self.spans
                        if th == WORKER_THREAD and s <= t <= e))
        return ">".join(n for _, _, n in cover) or "no span (worker waits)"

    def breakdown(self) -> Dict[str, list]:
        """Device operations by total time, and device idle time by what
        the serving thread was doing, each the top `TOP`."""
        ops: Dict[str, float] = collections.Counter()
        for v in self.ops.values():
            for name, s, e in v:
                ops[name] += clip(s, e, self.t_a, self.t_b)
        idle: Dict[str, float] = collections.Counter()
        for a, b in self.idle_gaps():
            idle[self.host_doing(0.5 * (a + b))] += b - a
        return {"device_ops": [[k, v] for k, v in ops.most_common(TOP)],
                "idle_gaps": [[k, v] for k, v in idle.most_common(TOP)]}


def read_planes(path: str):
    """(device ops by chip in trace ns, SYNC start in trace ns, summary of
    planes and lines)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: Dict[int, list] = {}
    sync: Optional[float] = None
    planes: Dict[str, Dict[str, int]] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        lines = {}
        for line in plane.lines:
            events = list(line.events)
            lines[line.name] = len(events)
            if m and line.name == OPS_LINE:
                ops[int(m.group(1))] = [
                    (short_name(e.name), e.start_ns,
                     e.start_ns + e.duration_ns) for e in events]
            elif not m and sync is None:
                for e in events:
                    if e.name == SYNC:
                        sync = e.start_ns
                        break
        planes[plane.name] = lines
    return ops, sync, planes


def reduce(path: str, t_a: float, t_b: float, t_sync: float,
           chrome: dict) -> Traced:
    """Reduce the profile at ``path`` to the window [t_a, t_b] (host
    seconds); ``t_sync`` is the host time read inside the `SYNC`
    annotation and ``chrome`` the program tracer's ``to_chrome()``."""
    ops_ns, sync_ns, planes = read_planes(path)
    if sync_ns is None:
        raise ValueError(f"no {SYNC!r} annotation in {path}")
    if not ops_ns:
        raise ValueError(f"no {OPS_LINE!r} line on a TPU plane in {path}; "
                         f"planes: {planes}")
    off = sync_ns * 1e-9 - t_sync
    ops = {d: [(n, s * 1e-9 - off, e * 1e-9 - off) for n, s, e in v]
           for d, v in ops_ns.items()}
    threads = {ev["tid"]: ev["args"]["name"] for ev in chrome["traceEvents"]
               if ev.get("ph") == "M" and ev.get("name") == "thread_name"}
    spans = []
    for ev in chrome["traceEvents"]:
        if ev.get("ph") != "X":
            continue
        s = ev["ts"] * 1e-6
        if t_a <= s <= t_b:
            spans.append((ev["name"], threads.get(ev["tid"], ""), s,
                          s + ev["dur"] * 1e-6, ev.get("args", {})))
    return Traced(t_a, t_b, ops, spans, planes)
