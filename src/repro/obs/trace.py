"""Lightweight span tracing with a Chrome/Perfetto ``trace_event`` exporter.

A request's whole life — admission → EDF queue wait → wave dispatch →
per-bucket kernel call → collect — renders as one timeline in
https://ui.perfetto.dev (or chrome://tracing), with fault-injection
retries, stragglers, heartbeat fires and elastic-remesh events as
instant markers.

Design constraints, in order:

* **near-zero overhead when disabled** — the hot path is one attribute
  read; :meth:`Tracer.span` returns a shared null singleton (no
  allocation), :meth:`Tracer.complete`/:meth:`Tracer.instant` return
  immediately.
* **monotonic-clock only** — all timestamps come from
  :func:`repro.obs.clock.now`; wall-clock never leaks into a trace.
* **ring-buffered** — a bounded ``deque`` keeps the newest ``capacity``
  events; a long soak can stay traced without growing memory.  The
  events it evicts are counted (:attr:`Tracer.dropped`), so a reader can
  tell a complete window from a truncated one.
* **linked** — every recorded span carries an ``id`` arg and may name
  a ``parent``: given explicitly, or else the scoped span open on the
  recording thread.  A request's ``rid`` leads to its wave, the wave to
  its bucket calls, and each call to its phases.
* **on the profiler's clock** — while enabled, each scoped span, and
  each begin/end span begun with ``annotate=True``, is also a
  ``jax.profiler.TraceAnnotation`` of the same name, so a profile taken
  meanwhile holds the program's spans next to the device ops.  A span
  ended on another thread than its own is not annotated: the profiler
  would place it on the thread that ends it.  JAX is imported on first
  use only: ``obs`` imports without it.

Three recording styles cover the serve stack's shapes:

* ``with tracer.span("generate", rows=n):`` — scoped work on one thread.
* ``h = tracer.begin("queue_wait"); ... tracer.end(h)`` — spans that
  start on one thread (submit) and finish on another (worker), or that
  overlap on one thread without nesting (launched bucket calls).
* ``tracer.complete(name, t0, t1)`` — retroactive, for code that already
  timed itself (submit, collect, plan builds).
"""
from __future__ import annotations

import collections
import itertools
import json
import os
import threading
from typing import Dict, List, Optional, Tuple

from . import clock

__all__ = ["Tracer", "get_tracer", "enable", "disable", "NULL_SPAN"]


class _NullSpan:
    """Shared no-op span/handle returned while tracing is disabled."""

    __slots__ = ()
    id = None

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **args: object) -> None:
        pass


_NULL = _NullSpan()
# for call sites that pick a span or nothing without building its args:
# ``with (tracer.span(...) if tracer.enabled else NULL_SPAN):``
NULL_SPAN = _NULL

_annotation_cls = None


def _profiler_annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` for ``name``; JAX is imported on
    the first call, not with this module."""
    global _annotation_cls
    if _annotation_cls is None:
        from jax.profiler import TraceAnnotation
        _annotation_cls = TraceAnnotation
    return _annotation_cls(name)


class _Span:
    """Context-manager span; records one complete event on exit, and is a
    profiler annotation of the same name while open."""

    __slots__ = ("_tracer", "_name", "_cat", "_args", "_t0", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 args: dict) -> None:
        self._tracer = tracer
        self._name = name
        self._cat = cat
        self._args = args
        self._t0 = 0.0
        self._annotation = None

    def set(self, **args: object) -> None:
        """Add args known only once the span's work has run."""
        self._args.update(args)

    # the span's interval holds its own bookkeeping (clock read first on
    # entry, and on exit last, inside the ring's lock), so spans run back
    # to back leave almost no gap
    def __enter__(self) -> "_Span":
        self._t0 = clock.now()
        tracer = self._tracer
        tracer._open_spans().append(tracer._linked(self._args)["id"])
        self._annotation = _profiler_annotation(self._name)
        self._annotation.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._annotation.__exit__(exc_type, exc, tb)
        tracer = self._tracer
        tracer._open_spans().pop()
        if exc_type is not None:
            self._args["error"] = exc_type.__name__
        if tracer._enabled:
            th = threading.current_thread()
            tracer._append(tracer._event("X", self._name, self._cat,
                                         self._t0, self._args),
                           th.ident or 0, th.name, open_t0=self._t0)
        return False


class SpanHandle:
    """Explicit begin/end handle; may be ended from a different thread.
    Begun with ``annotate``, it is a profiler annotation of the same name
    while open."""

    __slots__ = ("name", "cat", "args", "t0", "ident", "tname",
                 "annotation")

    def __init__(self, name: str, cat: str, args: dict, t0: float,
                 ident: int, tname: str, annotate: bool) -> None:
        self.name = name
        self.cat = cat
        self.args = args
        self.t0 = t0
        self.ident = ident
        self.tname = tname
        self.annotation = None
        if annotate:
            self.annotation = _profiler_annotation(name)
            self.annotation.__enter__()

    @property
    def id(self) -> int:
        """The span's id, for spans that name it as ``parent``."""
        return self.args["id"]


class Tracer:
    """Ring-buffered span recorder emitting Chrome ``trace_event`` JSON."""

    def __init__(self, capacity: int = 65536, enabled: bool = False) -> None:
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._lock = threading.Lock()
        self._events: collections.deque = collections.deque(maxlen=capacity)
        # OS thread ident -> (small display tid, thread name at first record)
        self._tids: Dict[int, Tuple[int, str]] = {}
        self._enabled = bool(enabled)
        # events the full ring evicted since the last clear()
        self.dropped = 0
        # read once (and again on enable): a syscall per event is costly
        self._pid = os.getpid()
        self._ids = itertools.count(1)
        # per thread: ids of the scoped spans open there, innermost last
        self._local = threading.local()

    # -- enable/disable: plain flag writes, deliberately lock-free so the
    # -- disabled fast path is a single unguarded attribute read
    @property
    def enabled(self) -> bool:
        return self._enabled

    def enable(self) -> None:
        self._pid = os.getpid()
        self._enabled = True

    def disable(self) -> None:
        self._enabled = False

    # -- recording ----------------------------------------------------------
    def _open_spans(self) -> List[int]:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def _linked(self, args: dict) -> dict:
        """``args`` with a fresh span id and, unless it names one, the
        open scoped span as parent."""
        args["id"] = next(self._ids)
        stack = self._open_spans()
        if stack and "parent" not in args:
            args["parent"] = stack[-1]
        return args

    def span(self, name: str, cat: str = "serve",
             parent: Optional[int] = None, **args: object):
        """Scoped span; returns a shared null object while disabled.
        ``parent`` overrides the scoped span open on this thread."""
        if not self._enabled:
            return _NULL
        if parent is not None:
            args["parent"] = parent
        return _Span(self, name, cat, args)

    def begin(self, name: str, cat: str = "serve",
              parent: Optional[int] = None, annotate: bool = False,
              **args: object):
        """Start a span that may be ended from another thread, or that
        may overlap others on this one; ``parent`` names its parent.
        ``annotate`` makes it a profiler annotation too, for a span that
        ends on the thread that begins it."""
        if not self._enabled:
            return _NULL
        th = threading.current_thread()
        args["id"] = next(self._ids)
        if parent is not None:
            args["parent"] = parent
        return SpanHandle(name, cat, args, clock.now(), th.ident or 0,
                          th.name, annotate)

    def end(self, handle, **extra: object) -> None:
        """Finish a :meth:`begin` handle; attributed to the begin thread."""
        if handle is None or handle is _NULL:
            return
        t1 = clock.now()
        if handle.annotation is not None:
            handle.annotation.__exit__(None, None, None)
        if not self._enabled:
            return
        args = dict(handle.args)
        args.update(extra)
        self._record("X", handle.name, handle.cat, handle.t0, t1,
                     handle.ident, handle.tname, args)

    def complete(self, name: str, t0: float, t1: float, cat: str = "serve",
                 **args: object) -> None:
        """Record an already-timed span retroactively (current thread)."""
        if not self._enabled:
            return
        th = threading.current_thread()
        self._record("X", name, cat, t0, t1, th.ident or 0, th.name,
                     self._linked(dict(args)))

    def instant(self, name: str, cat: str = "serve", **args: object) -> None:
        """Thread-scoped instant marker (retries, remesh, sheds...)."""
        if not self._enabled:
            return
        th = threading.current_thread()
        t = clock.now()
        self._record("i", name, cat, t, t, th.ident or 0, th.name,
                     dict(args))

    def _record(self, ph: str, name: str, cat: str, t0: float, t1: float,
                ident: int, tname: str, args: dict) -> None:
        ev = self._event(ph, name, cat, t0, args)
        if ph == "X":
            ev["dur"] = max(t1 - t0, 0.0) * 1e6
        else:
            ev["s"] = "t"
        self._append(ev, ident, tname)

    def _event(self, ph: str, name: str, cat: str, t0: float,
               args: dict) -> dict:
        return {"ph": ph, "name": name, "cat": cat, "ts": t0 * 1e6,
                "pid": self._pid, "args": args}

    def _append(self, ev: dict, ident: int, tname: str,
                open_t0: Optional[float] = None) -> None:
        """Add ``ev`` to the ring; given ``open_t0``, ``ev`` is a span that
        started then and ends now, its own recording included."""
        with self._lock:
            ev["tid"] = self._tid_locked(ident, tname)
            if open_t0 is not None:
                ev["dur"] = max(clock.now() - open_t0, 0.0) * 1e6
            if len(self._events) == self.capacity:
                self.dropped += 1
            self._events.append(ev)

    def _tid_locked(self, ident: int, tname: str) -> int:
        # small stable display ids beat raw pthread idents in the UI
        entry = self._tids.get(ident)
        if entry is None:
            entry = (len(self._tids) + 1, tname)
            self._tids[ident] = entry
        return entry[0]

    # -- inspection / export ------------------------------------------------
    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def events(self) -> List[dict]:
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._tids.clear()
            self.dropped = 0

    def to_chrome(self) -> dict:
        """Chrome/Perfetto ``trace_event`` document (JSON object format)."""
        with self._lock:
            events = list(self._events)
            tids = dict(self._tids)
        pid = self._pid
        meta: List[dict] = [{
            "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
            "args": {"name": "repro-serve"}}]
        for tid, tname in sorted(tids.values()):
            meta.append({"ph": "M", "name": "thread_name", "pid": pid,
                         "tid": tid, "args": {"name": tname}})
        return {"traceEvents": meta + events, "displayTimeUnit": "ms"}

    def export(self, path: str) -> int:
        """Write the trace JSON; returns the number of non-meta events."""
        doc = self.to_chrome()
        with open(path, "w") as f:
            json.dump(doc, f)
        return sum(1 for ev in doc["traceEvents"] if ev["ph"] != "M")


_tracer = Tracer()


def get_tracer() -> Tracer:
    """The process-wide tracer the serve stack records into."""
    return _tracer


def enable(clear: bool = False) -> Tracer:
    """Turn on the global tracer (optionally dropping old events)."""
    if clear:
        _tracer.clear()
    _tracer.enable()
    return _tracer


def disable() -> Tracer:
    _tracer.disable()
    return _tracer
