"""Pipeline span tracer: the one way host code times a stage.

The reference attributes hot-path cost with ``GY_HISTOGRAM`` wrappers
and prints them on a cadence; histograms answer "how slow is this
stage" but not "what did the last slow batch look like", nor "which
stage held the loop while the device sat idle". One ``with
spans.span(name)`` does three things, so the three views cannot drift
apart:

- writes a row into a fixed-size ring (``name, t, wallms, nrec, path,
  id, parent, req``): ``parent`` is the id of the enclosing span of
  the same thread or asyncio task (0 at top level), ``req`` the
  request the span works for (0 outside a query). Surfaced as
  ``selfstats.spans`` and rendered by ``python -m gyeeta_tpu obs top``;
- feeds ``Stats.observe_ms(name, ms)``, so every span has a timing
  histogram of the same name and the same count (``/metrics``
  ``gyt_stage_duration_seconds{stage=...}``, ``selfstats.timings``);
- with ``annotate=True``, enters ``jax.profiler.TraceAnnotation`` so
  the span lies in the profiler's host plane, on the profiler's clock,
  beside the device's own events. LEAF spans only: a trace reader that
  names an idle gap by the host event that overlaps it most would
  otherwise read the parent (``feed``, ``tick``) over every gap.

An interval that begins on one thread and ends on another (a request
waiting for a worker, a tick waiting for its snapshot swap) is not a
``with`` block: :meth:`SpanTracer.interval` observes it from a
``time.perf_counter()`` stamp, into the ring and the stage of its name.

Overhead discipline: a span is two clock reads, one context-variable
set/reset, one ring write and one histogram bump under their locks —
a few microseconds; a ``TraceAnnotation`` outside a profiler session is
a flag check. Spans are per BATCH, per tick and per query, never per
event. Wall times measure HOST time; jitted dispatches are async, so an
enqueue-only span reads near zero and the first span that blocks on
the device absorbs the device time queued before it. For device
timelines, bracket a live server with ``jax.profiler.start_trace`` /
``stop_trace`` (Python tracer off) as ``benchmarks/lib/child.py`` does.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import threading
import time

_FIELDS = ("name", "t", "wallms", "nrec", "path", "id", "parent", "req")

# (id, req) of the innermost open span of this thread / asyncio task
_OPEN: contextvars.ContextVar = contextvars.ContextVar(
    "gyt_open_span", default=(0, 0))


class _Span:
    """One open span (the ``with`` object of :meth:`SpanTracer.span`)."""

    __slots__ = ("_tr", "_name", "_nrec", "_path", "_req", "_annotate",
                 "_ann", "_id", "_parent", "_tok", "_t", "_p0")

    def __init__(self, tr, name, nrec, path, req, annotate):
        self._tr, self._name, self._nrec = tr, name, nrec
        self._path, self._req, self._annotate = path, req, annotate
        self._ann = None

    def __enter__(self):
        parent, req = _OPEN.get()
        self._parent = parent
        self._req = self._req or req
        self._id = next(self._tr._ids)
        self._tok = _OPEN.set((self._id, self._req))
        if self._annotate:
            self._ann = self._tr._annotation(
                self._name, nrec=self._nrec, req=self._req)
            self._ann.__enter__()
        self._t = time.time()
        self._p0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        ms = (time.perf_counter() - self._p0) * 1e3
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _OPEN.reset(self._tok)
        self._tr.record(self._name, self._t, ms, self._nrec, self._path,
                        self._id, self._parent, self._req)
        return False


class SpanTracer:
    """Ring buffer of spans, safe from any thread (the serving loop and
    the query workers write it). ``capacity`` bounds memory forever; old
    spans are overwritten (the notifymsg-ring discipline). 8192 rows
    hold several seconds at the ~1,000 spans/s of a loaded server.

    ``stats`` is the ``Stats`` registry every span also lands in as a
    timing stage; ``annotation`` is ``jax.profiler.TraceAnnotation``
    (handed in by the runtime so that this module imports no jax — the
    ``obs top`` client imports it)."""

    def __init__(self, capacity: int = 8192, stats=None, annotation=None):
        self._cap = max(capacity, 1)
        self._buf: list = [None] * self._cap
        self._i = 0
        self.total = 0          # spans ever recorded (overwrites included)
        self._stats = stats
        self._annotation = annotation
        self._mu = threading.Lock()
        self._ids = itertools.count(1)      # next() is atomic in CPython
        self._reqs = itertools.count(1)

    def next_req(self) -> int:
        """A process-wide request id: taken where a request enters, it
        rides every span that works for the request, across threads."""
        return next(self._reqs)

    def record(self, name: str, t: float, wallms: float, nrec: int = 0,
               path: str = "", id: int = 0, parent: int = 0,  # noqa: A002
               req: int = 0) -> None:
        """One finished span → ring row + timing stage ``name``."""
        with self._mu:
            self._buf[self._i] = (name, t, wallms, nrec, path, id, parent,
                                  req)
            self._i = (self._i + 1) % self._cap
            self.total += 1
        if self._stats is not None:
            self._stats.observe_ms(name, wallms)

    def span(self, name: str, nrec: int = 0, path: str = "", req: int = 0,
             annotate: bool = False) -> _Span:
        """Time one code block (host wall time): ring row, histogram
        stage ``name`` and, for ``annotate=True``, a profiler
        annotation. ``req`` defaults to the enclosing span's."""
        return _Span(self, name, nrec, path, req,
                     annotate and self._annotation is not None)

    @contextlib.contextmanager
    def request(self, req: int):
        """Spans opened inside the block work for request ``req`` (a
        worker thread picking up a request the loop admitted)."""
        tok = _OPEN.set((_OPEN.get()[0], req))
        try:
            yield
        finally:
            _OPEN.reset(tok)

    def interval(self, name: str, p0: float, nrec: int = 0,
                 req: int = 0) -> None:
        """Observe the interval from the ``time.perf_counter()`` stamp
        ``p0`` (taken on any thread) to now as a top-level row + stage."""
        ms = (time.perf_counter() - p0) * 1e3
        self.record(name, time.time() - ms * 1e-3, ms, nrec, "",
                    next(self._ids), 0, req)

    def __len__(self) -> int:
        return min(self.total, self._cap)

    def rows(self, last: int = 128) -> list[dict]:
        """Newest-first span dicts (bounded by ``last``)."""
        with self._mu:
            n = min(self.total, self._cap, last)
            recs = [self._buf[(self._i - k) % self._cap]
                    for k in range(1, n + 1)]
        return [{f: (round(v, 4) if f == "wallms" else v)
                 for f, v in zip(_FIELDS, rec)} for rec in recs]

    def clear(self) -> None:
        with self._mu:
            self._buf = [None] * self._cap
            self._i = 0
            self.total = 0
