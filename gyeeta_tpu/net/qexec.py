"""Off-loop query executor: bounded worker pool + admission control.

Every query edge (GYT binary, REST gateway, stock NM) used to execute
inline on the asyncio event loop — the same loop that drains agent
sockets into ``Runtime.feed``. A dashboard fleet therefore stalled the
fold and the fold stalled query p99. With snapshot serving
(``query/snapshot.py``) a live query never touches the fold, so it can
leave the loop entirely: :class:`QueryExecutor` runs it on a bounded
``ThreadPoolExecutor`` (snapshot reads are thread-safe — frozen device
buffers + GIL-shared result caches), and sheds with a COUNTED overload
error once the in-flight window fills, instead of wedging the loop
behind an unbounded queue (``gyt_queries_shed_total``; the reference's
L2 pools bound their MPMC queues the same way,
``server/gy_mconnhdlr.h:53-75``).

Shedding is queue-depth-aware and policy-selectable (ROADMAP query
item (d)): under sustained overload the default ``lifo`` policy serves
the NEWEST waiting query first and sheds the OLDEST — a dashboard
refreshing every second wants its latest request answered, not a
30-second-old one it already gave up on; the stale request costs the
same render and produces an ignored response. ``fifo`` keeps classic
arrival order with tail-drop (shed the newest arrival when full) as
the control. Every shed lands on ``gyt_queries_shed_total{policy=…}``.

Knobs (env, read at construction; also settable via ``serve`` flags):

- ``GYT_QUERY_WORKERS``    — pool width (default 4)
- ``GYT_QUERY_QUEUE_MAX``  — max in-flight (queued + running) before
  shedding (default 128)
- ``GYT_QUERY_SHED_POLICY`` — ``lifo`` (default: serve newest, shed
  oldest) or ``fifo`` (serve oldest, shed newest arrival)
- ``GYT_QUERY_SNAPSHOT``   — 0 routes the serving edges back to inline
  strong-consistency execution (the pre-snapshot behavior; the
  escape hatch)

GIL relief (ISSUE-12): the worker threads above still serialize on
the GIL for the pure-Python half of a render, and the REST gateway
additionally pays ``json.dumps`` of every response body ON its
serving loop — at dashboard fan-out sizes that encode is the loop's
single biggest CPU bite. :class:`JsonRenderPool` moves the final
JSON encode of LARGE responses into a ``ProcessPoolExecutor`` behind
``GYT_QUERY_PROCS`` (default 0 = off): the loop thread pays a cheap
C-speed pickle of the row dicts, the child pays the slow encode with
its own GIL, and the bytes come back ready to write. Small responses
(below ``GYT_QUERY_PROCS_MIN_ROWS``, default 64 rows) stay inline —
the pickle round trip would cost more than it frees. The win is
measured, not assumed: ``_querylat.py``'s render-offload phase
records loop-thread CPU per response in both modes
(QUERYLAT_r07.json ``render_offload`` row).
"""

from __future__ import annotations

import asyncio
import collections
import concurrent.futures
import os
import time
from typing import Optional

from gyeeta_tpu.obs.spans import SpanTracer


class Overloaded(Exception):
    """Admission control shed: the in-flight query window is full.
    The serving edge answers a counted busy/overload error; the loop
    (and the fold) stay live."""


def snapshot_serving_enabled(env=None) -> bool:
    env = os.environ if env is None else env
    return str(env.get("GYT_QUERY_SNAPSHOT", "1")).strip().lower() \
        not in ("0", "false", "no")


def query_procs(env=None) -> int:
    env = os.environ if env is None else env
    try:
        return max(0, int(env.get("GYT_QUERY_PROCS", "0")))
    except ValueError:
        return 0


def _encode_json(obj) -> bytes:
    """Child-process encode (top-level for pickling)."""
    import json
    return json.dumps(obj).encode()


class JsonRenderPool:
    """Off-GIL JSON encode tier for the REST gateway edge (see the
    module docstring). Safe by construction: a broken pool (killed
    child, fork trouble) falls back to the inline encode and counts
    it — responses never fail because the relief tier did."""

    def __init__(self, procs: Optional[int] = None,
                 min_rows: Optional[int] = None, stats=None):
        env = os.environ
        self.procs = query_procs() if procs is None else int(procs)
        self.min_rows = int(min_rows if min_rows is not None
                            else env.get("GYT_QUERY_PROCS_MIN_ROWS",
                                         "64"))
        self.stats = stats
        self._pool = None
        if self.procs > 0:
            # spawn, not fork: the serving process is multi-threaded
            # (JAX runtime, query workers, WAL writer) and a forked
            # child can deadlock on locks snapshotted mid-hold
            import multiprocessing
            self._pool = concurrent.futures.ProcessPoolExecutor(
                max_workers=self.procs,
                mp_context=multiprocessing.get_context("spawn"))

    @property
    def enabled(self) -> bool:
        return self._pool is not None

    def _offloadable(self, obj) -> bool:
        return (self._pool is not None and isinstance(obj, dict)
                and obj.get("nrecs", 0) >= self.min_rows)

    def _bump(self, name: str) -> None:
        if self.stats is not None:
            self.stats.bump(name)

    async def encode(self, obj) -> bytes:
        import json
        if not self._offloadable(obj):
            return json.dumps(obj).encode()
        loop = asyncio.get_running_loop()
        try:
            out = await loop.run_in_executor(self._pool, _encode_json,
                                             obj)
            self._bump("query_renders_offloaded")
            return out
        except Exception:               # noqa: BLE001 — relief tier
            self._bump("query_render_offload_errors")
            return json.dumps(obj).encode()

    def encode_sync(self, obj) -> bytes:
        """Blocking form (bench harness)."""
        import json
        if not self._offloadable(obj):
            return json.dumps(obj).encode()
        try:
            out = self._pool.submit(_encode_json, obj).result()
            self._bump("query_renders_offloaded")
            return out
        except Exception:               # noqa: BLE001
            self._bump("query_render_offload_errors")
            return json.dumps(obj).encode()

    def close(self) -> None:
        if self._pool is not None:
            self._pool.shutdown(wait=False, cancel_futures=True)
            self._pool = None


class ReqClock:
    """One request's stamps (``time.perf_counter()``) across the loop
    and a worker thread: ``req`` is the span ring's request id,
    ``t_in`` when the loop had decoded the request, ``t_done`` when its
    answer was computed (the worker's last line; the loop's, for a
    request that ran inline)."""

    __slots__ = ("req", "t_in", "t_done")

    def __init__(self, req: int):
        self.req = req
        self.t_in = self.t_done = time.perf_counter()


class QueryExecutor:
    def __init__(self, rt, workers: Optional[int] = None,
                 queue_max: Optional[int] = None,
                 shed_policy: Optional[str] = None):
        env = os.environ
        self.rt = rt
        # a runtime stand-in without a span ring (tests) gets its own
        self.spans = getattr(rt, "spans", None)
        if self.spans is None:
            self.spans = SpanTracer(stats=rt.stats)
        self.workers = int(workers if workers is not None
                           else env.get("GYT_QUERY_WORKERS", "4"))
        self.queue_max = int(queue_max if queue_max is not None
                             else env.get("GYT_QUERY_QUEUE_MAX", "128"))
        self.shed_policy = (shed_policy if shed_policy is not None
                            else env.get("GYT_QUERY_SHED_POLICY",
                                         "lifo")).strip().lower()
        if self.shed_policy not in ("lifo", "fifo"):
            raise ValueError(
                f"GYT_QUERY_SHED_POLICY must be lifo|fifo, got "
                f"{self.shed_policy!r}")
        self._pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=max(1, self.workers),
            thread_name_prefix="gyt-query")
        self._running = 0             # queries holding a worker thread
        # waiting room, newest at the right; (req, future, clock).
        # All scheduling state is event-loop-confined — no locks.
        self._pending: collections.deque = collections.deque()

    @property
    def _inflight(self) -> int:
        return self._running + len(self._pending)

    # -------------------------------------------------------------- run
    async def run(self, req: dict, clock: Optional[ReqClock] = None
                  ) -> dict:
        """Admit one query: execute immediately while the pool has
        headroom, else wait in the policy-ordered queue. Raises
        :class:`Overloaded` (counted, policy-labeled) when admission
        sheds it — which under ``lifo`` is the OLDEST waiter, so THIS
        call usually proceeds and a stale one errors out instead.
        ``clock`` carries the request's id and stamps (the GYT edge
        takes it where the frame is decoded; other edges start here)."""
        stats = self.rt.stats
        loop = asyncio.get_running_loop()
        if clock is None:
            clock = ReqClock(self.spans.next_req())
        if self._running < self.workers and not self._pending:
            return await self._execute(loop, req, clock)
        if self.shed_policy == "fifo" \
                and self._inflight >= self.queue_max:
            # classic bounded-FIFO tail drop: the NEW arrival sheds
            stats.bump("queries_shed|policy=fifo")
            stats.bump("queries_shed")
            raise Overloaded(
                f"query queue full ({self._inflight} in flight, "
                f"max {self.queue_max})")
        fut = loop.create_future()
        self._pending.append((req, fut, clock))
        if self.shed_policy == "lifo":
            # depth-aware freshness shed: drop the OLDEST waiters past
            # the bound — the dashboard that sent them has already
            # refreshed; the newest request is the one still on screen
            while self._inflight > self.queue_max and len(self._pending) > 1:
                _old_req, old_fut, _old_clock = self._pending.popleft()
                if not old_fut.done():
                    stats.bump("queries_shed|policy=lifo")
                    stats.bump("queries_shed")
                    old_fut.set_exception(Overloaded(
                        f"query queue full (lifo: oldest shed, "
                        f"{self._inflight} in flight, max "
                        f"{self.queue_max})"))
        self._gauge()
        return await fut

    async def _execute(self, loop, req: dict, clock: ReqClock) -> dict:
        self._running += 1
        self._gauge()
        try:
            return await loop.run_in_executor(self._pool, self._call,
                                              req, clock)
        finally:
            self._running -= 1
            self._dispatch_next(loop)
            self._gauge()

    def _dispatch_next(self, loop) -> None:
        """A worker freed: hand it the policy's next waiter (lifo =
        newest first; fifo = oldest first)."""
        while self._pending and self._running < self.workers:
            req, fut, clock = (
                self._pending.pop() if self.shed_policy == "lifo"
                else self._pending.popleft())
            if fut.done():                # already shed
                continue

            async def _chain(req=req, fut=fut, clock=clock):
                try:
                    out = await self._execute(loop, req, clock)
                except BaseException as e:     # noqa: BLE001
                    if not fut.done():
                        fut.set_exception(e)
                else:
                    if not fut.done():
                        fut.set_result(out)

            loop.create_task(_chain())
            return                        # _execute's finally continues

    def _gauge(self) -> None:
        self.rt.stats.gauge("query_queue_depth", float(self._inflight))

    def _call(self, req: dict, clock: ReqClock) -> dict:
        """On a worker thread. ``query_queue`` is the interval the
        request waited for it: admission queue + executor hand-off."""
        self.spans.interval("query_queue", clock.t_in, req=clock.req)
        try:
            with self.spans.request(clock.req):
                return self.rt.query({**req, "consistency": "snapshot"})
        finally:
            clock.t_done = time.perf_counter()

    def prewarm(self, prev) -> int:
        """A tick just replaced snapshot ``prev``: render the requests
        it answered from its cache (asked again within its life — a
        dashboard's refresh) into the NEW snapshot's cache, on the
        workers, before their next ask. Without it the first ask of
        each request after every tick is a render, and every client
        refreshing the same view waits behind it. The renders are the
        ones those asks would have paid; a request nobody repeats costs
        one render more, once. They start with the tick's end, beside
        the loop's catch-up: the first answer from the new tick comes
        later for it (PERF.md §6, PR 30). → jobs handed to the pool."""
        snap = getattr(self.rt, "snapshot", None)
        if prev is None or snap is None or snap is prev:
            return 0
        reqs = prev.repeated()
        for req in reqs:
            self._pool.submit(self._warm, snap, req)
        return len(reqs)

    def _warm(self, snap, req: dict) -> None:
        """On a worker thread: one render-ahead, a request of the
        server's own (its ``query_render`` rides this span's id)."""
        try:
            with self.spans.request(self.spans.next_req()), \
                    self.spans.span("query_prewarm"):
                snap.warm(req)
        except Exception:                   # noqa: BLE001 — nobody waits
            # the live ask of the same request will raise to its client
            self.rt.stats.bump("query_cache_prewarm_errors")

    def close(self) -> None:
        for _req, fut, _clock in self._pending:
            if not fut.done():
                fut.cancel()
        self._pending.clear()
        self._pool.shutdown(wait=False, cancel_futures=True)
