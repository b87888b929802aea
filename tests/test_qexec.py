"""Queue-depth-aware query shedding (net/qexec.py): LIFO freshness.

ROADMAP query item (d): under sustained overload a dashboard fleet
wants its NEWEST request answered — the oldest waiter belongs to a
refresh cycle the dashboard already abandoned, so serving it burns a
render on an ignored response. The ``lifo`` policy serves newest-first
and sheds oldest (counted, policy-labeled); ``fifo`` is the classic
arrival-order control with tail drop. The scenario test asserts the
freshness claim directly: mean served submit-index under LIFO beats
FIFO on an identical saturating burst.
"""

from __future__ import annotations

import asyncio
import time

import pytest

from gyeeta_tpu.net.qexec import Overloaded, QueryExecutor
from gyeeta_tpu.utils.selfstats import Stats


class _FakeRT:
    """Just enough runtime for the executor: a stats registry and a
    slow query (the render the pool serializes behind)."""

    def __init__(self, render_s: float = 0.03):
        self.stats = Stats()
        self.render_s = render_s
        self.served: list = []

    def query(self, req):
        time.sleep(self.render_s)
        self.served.append(req["i"])
        return {"i": req["i"], "snaptick": 0}


async def _burst(policy: str, n: int = 10, queue_max: int = 3):
    """Saturating burst: worker pool of 1, ``n`` queries submitted in
    order while the first renders. Returns (rt, served_ok, shed_idx)."""
    rt = _FakeRT()
    ex = QueryExecutor(rt, workers=1, queue_max=queue_max,
                       shed_policy=policy)

    async def one(i):
        try:
            out = await ex.run({"i": i})
            return ("ok", out["i"])
        except Overloaded:
            return ("shed", i)

    tasks = []
    for i in range(n):
        tasks.append(asyncio.ensure_future(one(i)))
        # deterministic arrival order: each submission reaches the
        # executor before the next is created
        await asyncio.sleep(0.002)
    outs = await asyncio.gather(*tasks)
    ex.close()
    ok = [i for kind, i in outs if kind == "ok"]
    shed = [i for kind, i in outs if kind == "shed"]
    return rt, ok, shed


def test_lifo_serves_newest_sheds_oldest():
    rt, ok, shed = asyncio.run(_burst("lifo"))
    assert ok and shed, (ok, shed)
    # the LAST-submitted query is always served under lifo (it is by
    # definition the freshest waiter at every dispatch point)
    assert 9 in ok, ok
    # sheds are the oldest waiters, policy-labeled and totalled
    c = rt.stats.counters
    assert c.get("queries_shed|policy=lifo", 0) == len(shed)
    assert c.get("queries_shed", 0) == len(shed)
    assert max(shed) < max(ok)


def test_fifo_control_tail_drops_newest():
    rt, ok, shed = asyncio.run(_burst("fifo"))
    assert ok and shed, (ok, shed)
    # fifo serves in arrival order; the overflow that sheds is the
    # NEWEST arrival (tail drop)
    assert 0 in ok and 1 in ok
    c = rt.stats.counters
    assert c.get("queries_shed|policy=fifo", 0) == len(shed)
    assert min(shed) > min(ok)


def test_dashboard_freshness_lifo_beats_fifo():
    """THE claim: on the same saturating burst, the mean submit-index
    of SERVED queries (dashboard freshness — later index == fresher
    request) is strictly higher under lifo than fifo."""
    _, ok_l, _ = asyncio.run(_burst("lifo"))
    _, ok_f, _ = asyncio.run(_burst("fifo"))
    fresh_l = sum(ok_l) / len(ok_l)
    fresh_f = sum(ok_f) / len(ok_f)
    assert fresh_l > fresh_f, (ok_l, ok_f)


def test_policy_validated_and_no_hang_on_close():
    rt = _FakeRT()
    with pytest.raises(ValueError):
        QueryExecutor(rt, workers=1, queue_max=1, shed_policy="random")

    async def run():
        ex = QueryExecutor(rt, workers=2, queue_max=8,
                           shed_policy="lifo")
        outs = await asyncio.gather(*(ex.run({"i": i})
                                      for i in range(4)))
        assert sorted(o["i"] for o in outs) == [0, 1, 2, 3]
        ex.close()

    asyncio.run(run())


def test_prewarm_hands_over_and_counts_a_failed_render():
    """``prewarm`` renders the replaced snapshot's repeated requests on
    the fresh one; a render that raises is counted, not lost in the
    pool (the live ask of that request raises to its client)."""

    class _Snap:
        def __init__(self, reqs=()):
            self.reqs, self.warmed = list(reqs), []

        def repeated(self):
            return self.reqs

        def warm(self, req):
            if req.get("bad"):
                raise ValueError("no such column")
            self.warmed.append(req)

    rt = _FakeRT()
    ex = QueryExecutor(rt, workers=1)
    try:
        prev = _Snap([{"i": 1}, {"i": 2, "bad": True}, {"i": 3}])
        rt.snapshot = prev
        assert ex.prewarm(prev) == 0           # no publish in between
        assert ex.prewarm(None) == 0
        rt.snapshot = new = _Snap()
        assert ex.prewarm(prev) == 3
        ex._pool.submit(lambda: None).result()  # one worker: FIFO
        assert new.warmed == [{"i": 1}, {"i": 3}] and not prev.warmed
        assert rt.stats.counters.get("query_cache_prewarm_errors") == 1
        rows = {r["stage"]: r["count"] for r in rt.stats.timing_rows()}
        assert rows.get("query_prewarm") == 3
    finally:
        ex.close()
