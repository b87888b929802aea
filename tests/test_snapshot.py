"""Snapshot-isolated query serving (ISSUE 9).

Satellite done-criteria: queries racing a full-rate feed on a second
thread return a single-tick-consistent view byte-equal to the same
query run serialized at that tick (Runtime AND ShardedRuntime);
per-snapshot result-cache invalidation on tick/CRUD/restore; NM-vs-REST
byte-equal parity preserved through the snapshot path; overload
shedding (queue cap hit → counted error, serving loop stays live); and
a 100-query burst between ticks causes ZERO fold dispatches.
"""

from __future__ import annotations

import asyncio
import json
import threading
import time

import pytest

from gyeeta_tpu.engine.aggstate import EngineCfg
from gyeeta_tpu.ingest import wire
from gyeeta_tpu.runtime import Runtime
from gyeeta_tpu.sim.partha import ParthaSim
from gyeeta_tpu.utils.config import RuntimeOpts

CFG = EngineCfg(n_hosts=8, svc_capacity=256, task_capacity=256,
                conn_batch=256, resp_batch=512, listener_batch=64,
                fold_k=2)

QUERY = {"subsys": "svcstate", "sortcol": "svcid", "sortdesc": False,
         "maxrecs": 100}


def _feed_buf(sim, n=256):
    return (sim.conn_frames(n) + sim.resp_frames(2 * n)
            + sim.listener_frames()
            + wire.encode_frame(wire.NOTIFY_HOST_STATE,
                                sim.host_state_records()))


def _warm(rt, sim, ticks=2):
    rt.feed(sim.name_frames())
    for _ in range(ticks):
        rt.feed(_feed_buf(sim))
        rt.run_tick()


def _dispatches(rt) -> int:
    c = rt.stats.counters
    return (c.get("fold_dispatches", 0) + c.get("slab_dispatches", 0))


def _race_snapshot_consistency(rt, sim, n_queries=40):
    """Feed at full rate on a second thread while the main thread
    queries the snapshot: every response must be byte-equal to the
    reference taken serialized right after the publish tick."""
    ref = json.dumps(rt.query({**QUERY, "consistency": "snapshot"}),
                     default=str, sort_keys=True)
    stop = threading.Event()
    errs: list = []

    def pump():
        try:
            while not stop.is_set():
                rt.feed(_feed_buf(sim))
        except Exception as e:          # pragma: no cover
            errs.append(e)

    t = threading.Thread(target=pump, daemon=True)
    t.start()
    try:
        for _ in range(n_queries):
            got = json.dumps(
                rt.query({**QUERY, "consistency": "snapshot"}),
                default=str, sort_keys=True)
            assert got == ref, "snapshot leaked mid-tick folds"
    finally:
        stop.set()
        t.join(timeout=30)
    assert not errs, errs
    # the feed thread really folded new data meanwhile
    rt.flush()
    strong = rt.query(dict(QUERY))
    assert json.dumps(strong, default=str, sort_keys=True) != ref \
        or rt.snapshot.tick == rt._tick_no


def test_snapshot_isolation_under_feed_runtime():
    rt = Runtime(CFG)
    try:
        sim = ParthaSim(n_hosts=8, n_svcs=3, seed=11)
        _warm(rt, sim)
        _race_snapshot_consistency(rt, sim)
    finally:
        rt.close()


@pytest.mark.slow
def test_snapshot_isolation_under_feed_sharded():
    from gyeeta_tpu.parallel import make_mesh
    from gyeeta_tpu.parallel.shardedrt import ShardedRuntime

    srt = ShardedRuntime(CFG._replace(n_hosts=16), make_mesh(8),
                         RuntimeOpts(dep_pair_capacity=1024,
                                     dep_edge_capacity=512))
    try:
        sim = ParthaSim(n_hosts=16, n_svcs=3, seed=13)
        _warm(srt, sim)
        _race_snapshot_consistency(srt, sim, n_queries=15)
    finally:
        srt.close()


def test_query_burst_between_ticks_zero_dispatches():
    """Satellite: live queries no longer force a device dispatch — a
    100-query burst between ticks folds NOTHING (asserted via
    selfstats), and repeats collapse into the result cache."""
    rt = Runtime(CFG)
    try:
        sim = ParthaSim(n_hosts=8, n_svcs=3, seed=12)
        _warm(rt, sim)
        # staged-but-unfolded records must stay staged (no flush)
        rt.feed(sim.conn_frames(64))
        d0 = _dispatches(rt)
        q0 = rt.stats.counters.get("queries", 0)
        for _ in range(100):
            out = rt.query({**QUERY, "consistency": "snapshot"})
        assert _dispatches(rt) == d0
        assert rt.stats.counters.get("queries", 0) == q0 + 100
        assert out["snaptick"] == rt.snapshot.tick
        hits = rt.stats.counters.get("query_cache_hits", 0)
        assert hits >= 99
    finally:
        rt.close()


def test_result_cache_invalidation_on_tick_crud_restore(tmp_path):
    rt = Runtime(CFG, RuntimeOpts(
        checkpoint_dir=str(tmp_path), checkpoint_every_ticks=10 ** 9))
    try:
        sim = ParthaSim(n_hosts=8, n_svcs=3, seed=14)
        _warm(rt, sim)
        a = rt.query({**QUERY, "consistency": "snapshot"})
        b = rt.query({**QUERY, "consistency": "snapshot"})
        assert a is b                      # same snapshot → cache hit
        ver0 = rt.snapshot.version

        # --- tick invalidates: new snapshot, new render, fresh data
        rt.feed(_feed_buf(sim))
        rt.run_tick()
        assert rt.snapshot.version > ver0
        c = rt.query({**QUERY, "consistency": "snapshot"})
        assert c is not a
        assert c["snaptick"] > a["snaptick"]

        # --- CRUD invalidates aux views mid-snapshot
        before = rt.query({"subsys": "alertdef",
                           "consistency": "snapshot"})
        rt.query({"op": "add", "objtype": "alertdef",
                  "alertname": "snapdef", "subsys": "svcstate",
                  "filter": "{ svcstate.state in 'Severe' }"})
        after = rt.query({"subsys": "alertdef",
                          "consistency": "snapshot"})
        assert "snapdef" in [r.get("alertname") for r in after["recs"]]
        assert before["nrecs"] == after["nrecs"] - 1

        # --- restore republishes over the restored state
        from gyeeta_tpu.utils import checkpoint as ckpt
        path = ckpt.save(str(tmp_path / "snap_test.npz"), rt.cfg,
                         rt.state, extra={"tick": rt._tick_no})
        rt.feed(_feed_buf(sim))
        rt.run_tick()
        ver1 = rt.snapshot.version
        rt.restore(path)
        assert rt.snapshot.version > ver1
        d = rt.query({**QUERY, "consistency": "snapshot"})
        assert d["snaptick"] == rt._tick_no
    finally:
        rt.close()


def test_strong_consistency_optin_still_flushes():
    """consistency=strong keeps the flush-then-read semantics: staged
    records become visible without a tick."""
    rt = Runtime(CFG)
    try:
        sim = ParthaSim(n_hosts=8, n_svcs=3, seed=15)
        _warm(rt, sim)
        base = rt.query({"subsys": "serverstatus",
                         "consistency": "snapshot"})["recs"][0]
        rt.feed(sim.conn_frames(512))
        strong = rt.query({"subsys": "serverstatus",
                           "consistency": "strong"})["recs"][0]
        assert strong["connevents"] > base["connevents"]
        with pytest.raises(ValueError):
            rt.query({"subsys": "svcstate", "consistency": "nope"})
    finally:
        rt.close()


# --------------------------------------------------------- serving edge
async def _busy_edge_scenario():
    """Overload shedding: queue cap hit → counted QS_BUSY error while
    the loop (and later queries) stay live."""
    from gyeeta_tpu.net import GytServer, QueryClient

    rt = Runtime(CFG)
    sim = ParthaSim(n_hosts=8, n_svcs=3, seed=16)
    _warm(rt, sim)
    srv = GytServer(rt, tick_interval=None, query_workers=1,
                    query_queue_max=1)
    host, port = await srv.start()

    # make snapshot queries slow enough to overlap: wrap the pool call
    inner = srv.qexec._call

    def slow_call(*args):
        import time
        time.sleep(0.3)
        return inner(*args)

    srv.qexec._call = slow_call

    async def one(i):
        qc = QueryClient()
        await qc.connect(host, port)
        try:
            return await qc.query({"subsys": "svcstate", "maxrecs": 5})
        except RuntimeError as e:
            return {"error": str(e)}
        finally:
            await qc.close()

    outs = await asyncio.gather(*(one(i) for i in range(6)))
    shed = [o for o in outs if "error" in o]
    ok = [o for o in outs if "error" not in o]
    counted = rt.stats.counters.get("queries_shed", 0)

    # loop still live: an inline (strong) query and a fresh snapshot
    # query both succeed afterwards
    srv.qexec._call = inner
    qc = QueryClient()
    await qc.connect(host, port)
    after = await qc.query({"subsys": "svcstate", "maxrecs": 5,
                            "consistency": "strong"})
    after_snap = await qc.query({"subsys": "svcstate", "maxrecs": 5})
    await qc.close()
    await srv.stop()
    return shed, ok, counted, after, after_snap


def test_overload_shed_counted_loop_alive():
    shed, ok, counted, after, after_snap = \
        asyncio.run(_busy_edge_scenario())
    assert shed and ok, (shed, ok)
    assert counted == len(shed)
    assert all("queue full" in o["error"] for o in shed)
    assert after["nrecs"] == 5 and after_snap["nrecs"] == 5


async def _parity_scenario(rt):
    """NM-vs-REST byte-equal parity THROUGH the snapshot path, while a
    feed keeps folding (the snapshot pins both edges to one tick)."""
    from gyeeta_tpu.net import GytServer
    from gyeeta_tpu.net.webgw import WebGateway
    from gyeeta_tpu.sim.nodeweb import NodeWebSim

    srv = GytServer(rt, tick_interval=None)
    host, port = await srv.start()
    gw = WebGateway(host, port)
    gh, gp = await gw.start()

    async def rest_query(req: dict) -> bytes:
        reader, writer = await asyncio.open_connection(gh, gp)
        body = json.dumps(req).encode()
        writer.write(
            b"POST /query HTTP/1.1\r\nHost: t\r\nConnection: close\r\n"
            + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
        await writer.drain()
        raw = await reader.read(-1)
        writer.close()
        head, _, rbody = raw.partition(b"\r\n\r\n")
        assert b" 200 " in head.splitlines()[0], head
        return rbody

    nw = NodeWebSim()
    await nw.connect(host, port)
    got = {}
    for subsys in ("svcstate", "hoststate", "topk", "serverstatus"):
        # interleave live folds between the two edges: snapshot
        # isolation must keep them byte-equal anyway
        nm_obj = await nw.query_web(subsys, maxrecs=50)
        rt.feed(ParthaSim(n_hosts=8, n_svcs=3, seed=17).conn_frames(256))
        rest_raw = await rest_query({"subsys": subsys, "maxrecs": 50})
        got[subsys] = (json.dumps(nm_obj).encode(), rest_raw,
                       nm_obj.get("snaptick"))
    await nw.close()
    await gw.stop()
    await srv.stop()
    return got


def test_nm_rest_parity_through_snapshot():
    rt = Runtime(CFG)
    try:
        sim = ParthaSim(n_hosts=8, n_svcs=3, seed=17)
        _warm(rt, sim)
        got = asyncio.run(_parity_scenario(rt))
        for subsys, (nm_raw, rest_raw, snaptick) in got.items():
            assert nm_raw == rest_raw, f"{subsys}: bytes differ"
            assert snaptick == rt.snapshot.tick   # pinned to one tick
        # the snapshot tier actually served these (cache hits: the two
        # edges collapsed to one render per subsystem)
        assert rt.stats.counters.get("query_cache_hits", 0) >= 4
    finally:
        rt.close()


def test_metrics_scrape_touches_no_live_state():
    """/metrics through the snapshot path runs zero folds and zero
    health readbacks — scrapes cannot stall the fold."""
    rt = Runtime(CFG)
    try:
        sim = ParthaSim(n_hosts=8, n_svcs=3, seed=18)
        _warm(rt, sim)
        rt.feed(sim.conn_frames(64))      # staged, must stay staged
        d0 = _dispatches(rt)
        out = rt.query({"subsys": "metrics",
                        "consistency": "snapshot"})
        assert _dispatches(rt) == d0
        assert "gyt_snapshot_age_seconds" in out["text"]
        assert "gyt_snapshots_published_total" in out["text"]
    finally:
        rt.close()


# ------------------------------------------- render-ahead at a publish
HOSTQ = {"subsys": "hoststate", "sortcol": "hostid", "sortdesc": False}
ONCE = {"subsys": "svcstate", "maxrecs": 3}


def _ask(rt, req):
    return rt.query({**req, "consistency": "snapshot"})


@pytest.mark.parametrize("case", ["repeat", "one_off", "asked_first",
                                  "dropped", "cache_off", "bounded",
                                  "in_order"])
def test_prewarm_renders_ahead_what_the_last_snapshot_repeated(
        case, monkeypatch):
    """A request a snapshot answered from its cache is rendered into
    the next snapshot's cache by ``QueryExecutor.prewarm`` — same
    answer as a live ask, a hit for the first live ask; a request asked
    once is not; one nobody asks any more is rendered ahead once."""
    from gyeeta_tpu.net.qexec import QueryExecutor
    if case == "cache_off":
        monkeypatch.setenv("GYT_QUERY_CACHE_MAX", "0")
    rt = Runtime(CFG)
    qx = QueryExecutor(rt, workers=2)
    c = rt.stats.counters

    def tick():
        prev = rt.snapshot
        rt.feed(_feed_buf(sim, 64))
        rt.run_tick()
        n = qx.prewarm(prev)
        t0 = time.monotonic()
        while rt.snapshot.result_cache_len() < n:   # the workers' part
            assert time.monotonic() - t0 < 60.0
            time.sleep(0.005)
        return n

    try:
        sim = ParthaSim(n_hosts=8, n_svcs=3, seed=21)
        _warm(rt, sim)
        assert qx.prewarm(rt.snapshot) == 0          # nothing replaced
        _ask(rt, QUERY), _ask(rt, QUERY)             # a repeat
        _ask(rt, ONCE)                               # a one-off
        if case == "bounded":
            for k in range(40):
                q = {**ONCE, "maxrecs": 4 + k}
                _ask(rt, q), _ask(rt, q)
        if case == "in_order":
            for _ in range(3):
                _ask(rt, HOSTQ)             # repeated later than QUERY
            assert rt.snapshot.repeated() == [QUERY, HOSTQ]
            assert tick() == 2
            return
        assert rt.snapshot.repeated() == ([] if case == "cache_off" else
                                          [QUERY] + [{**ONCE, "maxrecs":
                                                      4 + k} for k in
                                                     range(15)]
                                          if case == "bounded" else [QUERY])
        if case == "asked_first":
            # a live ask got there before the workers: one render
            prev = rt.snapshot
            rt.feed(_feed_buf(sim, 64))
            rt.run_tick()
            m0 = c.get("query_cache_misses", 0)
            live = _ask(rt, QUERY)
            rt.snapshot.warm(QUERY)
            assert c.get("query_cache_misses", 0) == m0 + 1
            assert c.get("query_cache_prewarms", 0) == 0
            assert _ask(rt, QUERY) is live
            return
        n = tick()
        assert n == {"cache_off": 0, "bounded": 16}.get(case, 1)
        assert c.get("query_cache_prewarms", 0) == n
        if case == "cache_off":
            return
        m0, h0 = c.get("query_cache_misses", 0), c.get(
            "query_cache_hits", 0)
        if case == "one_off":
            _ask(rt, ONCE)
            assert c.get("query_cache_misses", 0) == m0 + 1
            return
        if case == "dropped":
            # nobody asked it on this snapshot: not carried further
            assert rt.snapshot.repeated() == []
            assert tick() == 0
            return
        out = _ask(rt, QUERY)
        assert (c.get("query_cache_misses", 0), c.get(
            "query_cache_hits", 0)) == (m0, h0 + 1)
        assert out["snaptick"] == rt.snapshot.tick
        # what a live render of the same snapshot gives, bit for bit
        fresh = rt.snapshot._render(dict(QUERY))
        assert json.dumps(out, default=str, sort_keys=True) == \
            json.dumps(fresh, default=str, sort_keys=True)
        # one ask keeps it riding: rendered ahead on the next one too
        assert rt.snapshot.repeated() == [QUERY]
    finally:
        qx.close()
        rt.close()


def test_tick_loop_prewarms_the_fresh_snapshot():
    """The serving edge's tick hands the fresh snapshot its
    predecessor's repeated requests: a client's first ask after the
    tick is a hit."""
    from gyeeta_tpu.net import GytServer
    from gyeeta_tpu.sim.nodeweb import NodeWebSim

    async def scenario(rt):
        srv = GytServer(rt, tick_interval=0.3)
        host, port = await srv.start()
        nw = NodeWebSim()
        await nw.connect(host, port)
        try:
            a = await nw.query_web("hoststate", maxrecs=50)
            await nw.query_web("hoststate", maxrecs=50)
            c = rt.stats.counters
            t0 = time.monotonic()
            while not c.get("query_cache_prewarms", 0):
                assert time.monotonic() - t0 < 30.0
                await asyncio.sleep(0.02)
            await asyncio.sleep(0.1)
            m0 = c.get("query_cache_misses", 0)
            b = await nw.query_web("hoststate", maxrecs=50)
            return a, b, c.get("query_cache_misses", 0) - m0
        finally:
            await nw.close()
            await srv.stop()

    rt = Runtime(CFG)
    try:
        _warm(rt, ParthaSim(n_hosts=8, n_svcs=3, seed=22))
        a, b, missed = asyncio.run(scenario(rt))
        assert b["snaptick"] > a["snaptick"]
        assert missed == 0
    finally:
        rt.close()
