"""Socket-level end-to-end: GytServer + NetAgent fleet + QueryClient.

The network edge's done-criterion (VERDICT r2 task 3): launch the server,
connect N agents over real TCP sockets, stream sweeps, run ticks, answer a
svcstate query over the wire. Mirrors the reference's agent bring-up
(``partha/gy_paconnhdlr.cc:1200`` blocking register → stream) and the
madhava recv loop (``server/gy_mconnhdlr.cc:2430-2520``) at miniature
scale.
"""

from __future__ import annotations

import asyncio

import pytest

from gyeeta_tpu import version
from gyeeta_tpu.engine.aggstate import EngineCfg
from gyeeta_tpu.ingest import wire
from gyeeta_tpu.net import GytServer, NetAgent, QueryClient
from gyeeta_tpu.net.agent import register
from gyeeta_tpu.runtime import Runtime
from gyeeta_tpu.sim.partha import ParthaSim
from waiting import counter, sweeps_fed, until


CFG = EngineCfg(n_hosts=8, svc_capacity=256, task_capacity=256,
                conn_batch=256, resp_batch=512, listener_batch=64,
                fold_k=2)


def run(coro):
    return asyncio.run(coro)


async def _fleet_session(n_agents: int, hostmap_path=None):
    rt = Runtime(CFG)
    srv = GytServer(rt, tick_interval=None, hostmap_path=hostmap_path)
    host, port = await srv.start()
    agents = [NetAgent(seed=i, n_svcs=2, n_groups=3)
              for i in range(n_agents)]
    hids = []
    for a in agents:
        hids.append(await a.connect(host, port))
    for i in range(3):
        for a in agents:
            await a.send_sweep(n_conn=128, n_resp=256)
        # the server reads the sockets on this loop: fold what it read
        await sweeps_fed(rt, (i + 1) * n_agents)
        rt.flush()
        rt.run_tick()
    qc = QueryClient()
    await qc.connect(host, port)
    out = await qc.query({"subsys": "svcstate",
                          "filter": "{ svcstate.qps5s >= 0 }"})
    host_out = await qc.query({"subsys": "hoststate"})
    await qc.close()
    for a in agents:
        await a.close()
    await srv.stop()
    return rt, hids, out, host_out


def test_fleet_over_sockets():
    rt, hids, out, host_out = run(_fleet_session(4))
    assert sorted(hids) == [0, 1, 2, 3]
    # each agent contributes n_svcs=2 listeners
    assert out["nrecs"] == 8
    by_host = {r["hostid"] for r in out["recs"]}
    assert by_host == {0, 1, 2, 3}
    # names travelled over the wire as NAME_INTERN announcements
    assert all(r["svcname"].startswith("svc-") for r in out["recs"])
    assert host_out["nrecs"] == 4
    assert rt.stats.snapshot()["agents_registered"] == 4


def test_sticky_host_id_on_reconnect(tmp_path):
    path = tmp_path / "hostmap.json"

    async def scenario():
        rt = Runtime(CFG)
        srv = GytServer(rt, tick_interval=None, hostmap_path=str(path))
        host, port = await srv.start()
        a = NetAgent(seed=7)
        hid1 = await a.connect(host, port)
        await a.close()
        # another agent claims the next slot in between
        b = NetAgent(seed=8)
        hid_b = await b.connect(host, port)
        await b.close()
        # same machine-id → same host_id
        a2 = NetAgent(machine_id=a.machine_id, seed=7)
        hid2 = await a2.connect(host, port)
        await a2.close()
        await srv.stop()

        # a restarted server reloads the persisted placement map
        rt3 = Runtime(CFG)
        srv3 = GytServer(rt3, tick_interval=None, hostmap_path=str(path))
        host3, port3 = await srv3.start()
        a3 = NetAgent(machine_id=a.machine_id, seed=7)
        hid3 = await a3.connect(host3, port3)
        await a3.close()
        await srv3.stop()
        return hid1, hid_b, hid2, hid3

    hid1, hid_b, hid2, hid3 = run(scenario())
    assert hid1 == hid2 == hid3
    assert hid_b != hid1


def test_version_gate_rejects_old_agent():
    async def scenario():
        rt = Runtime(CFG)
        srv = GytServer(rt, tick_interval=None)
        host, port = await srv.start()
        a = NetAgent(seed=1, wire_version=version.MIN_WIRE_VERSION - 1)
        with pytest.raises(ConnectionRefusedError):
            await a.connect(host, port)
        await srv.stop()

    run(scenario())


def test_capacity_rejection():
    async def scenario():
        cfg = CFG._replace(n_hosts=2)
        rt = Runtime(cfg)
        srv = GytServer(rt, tick_interval=None)
        host, port = await srv.start()
        a1, a2, a3 = (NetAgent(seed=i) for i in range(3))
        await a1.connect(host, port)
        await a2.connect(host, port)
        with pytest.raises(ConnectionRefusedError):
            await a3.connect(host, port)
        await a1.close()
        await a2.close()
        await srv.stop()

    run(scenario())


def test_query_conn_holds_no_host_slot():
    async def scenario():
        cfg = CFG._replace(n_hosts=1)
        rt = Runtime(cfg)
        srv = GytServer(rt, tick_interval=None)
        host, port = await srv.start()
        # query conns register without consuming agent capacity
        qc = QueryClient()
        await qc.connect(host, port)
        a = NetAgent(seed=0)
        hid = await a.connect(host, port)
        await qc.close()
        await a.close()
        await srv.stop()
        return hid

    assert run(scenario()) == 0


def test_malformed_first_frame_closes_conn():
    async def scenario():
        rt = Runtime(CFG)
        srv = GytServer(rt, tick_interval=None)
        host, port = await srv.start()
        reader, writer = await asyncio.open_connection(host, port)
        writer.write(b"GET / HTTP/1.1\r\n\r\n" + b"\0" * 64)
        await writer.drain()
        data = await reader.read(256)        # server closes without a resp
        writer.close()
        await srv.stop()
        return data

    assert run(scenario()) == b""


def test_event_frames_fold_into_engine():
    async def scenario():
        rt = Runtime(CFG)
        srv = GytServer(rt, tick_interval=None)
        host, port = await srv.start()
        a = NetAgent(seed=0, n_svcs=2)
        await a.connect(host, port)
        await a.send_sweep(n_conn=64, n_resp=128)
        await sweeps_fed(rt, 1)
        rt.flush()
        await a.close()
        await srv.stop()
        return rt

    rt = run(scenario())
    assert float(rt.state.n_conn) == 64.0
    assert float(rt.state.n_resp) == 128.0


# ------------------------------------------------------- the socket edge
# An event conn's bytes are received into a buffer the conn owns and
# deframed where they lie (net/server.py:_EventConn). What a test sends
# is small, so nothing dispatches: the record counters are the runtime's
# ``ingest_records`` count of what the edge fed it.
def _frames(seed: int = 5, n_conn: int = 1, n_resp: int = 2) -> bytes:
    """Three frames: ``n_conn`` conns, ``n_resp`` resps, one resp."""
    sim = ParthaSim(n_hosts=8, n_svcs=2, seed=seed)
    return (sim.conn_frames(n_conn) + sim.resp_frames(n_resp)
            + sim.resp_frames(1))


def _events(rt) -> tuple:
    return counter(rt, "conn_events"), counter(rt, "resp_events")


async def _edge(rt=None, **kw):
    rt = rt or Runtime(CFG)
    srv = GytServer(rt, tick_interval=None, **kw)
    host, port = await srv.start()
    return rt, srv, host, port


async def _event_conn(host, port, mid: int):
    reader, writer, status, _hid = await register(host, port, mid,
                                                  wire.CONN_EVENT)
    assert status == wire.REG_OK
    return reader, writer


async def _send(srv, writer, buf: bytes) -> None:
    """Write ``buf`` and wait until the edge has received and worked on
    all of it, so the next write is a read of its own."""
    want = counter(srv.rt, "edge_bytes") + len(buf)
    writer.write(buf)
    await writer.drain()
    await until(lambda: counter(srv.rt, "edge_bytes") >= want
                and not any(c._due for c in srv._event_conns),
                what="the edge to take the write in")


def test_edge_cut_at_every_offset_equals_one_write():
    buf = _frames()

    async def scenario():
        rt, srv, host, port = await _edge()
        _r, w = await _event_conn(host, port, 0xE001)
        await _send(srv, w, buf)
        whole = _events(rt)
        for cut in range(1, len(buf)):
            await _send(srv, w, buf[:cut])
            await _send(srv, w, buf[cut:])
        got = _events(rt)
        stats = rt.stats.snapshot()
        w.close()
        await srv.stop()
        return whole, got, stats

    whole, got, stats = run(scenario())
    assert whole == (1, 3)
    assert got == (len(buf), 3 * len(buf))      # every cut: the same
    assert stats.get("frames_bad", 0) == 0
    assert stats["edge_reads"] == 2 * len(buf) - 1
    assert stats["edge_bytes"] == len(buf) * len(buf)
    # only a partial frame behind complete ones is ever moved
    assert 0 < stats["edge_tail_bytes"] < stats["edge_bytes"]
    assert stats.get("edge_owned_copies", 0) == 0


@pytest.mark.parametrize("how", ["whole", "tail_later", "then_eof"])
def test_edge_register_and_first_frames_in_one_write(how):
    """Bytes behind REGISTER_REQ in the same segment are carried over at
    the switch off the stream API: none lost, none fed twice."""
    buf = _frames(n_conn=3, n_resp=5) * 4
    cut = len(buf) - 21 if how == "tail_later" else len(buf)

    async def scenario():
        rt, srv, host, port = await _edge()
        reader, writer = await asyncio.open_connection(host, port)
        writer.write(wire.encode_register_req(
            0xE002, wire.CONN_EVENT, version.CURR_WIRE_VERSION)
                     + buf[:cut])
        if how == "then_eof":
            writer.write_eof()
        await writer.drain()
        dtype, payload = await wire.read_frame(reader)
        assert dtype == wire.COMM_REGISTER_RESP
        assert wire.decode_register_resp(payload)[0] == wire.REG_OK
        if how == "tail_later":
            await until(lambda: _events(rt) == (12, 23))
            writer.write(buf[cut:])
            await writer.drain()
        await until(lambda: _events(rt) == (12, 24),
                    what="every frame behind the REGISTER_REQ")
        if how == "then_eof":
            assert await reader.read(16) == b""     # the server hung up
        writer.close()
        await srv.stop()
        return rt.stats.snapshot()

    stats = run(scenario())
    assert stats.get("frames_bad", 0) == 0
    assert stats.get("frames_rejected|reason=truncated", 0) == 0
    assert stats["edge_bytes"] == len(buf)


def test_edge_frame_larger_than_buffer_grows_it(monkeypatch):
    from gyeeta_tpu.net import server as S
    monkeypatch.setattr(S, "_READ_SZ", 256)
    buf = _frames(n_conn=9)             # a 2,184-byte frame leads

    async def scenario():
        rt, srv, host, port = await _edge()
        _r, w = await _event_conn(host, port, 0xE003)
        await _send(srv, w, buf)
        await until(lambda: _events(rt) == (9, 3))
        (conn,) = srv._event_conns
        size = len(conn._buf)
        await _send(srv, w, buf)         # and the grown buffer serves on
        await until(lambda: _events(rt) == (18, 6))
        w.close()
        await srv.stop()
        return size, rt.stats.snapshot()

    size, stats = run(scenario())
    assert size == 4096                 # 256 doubled until the frame fit
    assert stats.get("frames_bad", 0) == 0


def test_edge_two_conns_partial_frames_never_splice():
    a, b = _frames(seed=6, n_conn=2), _frames(seed=7, n_conn=3)

    async def scenario():
        rt, srv, host, port = await _edge()
        _ra, wa = await _event_conn(host, port, 0xE004)
        _rb, wb = await _event_conn(host, port, 0xE005)
        for i in range(1, 24):          # a's partial frame, then b's, ...
            ca, cb = 11 * i, len(b) - 7 * i
            await _send(srv, wa, a[:ca])
            await _send(srv, wb, b[:cb])
            await _send(srv, wa, a[ca:])
            await _send(srv, wb, b[cb:])
        got = _events(rt)
        stats = rt.stats.snapshot()
        wa.close()
        wb.close()
        await srv.stop()
        return got, stats

    got, stats = run(scenario())
    assert got == (23 * 5, 23 * 6)
    assert stats.get("frames_bad", 0) == 0
    assert stats.get("conns_framing_errors", 0) == 0


def test_edge_bad_magic_closes_that_conn_only_and_eof_in_frame_counts():
    buf = _frames()

    async def scenario():
        rt, srv, host, port = await _edge()
        ra, wa = await _event_conn(host, port, 0xE006)
        _rb, wb = await _event_conn(host, port, 0xE007)
        await _send(srv, wa, buf)
        wa.write(b"\xff" * 64)          # poison header mid-stream
        await wa.drain()
        assert await asyncio.wait_for(ra.read(16), 10.0) == b""
        wa.close()
        await _send(srv, wb, buf)        # the other conn is untouched
        await until(lambda: _events(rt)[0] == 2)
        bad = rt.stats.snapshot()
        await _send(srv, wb, buf[:-9])   # EOF inside a frame
        wb.close()
        await until(lambda: counter(
            rt, "frames_rejected|reason=truncated") == 1)
        await until(lambda: not srv._event_conns)
        await srv.stop()
        return bad, rt.stats.snapshot()

    bad, end = run(scenario())
    assert (bad["conn_events"], bad["resp_events"]) == (2, 6)
    assert bad["frames_bad"] == 1
    assert bad["conns_framing_errors"] == 1
    assert bad["frames_rejected|reason=bad_magic"] == 1
    assert bad.get("frames_rejected|reason=truncated", 0) == 0
    assert end["frames_bad"] == 1 and end["conns_framing_errors"] == 1
    assert (end["conn_events"], end["resp_events"]) == (3, 8)


def test_edge_idle_deadline_reaps_the_silent_conn_only():
    buf = _frames()

    async def scenario():
        rt, srv, host, port = await _edge(idle_timeout=0.4)
        rs, ws = await _event_conn(host, port, 0xE008)     # silent
        _rt, wt = await _event_conn(host, port, 0xE009)    # talks

        async def talk():
            while True:
                wt.write(buf)
                await wt.drain()
                await asyncio.sleep(0.05)

        talker = asyncio.create_task(talk())
        assert await asyncio.wait_for(rs.read(16), 10.0) == b""
        # one more whole deadline: the talking conn outlives it
        await asyncio.sleep(0.6)
        alive = len(srv._event_conns)
        talker.cancel()
        stats = rt.stats.snapshot()
        ws.close()
        wt.close()
        await srv.stop()
        return alive, stats

    alive, stats = run(scenario())
    assert alive == 1
    assert stats["conn_timeouts|kind=idle"] == 1
    assert stats["conn_events"] >= 10


def test_edge_feed_barrier_takes_in_a_booked_read():
    """A read is worked on a loop turn after it arrived; the barrier
    ahead of a tick or a strong query must not leave it out."""
    buf = _frames()

    async def scenario():
        rt, srv, host, port = await _edge()
        _r, w = await _event_conn(host, port, 0xE00C)
        await until(lambda: len(srv._event_conns) == 1)
        (conn,) = srv._event_conns
        conn.get_buffer(-1)[:len(buf)] = buf    # as the transport does
        conn.buffer_updated(len(buf))
        booked = _events(rt)
        srv._feed_barrier()                     # no turn of the loop
        got = _events(rt)
        w.close()
        await srv.stop()
        return booked, got

    assert run(scenario()) == ((0, 0), (1, 3))


@pytest.mark.parametrize("keeper", ["journal", "pipeline", "feeder",
                                    "recorder", "none"])
def test_edge_keepers_hold_owned_bytes(keeper, tmp_path):
    """Whoever keeps a run's bytes past the feed holds bytes of its own:
    unchanged after the conn's receive buffer is overwritten by the
    next read. With nobody keeping them, no copy is made."""
    first, second = _frames(seed=8), _frames(seed=9)
    assert len(first) == len(second) and first != second
    held = []

    def spy(obj, name):
        inner = getattr(obj, name)

        def wrapped(buf, *a, **kw):
            held.append(buf)
            return inner(buf, *a, **kw)
        setattr(obj, name, wrapped)

    async def scenario():
        kw, opts = {}, None
        if keeper == "journal":
            from gyeeta_tpu.utils.config import RuntimeOpts
            opts = RuntimeOpts(journal_dir=str(tmp_path / "wal"))
        elif keeper == "pipeline":
            kw["feed_pipeline"] = True
        elif keeper == "recorder":
            kw["record_path"] = str(tmp_path / "cap.gytrec")
        rt, srv, host, port = await _edge(Runtime(CFG, opts), **kw)
        if keeper == "journal":
            spy(rt.journal, "append")
        elif keeper == "pipeline":
            spy(srv._pipe, "feed")
        elif keeper == "feeder":
            # the mesh runtime's handoff, stood in for: it queues the
            # run for another task exactly as ShardFeeder.submit does
            class Feeder:
                def submit(self, buf, hid=0, conn_id=0):
                    return len(buf)

                def start(self): pass
                def flush_pending(self): pass
                async def stop(self): pass
            srv._feeder = Feeder()
            spy(srv._feeder, "submit")
        _r, w = await _event_conn(host, port, 0xE00A)
        await _send(srv, w, first)
        await _send(srv, w, second)      # lands where ``first`` lay
        srv._feed_barrier()
        got = _events(rt)
        w.close()
        await srv.stop()
        return got, rt.stats.snapshot()

    got, stats = run(scenario())
    copies = stats.get("edge_owned_copies", 0)
    if keeper == "recorder":
        # writes through before it returns, keeps nothing: no copy, and
        # the capture holds exactly the bytes sent, in order
        from gyeeta_tpu.utils.replay import read_chunks
        chunks = [c for _t, c in read_chunks(tmp_path / "cap.gytrec")]
        assert b"".join(chunks) == first + second
        assert copies == 0
    elif keeper == "none":
        assert copies == 0 and not held
    else:
        assert [type(b) for b in held] == [bytes, bytes]
        assert held == [first, second]
        assert copies == 2
    if keeper != "feeder":
        assert got == (2, 6)


def test_edge_reference_magic_conn_adapted_and_counted_once():
    from test_refproto import RP, _conn_record, _ref_frame
    frame = _ref_frame(RP.REF_NOTIFY_TCP_CONN, 4, b"".join(
        _conn_record(0x0DD0_5512, 7443, 500) for _ in range(4)))

    async def scenario():
        rt, srv, host, port = await _edge()
        _r, w = await _event_conn(host, port, 0xE00B)
        await _send(srv, w, frame[:50])      # a partial reference frame
        await _send(srv, w, frame[50:] + frame[:7])
        await _send(srv, w, frame[7:])
        await until(lambda: counter(rt, "conn_events") == 8)
        w.close()
        await srv.stop()
        return rt.stats.snapshot()

    stats = run(scenario())
    assert stats["conns_ref_adapted"] == 1
    assert stats["conn_events"] == 8
    assert stats.get("frames_bad", 0) == 0
    # the adapter keeps slices of what it is given: an owned copy a read
    assert stats["edge_owned_copies"] == 3
