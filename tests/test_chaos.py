"""Chaos tier: fault injection against the hardened serving edge.

Property tests (fast tier): each fault type in isolation — truncation,
corruption, stall (slow-loris), disconnect — must leave the server up,
close/reap the conn within the configured deadline, and bump the
matching labeled counter by exactly the injected count. Plus the
client-deadline satellites (connect/query timeouts), spool bounds, the
AGENT_STATS fold, and the checkpoint walk-back on a torn newest file.

The slow-tier e2e drives sim agents through the seeded
:class:`~gyeeta_tpu.sim.chaos.ChaosProxy` under a fault schedule that
includes one server kill + ``--restore-latest``-style restart, and
asserts convergence to a fault-free control run with zero silent loss
(ref recovery semantics: parmon respawn ``gypartha.cc:965``,
resend-inventory ``gy_socket_stat.h:1235``).
"""

from __future__ import annotations

import asyncio
import time

import numpy as np
import pytest

from gyeeta_tpu import version
from gyeeta_tpu.engine.aggstate import EngineCfg
from gyeeta_tpu.ingest import wire
from gyeeta_tpu.net import GytServer, NetAgent, QueryClient
from gyeeta_tpu.net.agent import register
from gyeeta_tpu.runtime import Runtime
from gyeeta_tpu.server_main import (latest_checkpoint,
                                    restore_latest_checkpoint)
from gyeeta_tpu.sim.chaos import ChaosProxy, FaultPlan
from gyeeta_tpu.sim.partha import ParthaSim
from gyeeta_tpu.utils import checkpoint as ckpt

CFG = EngineCfg(n_hosts=4, svc_capacity=64, task_capacity=128,
                conn_batch=64, resp_batch=64, listener_batch=32,
                fold_k=2)


@pytest.fixture(scope="module")
def rt():
    """One Runtime for every property test (compile once); tests
    measure counter DELTAS, never absolutes."""
    rt = Runtime(CFG)
    rt.run_tick()                 # pre-warm the tick path's compiles
    return rt


def c(rt, name: str) -> int:
    return int(rt.stats.counters.get(name, 0))


async def _until(pred, timeout: float = 8.0, dt: float = 0.02) -> bool:
    loop = asyncio.get_running_loop()
    end = loop.time() + timeout
    while loop.time() < end:
        if pred():
            return True
        await asyncio.sleep(dt)
    return pred()


# ---------------------------------------------------------- fault: stall
def test_slowloris_reaped_within_deadline(rt):
    """Valid magic, header never completed → reaped on the handshake
    deadline, counted with a kind label, tick loop unbothered."""
    async def scenario():
        srv = GytServer(rt, tick_interval=0.05, handshake_timeout=0.4)
        host, port = await srv.start()
        before = c(rt, "conn_timeouts|kind=handshake")
        tick0 = rt._tick_no
        reader, writer = await asyncio.open_connection(host, port)
        writer.write(wire.MAGIC_PM.to_bytes(4, "little"))   # then stall
        await writer.drain()
        t0 = time.monotonic()
        data = await asyncio.wait_for(reader.read(64), 5.0)
        reap_s = time.monotonic() - t0
        writer.close()
        # tick loop kept running while the loris hung
        await _until(lambda: rt._tick_no > tick0, timeout=3.0)
        ticks = rt._tick_no - tick0
        await srv.stop()
        return data, reap_s, before, ticks

    data, reap_s, before, ticks = asyncio.run(scenario())
    assert data == b""                      # server closed the conn
    assert reap_s < 2.0                     # within the deadline (+lag)
    assert c(rt, "conn_timeouts|kind=handshake") - before == 1
    assert ticks >= 1                       # tick loop never blocked
    # the counter renders in the exposition with its kind label
    from gyeeta_tpu.obs import prom
    assert 'gyt_conn_timeouts_total{kind="handshake"}' in \
        prom.render(rt.stats)


def test_idle_event_conn_reaped(rt):
    async def scenario():
        srv = GytServer(rt, tick_interval=None, idle_timeout=0.3)
        host, port = await srv.start()
        before = c(rt, "conn_timeouts|kind=idle")
        a = NetAgent(seed=201)
        await a.connect(host, port)         # registers, then silence
        ok = await _until(
            lambda: c(rt, "conn_timeouts|kind=idle") - before == 1,
            timeout=4.0)
        await a.close()
        await srv.stop()
        return ok, before

    ok, before = asyncio.run(scenario())
    assert ok
    assert c(rt, "conn_timeouts|kind=idle") - before == 1


# ----------------------------------------------------- fault: corruption
def test_corruption_counted_and_server_survives(rt):
    async def scenario():
        srv = GytServer(rt, tick_interval=None)
        host, port = await srv.start()
        before = c(rt, "frames_rejected|reason=bad_magic")
        reader, writer, status, hid = await register(
            host, port, 0xC0441, wire.CONN_EVENT)
        assert status == wire.REG_OK
        writer.write(b"\xff" * 64)          # corrupt header in-stream
        await writer.drain()
        data = await asyncio.wait_for(reader.read(64), 5.0)
        writer.close()
        # exactly ONE injected corruption → one labeled reject
        ok = await _until(
            lambda: c(rt, "frames_rejected|reason=bad_magic")
            - before == 1, timeout=4.0)
        # the server stays up: a fresh agent connects and sweeps
        a = NetAgent(seed=202, n_svcs=2, n_groups=3)
        await a.connect(host, port)
        await a.send_sweep(n_conn=16, n_resp=16)
        await asyncio.sleep(0.05)
        await a.close()
        await srv.stop()
        return data, ok, before

    data, ok, before = asyncio.run(scenario())
    assert data == b""                      # conn was closed
    assert ok
    assert c(rt, "frames_rejected|reason=bad_magic") - before == 1


# ----------------------------------------------------- fault: truncation
def test_truncation_counted(rt):
    async def scenario():
        srv = GytServer(rt, tick_interval=None)
        host, port = await srv.start()
        before = c(rt, "frames_rejected|reason=truncated")
        reader, writer, status, hid = await register(
            host, port, 0xC0442, wire.CONN_EVENT)
        assert status == wire.REG_OK
        sim = ParthaSim(n_hosts=1, n_svcs=2, seed=5, host_base=hid)
        frame = wire.encode_frame(wire.NOTIFY_TCP_CONN,
                                  sim.conn_records(16))
        writer.write(frame[:-10])           # tail truncated in flight
        await writer.drain()
        writer.close()                      # …then the conn dies
        ok = await _until(
            lambda: c(rt, "frames_rejected|reason=truncated")
            - before == 1, timeout=4.0)
        await srv.stop()
        return ok, before

    ok, before = asyncio.run(scenario())
    assert ok
    assert c(rt, "frames_rejected|reason=truncated") - before == 1


# ----------------------------------------------- fault: disconnect/reconn
def test_disconnect_then_reconnect_counted(rt):
    """Abrupt disconnects never kill the server; a re-registration of
    the same machine-id is counted as an agent reconnect."""
    async def scenario():
        srv = GytServer(rt, tick_interval=None)
        host, port = await srv.start()
        before = c(rt, "agent_reconnects")
        a = NetAgent(seed=203, n_svcs=2, n_groups=3)
        await a.connect(host, port)
        a._writer.transport.abort()         # mid-stream RST, no FIN
        a._writer = None
        await asyncio.sleep(0.05)
        hid1 = a.host_id
        hid2 = await a.connect(host, port)  # sticky id on reconnect
        await a.send_sweep(n_conn=16, n_resp=16)
        await asyncio.sleep(0.05)
        await a.close()
        await srv.stop()
        return hid1, hid2, before

    hid1, hid2, before = asyncio.run(scenario())
    assert hid1 == hid2
    assert c(rt, "agent_reconnects") - before == 1


# ------------------------------------------------------ error budget
def test_query_conn_error_budget(rt):
    async def scenario():
        srv = GytServer(rt, tick_interval=None, frame_error_budget=3)
        host, port = await srv.start()
        before = c(rt, "frames_rejected|reason=error_budget")
        reader, writer, status, _ = await register(
            host, port, 0xC0443, wire.CONN_QUERY)
        assert status == wire.REG_OK
        junk = wire.encode_trace_set([1], [1])   # valid frame, wrong type
        writer.write(junk * 4)              # budget 3 → 4th closes
        await writer.drain()
        data = await asyncio.wait_for(reader.read(64), 5.0)
        writer.close()
        await srv.stop()
        return data, before

    data, before = asyncio.run(scenario())
    assert data == b""
    assert c(rt, "frames_rejected|reason=error_budget") - before == 1


# ----------------------------------------------- client-side deadlines
def test_connect_deadlines_clear_error():
    async def scenario():
        # the handlers hold their conns until released, then close
        # them: since Python 3.12.1 Server.wait_closed waits for every
        # live conn, so a handler that just slept would hang the stop
        release = asyncio.Event()

        async def black_hole(reader, writer):
            try:
                await release.wait()
            finally:
                writer.close()

        srv = await asyncio.start_server(black_hole, "127.0.0.1", 0)
        host, port = srv.sockets[0].getsockname()[:2]
        a = NetAgent(seed=204, connect_timeout=0.2)
        with pytest.raises(ConnectionError, match="timed out"):
            await a.connect(host, port)
        qc = QueryClient(connect_timeout=0.2)
        with pytest.raises(ConnectionError, match="timed out"):
            await qc.connect(host, port)
        release.set()
        srv.close()
        await srv.wait_closed()
        return a, qc

    a, qc = asyncio.run(scenario())
    assert a.stats.counters["connect_timeouts"] == 1
    assert qc.stats.counters["connect_timeouts"] == 1


def test_query_deadline_clear_error():
    async def scenario():
        release = asyncio.Event()

        async def wedged(reader, writer):
            # answer registration, then swallow every query until the
            # test is over (see black_hole above for the close)
            try:
                await wire.read_frame(reader)
                writer.write(wire.encode_register_resp(
                    wire.REG_OK, 0xFFFFFFFF, version.CURR_WIRE_VERSION))
                await writer.drain()
                await release.wait()
            finally:
                writer.close()

        srv = await asyncio.start_server(wedged, "127.0.0.1", 0)
        host, port = srv.sockets[0].getsockname()[:2]
        qc = QueryClient()
        await qc.connect(host, port)
        with pytest.raises(TimeoutError, match="timed out"):
            await qc.query({"subsys": "hoststate"}, timeout=0.2)
        release.set()
        srv.close()
        await srv.wait_closed()
        return qc

    qc = asyncio.run(scenario())
    assert qc.stats.counters["query_timeouts"] == 1
    assert qc._writer is None               # desynced conn was reset


# ------------------------------------------------------------- spool
def test_spool_bounded_drop_oldest_counted():
    a = NetAgent(seed=205, spool_max_bytes=250)
    for i in range(5):
        a._spool_push(bytes([i]) * 100, 10)
    # 250-byte bound holds 2 full sweeps: 3 oldest dropped, counted
    assert a.spool_len() == 2
    assert a.stats.counters["spool_dropped"] == 3
    assert a.stats.counters["spool_dropped_records"] == 30
    # drop-OLDEST: the newest two survive
    assert [buf[0] for buf, _, _ in a._spool] == [3, 4]


def test_agent_stats_frame_folds_into_server_counters(rt):
    rec = np.zeros(1, wire.AGENT_STATS_DT)
    rec["host_id"] = 1
    rec["spool_dropped"] = 3
    rec["spool_dropped_records"] = 90
    rec["spool_resent"] = 2
    rec["connect_timeouts"] = 1
    before = {k: c(rt, k) for k in
              ("spool_dropped", "spool_dropped_records", "spool_resent",
               "agent_connect_timeouts")}
    rt.feed(wire.encode_frame(wire.NOTIFY_AGENT_STATS, rec))
    assert c(rt, "spool_dropped") - before["spool_dropped"] == 3
    assert c(rt, "spool_dropped_records") \
        - before["spool_dropped_records"] == 90
    assert c(rt, "spool_resent") - before["spool_resent"] == 2
    assert c(rt, "agent_connect_timeouts") \
        - before["agent_connect_timeouts"] == 1
    # and the fleet-wide loss counter reaches the exposition
    from gyeeta_tpu.obs import prom
    assert "gyt_spool_dropped_total" in prom.render(rt.stats)


# ----------------------------------------------- supervised reconnect
def test_supervised_reconnect_resends_spool(rt):
    """Server vanishes behind the proxy; the supervised agent never
    exits, keeps producing sweeps into the spool, reconnects with
    backoff, resends, and both ends count it."""
    async def scenario():
        srv = GytServer(rt, tick_interval=None)
        host, port = await srv.start()
        proxy = ChaosProxy(host, port)      # pass-through
        ph, pp = await proxy.start()
        before_reconn = c(rt, "agent_reconnects")
        before_resent = c(rt, "spool_resent")
        a = NetAgent(seed=206, n_svcs=2, n_groups=3,
                     connect_timeout=2.0)
        stop = asyncio.Event()
        task = asyncio.create_task(a.run_forever(
            ph, pp, interval=0.05, n_conn=16, n_resp=16,
            backoff_base=0.05, backoff_cap=0.2, stop=stop))
        assert await _until(
            lambda: a.stats.counters.get("sweeps_built", 0) >= 3)
        # ---- outage: proxy refuses + drops everything
        proxy.refusing = True
        proxy.drop_all()
        assert await _until(
            lambda: a.stats.counters.get("sweeps_spooled", 0) >= 2)
        assert not task.done()              # the supervisor never exits
        # ---- service restored
        proxy.refusing = False
        assert await _until(
            lambda: a.stats.counters.get("agent_reconnects", 0) >= 1
            and a.spool_len() == 0, timeout=10.0)
        # server saw the reconnect AND the agent's resend report
        assert await _until(
            lambda: c(rt, "agent_reconnects") - before_reconn >= 1)
        assert await _until(
            lambda: c(rt, "spool_resent") - before_resent >= 1)
        assert not task.done()
        stop.set()
        await asyncio.wait_for(task, 5.0)
        assert task.exception() is None
        await proxy.stop()
        await srv.stop()
        return a

    a = asyncio.run(scenario())
    assert a.stats.counters["spool_resent"] >= 1
    assert a.stats.counters.get("spool_dropped", 0) == 0


# ------------------------------------------------------- chaos proxy
def test_proxy_passthrough_resplit_intact():
    async def scenario():
        async def echo(reader, writer):
            try:
                while True:
                    d = await reader.read(1024)
                    if not d:
                        return
                    writer.write(d)
                    await writer.drain()
            finally:
                writer.close()

        srv = await asyncio.start_server(echo, "127.0.0.1", 0)
        host, port = srv.sockets[0].getsockname()[:2]
        proxy = ChaosProxy(host, port,
                           FaultPlan(seed=4, resplit=23))
        ph, pp = await proxy.start()
        reader, writer = await asyncio.open_connection(ph, pp)
        blob = bytes(range(256)) * 40       # 10KB
        writer.write(blob)
        await writer.drain()
        got = await asyncio.wait_for(reader.readexactly(len(blob)), 5.0)
        writer.close()
        await proxy.stop()
        srv.close()
        await srv.wait_closed()
        return blob, got

    blob, got = asyncio.run(scenario())
    assert got == blob                      # re-splitting never mutates


def test_fault_plan_deterministic():
    a = list(FaultPlan(seed=9, fault_kinds=("corrupt", "stall"),
                       mean_fault_bytes=4096).conn_faults(2, 16))
    b = list(FaultPlan(seed=9, fault_kinds=("corrupt", "stall"),
                       mean_fault_bytes=4096).conn_faults(2, 16))
    assert a == b and len(a) == 16
    # different conns / seeds draw different schedules
    assert a != list(FaultPlan(seed=9, fault_kinds=("corrupt", "stall"),
                               mean_fault_bytes=4096).conn_faults(3, 16))
    plan = FaultPlan(kill_windows=[(1.0, 2.0)])
    assert plan.in_kill_window(1.5) and not plan.in_kill_window(2.5)


# ------------------------------------------- WAN fault shapes (ISSUE 19)
async def _echo_server():
    async def echo(reader, writer):
        try:
            while True:
                d = await reader.read(1024)
                if not d:
                    return
                writer.write(d)
                await writer.drain()
        finally:
            writer.close()

    srv = await asyncio.start_server(echo, "127.0.0.1", 0)
    host, port = srv.sockets[0].getsockname()[:2]
    return srv, host, port


def test_asymmetric_latency_counted_per_direction():
    """latency_s2c_s delays ONLY the answer path: the ask path stays
    undelayed (counted per direction), and the round trip pays the
    s2c budget."""
    async def scenario():
        srv, host, port = await _echo_server()
        proxy = ChaosProxy(host, port,
                           FaultPlan(latency_s2c_s=0.15))
        ph, pp = await proxy.start()
        reader, writer = await asyncio.open_connection(ph, pp)
        t0 = time.monotonic()
        writer.write(b"ping")
        await writer.drain()
        got = await asyncio.wait_for(reader.readexactly(4), 5.0)
        rtt = time.monotonic() - t0
        writer.close()
        stats = dict(proxy.stats)
        await proxy.stop()
        srv.close()
        await srv.wait_closed()
        return got, rtt, stats

    got, rtt, stats = asyncio.run(scenario())
    assert got == b"ping"
    assert rtt >= 0.15                      # the answer path paid
    # exact per-direction accounting: one delayed s2c chunk, zero c2s
    assert stats["delayed_chunks_s2c"] == 1
    assert stats.get("delayed_chunks_c2s", 0) == 0
    # the plan resolves per-direction overrides against the symmetric
    # default
    plan = FaultPlan(latency_s=0.2, latency_c2s_s=0.05)
    assert plan.latency_for("c2s") == 0.05
    assert plan.latency_for("s2c") == 0.2


def test_partition_drops_bytes_conns_held():
    """A partition LOSES the bytes (counted exactly) while every conn
    stays open; after heal the same conn carries traffic again."""
    async def scenario():
        srv, host, port = await _echo_server()
        proxy = ChaosProxy(host, port)
        ph, pp = await proxy.start()
        reader, writer = await asyncio.open_connection(ph, pp)
        # prove the path first
        writer.write(b"pre")
        await writer.drain()
        assert await asyncio.wait_for(reader.readexactly(3), 5.0) \
            == b"pre"
        proxy.partitioned = True
        lost = b"x" * 1000
        writer.write(lost)
        await writer.drain()
        assert await _until(
            lambda: proxy.stats.get("partition_dropped_bytes", 0)
            >= len(lost))
        # the conn is HELD: no EOF arrived while partitioned
        with pytest.raises((asyncio.TimeoutError, TimeoutError)):
            await asyncio.wait_for(reader.read(1), 0.3)
        proxy.partitioned = False
        writer.write(b"post")
        await writer.drain()
        got = await asyncio.wait_for(reader.readexactly(4), 5.0)
        writer.close()
        stats = dict(proxy.stats)
        await proxy.stop()
        srv.close()
        await srv.wait_closed()
        return got, stats, len(lost)

    got, stats, nlost = asyncio.run(scenario())
    assert got == b"post"                   # healed, same conn
    # exact loss accounting: the lost blob, whole, nothing else
    assert stats["partition_dropped_bytes"] == nlost
    assert stats["partition_dropped_chunks"] == 1


def test_partition_window_schedule():
    plan = FaultPlan(partition_windows=[(0.5, 1.0), (2.0, 2.5)])
    assert not plan.in_partition_window(0.49)
    assert plan.in_partition_window(0.5)    # closed start edge
    assert not plan.in_partition_window(1.0)  # open end edge
    assert plan.in_partition_window(2.25)

    async def scenario():
        srv, host, port = await _echo_server()
        proxy = ChaosProxy(host, port,
                           FaultPlan(partition_windows=[(0.0, 0.3)]))
        await proxy.start()
        assert await _until(lambda: proxy.partitioned, timeout=2.0)
        assert await _until(lambda: not proxy.partitioned, timeout=2.0)
        spans = proxy.stats["partition_spans"]
        await proxy.stop()
        srv.close()
        await srv.wait_closed()
        return spans

    assert asyncio.run(scenario()) == 1     # one span, counted once


def test_region_kill_scheduling():
    from gyeeta_tpu.sim.chaos import RegionKill
    with pytest.raises(ValueError):
        RegionKill([(1.0, 1.0)])
    rk = RegionKill([(1.0, 2.0), (3.0, 4.0)])
    assert not rk.in_window(0.99) and rk.in_window(1.0)
    assert not rk.in_window(2.0) and rk.in_window(3.5)
    assert rk.end == 4.0

    async def scenario():
        events = []

        def kill():
            events.append("kill")

        async def restart():
            events.append("restart")

        rk = RegionKill([(0.05, 0.15), (0.25, 0.35)],
                        kill_cb=kill, restart_cb=restart,
                        poll_s=0.01)
        await asyncio.wait_for(rk.run(), 5.0)
        return events, dict(rk.stats)

    events, stats = asyncio.run(scenario())
    # each window fires kill exactly once at open, restart once at
    # close, in order — the campaign's exact accounting
    assert events == ["kill", "restart", "kill", "restart"]
    assert stats["region_kills"] == 2
    assert stats["region_restarts"] == 2


# ------------------------------------------------- checkpoint walk-back
def test_torn_newest_checkpoint_walks_back(rt, tmp_path):
    """A truncated newest .npz (crash mid-write without the fsync
    discipline) must not crash-loop the respawn path: the walk-back
    lands on the next-older good checkpoint."""
    good = tmp_path / "gyt_tick_00000010.npz"
    torn = tmp_path / "gyt_tick_00000020.npz"
    ckpt.save(str(good), CFG, rt.state, extra={"tick": 10})
    ckpt.save(str(torn), CFG, rt.state, extra={"tick": 20})
    torn.write_bytes(torn.read_bytes()[:120])     # tear it
    import os
    now = time.time()
    os.utime(good, (now - 60, now - 60))          # good is OLDER
    os.utime(torn, (now, now))
    assert latest_checkpoint(str(tmp_path)) == str(torn)
    restored = restore_latest_checkpoint(rt, str(tmp_path))
    assert restored == str(good)
    # no stray .tmp staging file survives a successful save
    assert not list(tmp_path.glob("*.tmp.npz"))


# ------------------------------------------------------------ e2e (slow)
@pytest.mark.slow
def test_chaos_e2e_server_kill_converges(tmp_path):
    """The whole robustness story: sim agents stream through the seeded
    chaos proxy (corruption + disconnects + re-splitting), the server
    dies mid-run and a replacement restores the latest usable
    checkpoint (walking past a torn newer one); the fleet view
    converges to a fault-free control run, the agents never exit, and
    every lost record is accounted for by the drop/reject counters."""
    control, chaos_out, agents, acct = asyncio.run(_e2e(tmp_path))

    c_svc, c_hosts = control
    x_svc, x_hosts = chaos_out
    # ---- convergence: same services, same hosts, resolved names, Up
    assert {r["svcid"] for r in x_svc["recs"]} \
        == {r["svcid"] for r in c_svc["recs"]}
    assert all(r["svcname"].startswith("svc-") for r in x_svc["recs"])
    assert x_hosts["nrecs"] == c_hosts["nrecs"] == 2
    assert all(r["state"] != "Down" for r in x_hosts["recs"])
    # ---- zero silent loss: everything built is either accepted by a
    # server epoch, still buffered, or counted as dropped/skipped
    built, dropped, remaining, accepted = acct
    assert built > 0
    assert accepted >= built - dropped - remaining, acct
    # ---- the run actually exercised the faults + the spool
    for a in agents:
        assert a.stats.counters["agent_reconnects"] >= 1
        assert a.stats.counters["spool_dropped"] >= 1


def _prewarm(rt, tmp_path, tag: str) -> None:
    """Trace/compile every fold program BEFORE the timed phases: jit
    tracing blocks the shared asyncio loop for seconds per program,
    which would stall the supervisors' timers mid-scenario. State is
    snapshotted and restored, so the warmup leaves no records behind
    (host-side registries are not fed — device slabs only).

    Durability-NEUTRAL: warmup records must not reach the write-ahead
    journal (``_journal_replaying`` suppresses appends) and the warmup
    tick must not write a checkpoint into the scenario's checkpoint
    dir — a prewarm checkpoint would otherwise record a WAL position
    PAST the crash window and recovery would replay nothing.

    Counter-NEUTRAL: the SIGKILL e2e accounts every built record
    against the accepted-kind counters across both server epochs, so
    the warmup feed must not inflate them — counters are snapshotted
    with the state and restored after."""
    snap = tmp_path / f"warm_{tag}.npz"
    ckpt.save(str(snap), CFG, rt.state)
    base_counters = dict(rt.stats.counters)
    sim = ParthaSim(n_hosts=4, n_svcs=2, n_groups=3, seed=77)
    old_opts = rt.opts
    rt.opts = old_opts._replace(checkpoint_dir=None)
    rt._journal_replaying = True
    try:
        rt.feed(sim.conn_frames(256) + sim.resp_frames(256)
                + sim.listener_frames() + sim.task_frames()
                + wire.encode_frame(wire.NOTIFY_HOST_STATE,
                                    sim.host_state_records())
                + wire.encode_frame(wire.NOTIFY_CPU_MEM_STATE,
                                    sim.cpu_mem_records()))
        rt.flush()
        rt.run_tick()
        rt.restore(str(snap))
    finally:
        rt._journal_replaying = False
        rt.opts = old_opts
        rt.stats.counters.clear()
        rt.stats.counters.update(base_counters)
    snap.unlink()


async def _e2e(tmp_path):
    hostmap = str(tmp_path / "hostmap.json")
    ckdir = tmp_path / "ck"
    ckdir.mkdir()

    # ---------------- control run: no proxy, no faults
    rt_c = Runtime(CFG)
    _prewarm(rt_c, tmp_path, "c")
    srv_c = GytServer(rt_c, tick_interval=None)
    host, port = await srv_c.start()
    ctl_agents = [NetAgent(seed=100 + i, n_svcs=2, n_groups=3)
                  for i in range(2)]
    for a in ctl_agents:
        await a.connect(host, port)
    for _ in range(6):
        for a in ctl_agents:
            await a.send_sweep(n_conn=32, n_resp=32)
        await asyncio.sleep(0.05)
        rt_c.flush()
        rt_c.run_tick()
    c_svc = rt_c.query({"subsys": "svcstate", "sortcol": "svcid"})
    c_hosts = rt_c.query({"subsys": "hoststate"})
    for a in ctl_agents:
        await a.close()
    await srv_c.stop()

    # ---------------- chaos run: proxy + faults + server kill/restore
    rt1 = Runtime(CFG)
    _prewarm(rt1, tmp_path, "1")
    srv1 = GytServer(rt1, tick_interval=None, hostmap_path=hostmap)
    h1, p1 = await srv1.start()
    plan = FaultPlan(seed=11, fault_kinds=("corrupt", "disconnect"),
                     mean_fault_bytes=96 * 1024, resplit=4096)
    proxy = ChaosProxy(h1, p1, plan)
    ph, pp = await proxy.start()
    agents = [NetAgent(seed=100 + i, n_svcs=2, n_groups=3,
                       spool_max_bytes=24 * 1024, connect_timeout=2.0,
                       resend_last=4)
              for i in range(2)]
    stop = asyncio.Event()
    tasks = [asyncio.create_task(a.run_forever(
        ph, pp, interval=0.05, n_conn=32, n_resp=32,
        backoff_base=0.05, backoff_cap=0.2, stop=stop))
        for a in agents]
    assert await _until(lambda: all(
        a.stats.counters.get("sweeps_built", 0) >= 6 for a in agents),
        timeout=20.0)
    rt1.flush()
    rt1.run_tick()

    # periodic checkpoint… then the server dies mid-run
    good = ckdir / f"gyt_tick_{rt1._tick_no:08d}.npz"
    ckpt.save(str(good), CFG, rt1.state, extra={"tick": rt1._tick_no})
    proxy.refusing = True
    proxy.drop_all()
    await srv1.stop()

    # outage: agents keep producing into the bounded spool until it
    # overflows (drop-oldest, counted) — supervisors never exit
    assert await _until(lambda: all(
        a.stats.counters.get("spool_dropped", 0) >= 1 for a in agents),
        timeout=20.0)
    assert all(not t.done() for t in tasks)

    # a torn NEWER checkpoint on disk: restore-latest must walk past it
    torn = ckdir / f"gyt_tick_{rt1._tick_no + 1:08d}.npz"
    torn.write_bytes(good.read_bytes()[:64])
    rt2 = Runtime(CFG)
    _prewarm(rt2, tmp_path, "2")
    assert restore_latest_checkpoint(rt2, str(ckdir)) == str(good)
    srv2 = GytServer(rt2, tick_interval=None, hostmap_path=hostmap)
    h2, p2 = await srv2.start()
    proxy.upstream = (h2, p2)
    proxy.refusing = False

    # reconnect: sticky ids, inventory re-announce, spool resend
    assert await _until(lambda: all(
        a.stats.counters.get("agent_reconnects", 0) >= 1
        and a.spool_len() == 0 for a in agents), timeout=25.0)
    floor = {a.seed: a.stats.counters.get("sweeps_built", 0)
             for a in agents}
    await _until(lambda: all(
        a.stats.counters.get("sweeps_built", 0) >= floor[a.seed] + 4
        for a in agents), timeout=20.0)
    assert all(not t.done() for t in tasks)   # never exited
    stop.set()
    await asyncio.wait_for(asyncio.gather(*tasks), 10.0)

    await asyncio.sleep(0.1)                  # let event loops drain
    rt2.flush()
    rt2.run_tick()
    x_svc = rt2.query({"subsys": "svcstate", "sortcol": "svcid"})
    x_hosts = rt2.query({"subsys": "hoststate"})

    # ---- loss accounting across BOTH server epochs
    built = sum(a.stats.counters.get("records_built", 0)
                for a in agents)
    dropped = sum(a.stats.counters.get("spool_dropped_records", 0)
                  for a in agents)
    remaining = sum(a.spool_records() for a in agents)
    # "accepted" includes records lost to COUNTED causes: skipped
    # unknown-subtype frames (corrupted subtype byte) are attributed
    # loss, not silent loss
    kinds = ("conn_events", "resp_events", "listener_records",
             "host_records", "task_records", "cpumem_records",
             "cgroup_records", "task_pings", "records_unknown_subtype")
    accepted = sum(int(r.stats.counters.get(k, 0))
                   for r in (rt1, rt2) for k in kinds)
    # the proxy really injected faults (ground truth for the schedule)
    assert (proxy.stats["corrupt"] + proxy.stats["disconnect"]) >= 1

    await proxy.stop()
    await srv2.stop()
    return ((c_svc, c_hosts), (x_svc, x_hosts), agents,
            (built, dropped, remaining, accepted))


# --------------------------------------------- SIGKILL + WAL e2e (slow)
# PR-4 proved CONVERGENCE after a kill (fresh sweeps rebuild the view);
# the inter-checkpoint window itself was lost. The WAL closes that gap:
# a kill mid-window + --restore-latest must yield a fleet view
# IDENTICAL to the fault-free control run, with every record accounted
# exactly once (checkpoint + journal replay + seq-pruned agent resend).

_ACCEPT_KINDS = ("conn_events", "resp_events", "listener_records",
                 "host_records", "task_records", "cpumem_records",
                 "cgroup_records", "task_pings", "sweep_marks",
                 "records_unknown_subtype")


def _accepted(rt) -> int:
    return sum(int(rt.stats.counters.get(k, 0)) for k in _ACCEPT_KINDS)


def _views(rt):
    """Canonical fleet view: svcstate + hoststate rows, key-sorted —
    the byte-identity surface (row order inside a window is the only
    legal divergence between the runs, so sort by the entity key)."""
    import json as _json
    svc = rt.query({"subsys": "svcstate", "sortcol": "svcid",
                    "maxrecs": 64})
    hosts = rt.query({"subsys": "hoststate", "maxrecs": 16})
    return (_json.dumps(sorted(svc["recs"],
                               key=lambda r: r["svcid"]),
                        sort_keys=True),
            _json.dumps(sorted(hosts["recs"],
                               key=lambda r: r["hostid"]),
                        sort_keys=True))


async def _send_counted(a, n_conn=32, n_resp=32) -> int:
    buf = a.build_sweep(n_conn, n_resp)
    a._writer.write(buf)
    await a._writer.drain()
    return wire.count_events(buf)


async def _sigkill_e2e(tmp_path):
    from gyeeta_tpu.utils.config import RuntimeOpts

    # ---------------- control: no journal, no kill — the ground truth
    rt_c = Runtime(CFG)
    _prewarm(rt_c, tmp_path, "kc")
    srv_c = GytServer(rt_c, tick_interval=None)
    host, port = await srv_c.start()
    ctl = [NetAgent(seed=300 + i, n_svcs=2, n_groups=3)
           for i in range(2)]
    built_c = 0
    for a in ctl:
        await a.connect(host, port)
    for _ in range(3):                              # window 1
        for a in ctl:
            built_c += await _send_counted(a)
    await asyncio.sleep(0.15)
    rt_c.flush()
    rt_c.run_tick()
    for _ in range(3):                              # window 2
        for a in ctl:
            built_c += await _send_counted(a)
    await asyncio.sleep(0.15)
    rt_c.flush()
    rt_c.run_tick()
    c_views = _views(rt_c)
    for a in ctl:
        await a.close()
    await srv_c.stop()

    # ---------------- chaos: journal on, SIGKILL mid-window 2
    hostmap = str(tmp_path / "khostmap.json")
    ckdir = tmp_path / "kck"
    wal = tmp_path / "kwal"
    opts = RuntimeOpts(journal_dir=str(wal), checkpoint_dir=str(ckdir),
                       checkpoint_every_ticks=1)
    rt1 = Runtime(CFG, opts)
    _prewarm(rt1, tmp_path, "k1")
    srv1 = GytServer(rt1, tick_interval=None, hostmap_path=hostmap)
    h1, p1 = await srv1.start()
    agents = [NetAgent(seed=300 + i, n_svcs=2, n_groups=3)
              for i in range(2)]
    built = 0
    for a in agents:
        await a.connect(h1, p1)
    for _ in range(3):                              # window 1
        for a in agents:
            built += await _send_counted(a)
    await asyncio.sleep(0.15)
    rt1.flush()
    rt1.run_tick()          # checkpoint @ tick 1: hwm=3, WAL truncated
    assert rt1._sweep_last_seq == {0: 3, 1: 3}
    # window 2 opens: two more sweeps per agent reach the server…
    for _ in range(2):
        for a in agents:
            built += await _send_counted(a)
    await asyncio.sleep(0.15)
    # …and are DURABLE only in the journal (mid-inter-checkpoint kill:
    # no graceful drain, no final checkpoint, no truncation)
    rt1_accepted = _accepted(rt1)
    await srv1.stop()
    for a in agents:
        a._drop_conn()
    # the 6th sweep is produced during the outage → the PR-4 spool
    for a in agents:
        buf = a.build_sweep(32, 32)
        built += wire.count_events(buf)
        a._spool_push(buf, wire.count_events(buf), a._sweep_seq)

    # ---------------- respawn: restore + WAL replay + pruned resend
    rt2 = Runtime(CFG, opts)
    _prewarm(rt2, tmp_path, "k2")
    assert restore_latest_checkpoint(rt2, str(ckdir)) is not None
    replayed = int(rt2.stats.counters.get("wal_replayed_records", 0))
    assert rt2.stats.counters["wal_replayed_chunks"] > 0
    # the replay advanced the dedup high-water mark past the window
    assert rt2._sweep_last_seq == {0: 5, 1: 5}
    srv2 = GytServer(rt2, tick_interval=None, hostmap_path=hostmap)
    h2, p2 = await srv2.start()
    for a in agents:
        hid = a.host_id
        assert await a.connect(h2, p2) == hid       # sticky placement
        # REGISTER_RESP pruned nothing (sweep 6 postdates the mark)
        assert a.spool_len() == 1
        await a._resend_spool()
        assert a.spool_len() == 0
    await asyncio.sleep(0.15)
    rt2.flush()
    rt2.run_tick()                                  # window 2 closes
    x_views = _views(rt2)
    rt2_accepted = _accepted(rt2)

    for a in agents:
        await a.close()
    await srv2.stop()
    return (c_views, x_views, built, built_c,
            rt1_accepted, rt2_accepted, replayed, rt2)


@pytest.mark.slow
def test_chaos_e2e_sigkill_wal_byte_identical(tmp_path,
                                              no_xla_disk_cache):
    (c_views, x_views, built, built_c, rt1_acc, rt2_acc, replayed,
     rt2) = asyncio.run(_sigkill_e2e(tmp_path))
    # the two runs really built the same stream
    assert built == built_c
    # ---- byte-identical fleet view vs the fault-free control
    assert x_views[0] == c_views[0]                 # svcstate
    assert x_views[1] == c_views[1]                 # hoststate
    # ---- exactly-once accounting: every record the agents built is
    # accepted by exactly one epoch-fold (replayed records were
    # accepted twice — once live in epoch 1, once by the replay — and
    # nothing else overlaps; the seq-pruned resend contributes the
    # crash-window spool exactly once)
    assert replayed > 0
    assert built == rt1_acc + rt2_acc - replayed, \
        (built, rt1_acc, rt2_acc, replayed)
    # the dedup mark tracked the full stream
    assert rt2._sweep_last_seq == {0: 6, 1: 6}


@pytest.mark.slow
def test_sharded_sigkill_wal_replay(tmp_path, no_xla_disk_cache):
    """The same durability contract on the mesh tier: per-shard state
    restores from the stacked checkpoint and the WAL replays through
    the sharded ingest routing — the final cluster view is byte-equal
    to a fault-free control run."""
    import json as _json

    from gyeeta_tpu.parallel.shardedrt import ShardedRuntime
    from gyeeta_tpu.utils.config import RuntimeOpts

    SCFG = EngineCfg(n_hosts=8, svc_capacity=64, task_capacity=64,
                     conn_batch=32, resp_batch=32, listener_batch=16,
                     fold_k=2)
    sim = ParthaSim(n_hosts=4, n_svcs=2, n_groups=3, seed=21)
    feeds = [sim.conn_frames(64) + sim.resp_frames(64)
             + sim.listener_frames() + sim.task_frames()
             + wire.encode_frame(wire.NOTIFY_HOST_STATE,
                                 sim.host_state_records())
             for _ in range(3)]

    def view(rt):
        out = rt.query({"subsys": "svcstate", "sortcol": "svcid",
                        "maxrecs": 64})
        return (_json.dumps(out["recs"], sort_keys=True),
                rt.rollup_stats())

    # control: fault-free, same feeds, same tick boundaries
    ctl = ShardedRuntime(SCFG)
    ctl.feed(feeds[0], hid=0, conn_id=1)
    ctl.flush()
    ctl.run_tick()
    ctl.feed(feeds[1], hid=1, conn_id=1)
    ctl.feed(feeds[2], hid=2, conn_id=2)
    ctl.flush()
    ctl.run_tick()
    want = view(ctl)

    # chaos: checkpoint after window 1, SIGKILL mid-window 2
    opts = RuntimeOpts(journal_dir=str(tmp_path / "swal"),
                       checkpoint_dir=str(tmp_path / "sck"),
                       checkpoint_every_ticks=1)
    rt1 = ShardedRuntime(SCFG, opts=opts)
    rt1.feed(feeds[0], hid=0, conn_id=1)
    rt1.flush()
    rep = rt1.run_tick()
    assert "checkpoint" in rep
    rt1.feed(feeds[1], hid=1, conn_id=1)
    rt1.feed(feeds[2], hid=2, conn_id=2)
    rt1.journal.fsync()          # the group-fsync cadence's job live
    # …no flush, no tick, no close: the process is gone

    rt2 = ShardedRuntime(SCFG, opts=opts)
    assert restore_latest_checkpoint(rt2, str(tmp_path / "sck")) \
        is not None
    assert rt2.stats.counters["wal_replayed_chunks"] == 2
    rt2.flush()
    rt2.run_tick()
    got = view(rt2)
    assert got[0] == want[0]
    assert got[1] == want[1]
