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
from gyeeta_tpu.runtime import Runtime
from waiting import sweeps_fed


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
