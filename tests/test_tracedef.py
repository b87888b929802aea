"""On-demand trace control: tracedef CRUD → TRACE_SET push → capture."""

import asyncio

import numpy as np

from gyeeta_tpu.engine.aggstate import EngineCfg
from gyeeta_tpu.ingest import wire
from gyeeta_tpu.net.agent import NetAgent, QueryClient
from gyeeta_tpu.net.server import GytServer
from gyeeta_tpu.runtime import Runtime
from gyeeta_tpu.sim.partha import ParthaSim
from gyeeta_tpu.trace.defs import TraceDef, TraceDefs
from waiting import counter, sweeps_fed, until

CFG = EngineCfg(n_hosts=8, svc_capacity=64, conn_batch=64, resp_batch=64,
                api_capacity=512, fold_k=2)


# ---------------------------------------------------------------- registry
def test_tracedef_diffing():
    td = TraceDefs(clock=lambda: 1000.0)
    td.add({"name": "all"})
    targets = {1: {10, 11}, 2: {20}}
    d = td.diff_for_hosts(targets)
    assert d == {1: ([10, 11], []), 2: ([20], [])}
    # no change → no diff
    assert td.diff_for_hosts(targets) == {}
    # shrink → disables
    d = td.diff_for_hosts({1: {10}})
    assert d == {1: ([], [11]), 2: ([], [20])}
    # unreachable host: diff not consumed
    td2 = TraceDefs(clock=lambda: 1000.0)
    d = td2.diff_for_hosts({5: {1}}, hosts=[])
    assert d == {}
    d = td2.diff_for_hosts({5: {1}}, hosts=[5])
    assert d == {5: ([1], [])}
    # expiry
    clock_t = [1000.0]
    td3 = TraceDefs(clock=lambda: clock_t[0])
    td3.add({"name": "tmp", "tend": 2000.0})
    assert td3._active_defs()
    clock_t[0] = 3000.0
    assert not td3._active_defs()


def test_tracedef_crud_and_targets():
    rt = Runtime(CFG)
    sim = ParthaSim(n_hosts=8, n_svcs=2, seed=4)
    rt.feed(sim.name_frames())
    rt.feed(wire.encode_frame(wire.NOTIFY_LISTENER_INFO,
                              sim.listener_info_records()))
    out = rt.query({"op": "add", "objtype": "tracedef", "name": "t1",
                    "filter": "{ svcinfo.hostid < 2 }"})
    assert out["ok"]
    q = rt.query({"subsys": "tracedef"})
    assert q["nrecs"] == 1 and q["recs"][0]["active"]
    diffs = rt.trace_control_diff(hosts=range(8))
    # hosts 0 and 1 each get their 2 services enabled
    assert set(diffs) == {0, 1}
    assert all(len(en) == 2 and not dis for en, dis in diffs.values())
    out = rt.query({"op": "delete", "objtype": "tracedef", "name": "t1"})
    assert out["ok"]
    diffs = rt.trace_control_diff(hosts=range(8))
    assert all(not en and len(dis) == 2 for en, dis in diffs.values())


def test_alert_crud_over_query_channel():
    rt = Runtime(CFG)
    out = rt.query({"op": "add", "objtype": "alertdef",
                    "alertname": "a1", "subsys": "hoststate",
                    "filter": "{ hoststate.state >= 4 }"})
    assert out["ok"]
    assert rt.query({"subsys": "alertdef"})["nrecs"] == 1
    out = rt.query({"op": "add", "objtype": "silence", "name": "s1",
                    "alertnames": ["a1"]})
    assert out["ok"]
    assert rt.query({"subsys": "silences"})["nrecs"] == 1
    assert rt.query({"op": "delete", "objtype": "alertdef",
                     "name": "a1"})["ok"]
    assert rt.query({"subsys": "alertdef"})["nrecs"] == 0
    # notifymsg recorded the config changes
    msgs = rt.query({"subsys": "notifymsg",
                     "filter": "{ notifymsg.source = 'config' }"})
    assert msgs["nrecs"] == 3


# -------------------------------------------------------------- end-to-end
def test_trace_control_end_to_end():
    """CRUD a tracedef → server pushes TRACE_SET → agent captures →
    per-API aggregates and traceuniq answer."""

    async def main():
        rt = Runtime(CFG)
        srv = GytServer(rt, tick_interval=3600)
        host, port = await srv.start()
        agents = [NetAgent(seed=i) for i in range(2)]
        for a in agents:
            await a.connect(host, port)
            await a.send_sweep(n_conn=64, n_resp=64)
        await sweeps_fed(rt, 2)
        qc = QueryClient()
        await qc.connect(host, port)

        # before any tracedef: no capture anywhere
        assert not agents[0].trace_enabled
        q = await qc.query({"subsys": "tracereq"})
        assert q["nrecs"] == 0

        out = await qc.query({"op": "add", "objtype": "tracedef",
                              "name": "all-svcs"})
        assert out["ok"]
        rt.run_tick()
        await srv.push_trace_control()
        # agents received enablement for their services
        await until(lambda: all(len(a.trace_enabled) == a.n_svcs
                                for a in agents), what="TRACE_SET enable")

        for a in agents:
            await a.send_sweep(n_conn=64, n_resp=256)
        await sweeps_fed(rt, 4)
        assert counter(rt, "trace_records") == 2 * 256
        # queries over the wire read the published snapshot: what the
        # sweep folded is visible from the next tick on, as it is for a
        # dashboard (the server ticks every 5 s; here by hand)
        rt.run_tick()
        q = await qc.query({"subsys": "tracereq", "maxrecs": 100})
        assert q["nrecs"] > 0
        st = await qc.query({"subsys": "tracestatus"})
        assert st["recs"][0]["nsvc"] == sum(a.n_svcs for a in agents)
        uq = await qc.query({"subsys": "traceuniq", "maxrecs": 50})
        assert uq["nrecs"] > 0
        assert all(r["napis"] >= 1 for r in uq["recs"])

        # delete → disable push → agents stop capturing
        assert (await qc.query({"op": "delete", "objtype": "tracedef",
                                "name": "all-svcs"}))["ok"]
        await srv.push_trace_control()
        await until(lambda: all(not a.trace_enabled for a in agents),
                    what="TRACE_SET disable")

        await qc.close()
        for a in agents:
            await a.close()
        await srv.stop()

    asyncio.run(main())
