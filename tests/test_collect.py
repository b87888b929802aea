"""Real host collectors (/proc //sys) + the collect=True agent mode."""

import asyncio
import os
import time

import numpy as np
import pytest

from gyeeta_tpu.engine.aggstate import EngineCfg
from gyeeta_tpu.ingest import wire
from gyeeta_tpu.net import collect as C
from gyeeta_tpu.net.agent import NetAgent, QueryClient
from gyeeta_tpu.net.server import GytServer
from gyeeta_tpu.runtime import Runtime
from gyeeta_tpu.utils.intern import InternTable
from waiting import send_sweep_fed

needs_proc = pytest.mark.skipif(not os.path.exists("/proc/stat"),
                                reason="no /proc")


@needs_proc
def test_cpumem_collector_sane():
    cm = C.CpuMemCollector(host_id=7)
    time.sleep(0.3)
    r = cm.sample()
    assert r.dtype == wire.CPU_MEM_DT and len(r) == 1
    v = r[0]
    assert 0.0 <= v["cpu_pct"] <= 100.0
    assert 0.0 < v["rss_pct"] < 100.0
    assert v["ncpus"] >= 1
    assert v["host_id"] == 7
    # second delta also sane (state carried across samples)
    time.sleep(0.2)
    v2 = cm.sample()[0]
    assert 0.0 <= v2["cpu_pct"] <= 100.0


@needs_proc
def test_host_info_collector():
    hi, names = C.collect_host_info(host_id=5)
    t = InternTable()
    t.update(names)
    v = hi[0]
    assert v["ncpus"] >= 1 and v["ram_mb"] > 0
    kern = t.lookup(wire.NAME_KIND_MISC, int(v["kern_ver_id"]))
    assert kern == os.uname().release
    distro = t.lookup(wire.NAME_KIND_MISC, int(v["distro_id"]))
    assert distro and distro != ""


@needs_proc
def test_cgroup_collector():
    cg = C.CgroupCollector(host_id=2)
    if not cg._base.exists():
        pytest.skip("no cgroup fs")
    cg.sample()                        # baseline
    time.sleep(0.3)
    recs, names = cg.sample()
    assert len(recs) >= 1              # at least the root group
    t = InternTable()
    t.update(names)
    r = recs[0]
    assert t.lookup(wire.NAME_KIND_MISC, int(r["dir_id"])) == "/"
    assert float(r["cpu_pct"]) >= 0.0
    assert int(r["nprocs"]) >= 1
    assert int(r["host_id"]) == 2


@needs_proc
def test_collect_agent_end_to_end():
    """A collect=True agent ships THIS host's real inventory and gauges
    through the socket edge into queryable subsystems."""

    async def main():
        cfg = EngineCfg(n_hosts=4, svc_capacity=64, conn_batch=64,
                        resp_batch=64, fold_k=2)
        rt = Runtime(cfg)
        srv = GytServer(rt, tick_interval=3600)
        host, port = await srv.start()
        a = NetAgent(seed=0, collect=True)
        await a.connect(host, port)
        await asyncio.sleep(0.3)       # real delta window
        await send_sweep_fed(rt, a, n_conn=64, n_resp=64)
        rt.run_tick()
        qc = QueryClient()
        await qc.connect(host, port)
        hi = await qc.query({"subsys": "hostinfo"})
        assert hi["nrecs"] == 1
        row = hi["recs"][0]
        assert row["kernverstr"] == os.uname().release
        assert row["ncpus"] == (os.cpu_count() or 1)
        assert row["host"] == os.uname().nodename
        cm = await qc.query({"subsys": "cpumem"})
        assert cm["nrecs"] == 1
        assert 0.0 <= cm["recs"][0]["cpu"] <= 100.0
        cg = await qc.query({"subsys": "cgroupstate"})
        # root cgroup at minimum (container mounts may hide children)
        assert cg["nrecs"] >= 1
        assert cg["recs"][0]["dir"].startswith("/")
        await qc.close()
        await a.close()
        await srv.stop()

    asyncio.run(main())


def test_mount_and_netif_collectors_real():
    """Mount + interface collectors read THIS box (ref MOUNT_HDLR /
    NET_IF_HDLR capabilities, gy_mount_disk.h:233 / gy_netif.h:708)."""
    import time as _time

    from gyeeta_tpu.net.collect import MountCollector, NetIfCollector

    m = MountCollector(host_id=2)
    recs, names = m.sample()
    assert len(recs) >= 1                  # at least the root fs
    local = recs[recs["is_network_fs"] == 0]
    assert len(local) >= 1 and (local["size_mb"] > 0).all()
    # network mounts are inventoried WITHOUT statvfs (size 0) unless
    # GYT_STAT_NETFS opts in — a hung NFS must not freeze the agent
    assert ((recs["used_pct"] >= 0) & (recs["used_pct"] <= 100)).all()
    n = NetIfCollector(host_id=2)
    n.sample()                             # baseline
    _time.sleep(0.2)
    nrecs, nnames = n.sample()
    assert len(nrecs) >= 1                 # at least lo
    assert (nrecs["rx_mb_sec"] >= 0).all()
    assert len(nnames) >= 1


def test_mount_netif_end_to_end():
    """collect-mode agent streams mount/netif sweeps; mountstate and
    netif subsystems answer over the wire with this box's real data."""
    import asyncio

    from gyeeta_tpu.net import GytServer, NetAgent, QueryClient
    from gyeeta_tpu.runtime import Runtime

    from gyeeta_tpu.engine.aggstate import EngineCfg

    cfg = EngineCfg(n_hosts=8, svc_capacity=128, task_capacity=128,
                    conn_batch=64, resp_batch=64, listener_batch=64,
                    fold_k=2)

    async def run():
        rt = Runtime(cfg)
        srv = GytServer(rt, tick_interval=None)
        host, port = await srv.start()
        agent = NetAgent(collect=True, n_svcs=2, n_groups=2)
        try:
            await agent.connect(host, port)
            await send_sweep_fed(rt, agent, n_conn=64, n_resp=64)
            await asyncio.sleep(0.3)   # a real window for the rates
            await send_sweep_fed(rt, agent, n_conn=64, n_resp=64)
            rt.flush()
            qc = QueryClient()
            await qc.connect(host, port)
            mnt = await qc.query({"subsys": "mountstate",
                                  "sortcol": "usedpct"})
            nif = await qc.query({"subsys": "netif", "sortcol": "name",
                                  "sortdesc": False})
            await qc.close()
            return mnt, nif
        finally:
            await agent.close()
            await srv.stop()

    mnt, nif = asyncio.run(run())
    assert mnt["nrecs"] >= 1
    r = mnt["recs"][0]
    assert r["mnt"].startswith("/") and r["fstype"]
    assert 0 <= r["usedpct"] <= 100
    assert nif["nrecs"] >= 1
    assert any(x["name"] == "lo" for x in nif["recs"])
