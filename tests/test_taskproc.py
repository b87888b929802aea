"""Real process collection: /proc walk → AGGR_TASK records → queries.

VERDICT r3 task 4's done-criterion: taskstate/topcpu queries show THIS
host's real processes, and TOPFORK is queryable. Ref: the task handler
aggregation ``common/gy_task_handler.cc:2568`` / ``gy_task_handler.h:180``
and TASK_TOP_PROCS ``gy_comm_proto.h:1415``.
"""

from __future__ import annotations

import asyncio
import subprocess
import time

import numpy as np
import pytest

from gyeeta_tpu.engine.aggstate import EngineCfg
from gyeeta_tpu.ingest import wire
from gyeeta_tpu.net import GytServer, NetAgent, QueryClient
from gyeeta_tpu.net.taskproc import ProcTaskCollector
from gyeeta_tpu.net.tcpconn import aggr_task_id_of
from gyeeta_tpu.runtime import Runtime
from waiting import sweeps_fed

CFG = EngineCfg(n_hosts=8, svc_capacity=256, task_capacity=512,
                conn_batch=256, resp_batch=512, listener_batch=64,
                fold_k=2)


def test_collector_groups_real_processes():
    col = ProcTaskCollector(host_id=5, machine_id=0xFEED)
    recs, names = col.sweep()
    assert len(recs) >= 1                  # at least this python
    assert len(names) >= 1                 # comms announced once
    # this test process appears in a python* group with real RSS
    ids = {int(r["aggr_task_id"]) for r in recs}
    py_ids = {aggr_task_id_of(0xFEED, c)
              for c in ("python", "python3", "pytest")}
    assert ids & py_ids
    total = int(recs["ntasks_total"].sum())
    assert total >= 2                      # >1 process on any live box
    time.sleep(0.3)
    recs2, names2 = col.sweep()
    assert len(names2) <= len(names)       # announce-once semantics
    me = [r for r in recs2 if int(r["aggr_task_id"]) in py_ids]
    assert me and float(me[0]["rss_mb"]) > 1.0


def test_fork_detection():
    col = ProcTaskCollector(host_id=5, machine_id=0xFEED)
    col.sweep()                            # baseline
    time.sleep(0.2)
    procs = [subprocess.Popen(["sleep", "30"]) for _ in range(3)]
    time.sleep(0.2)
    try:
        recs, _ = col.sweep()
        grp = recs[recs["aggr_task_id"]
                   == np.uint64(aggr_task_id_of(0xFEED, "sleep"))]
        assert len(grp) == 1
        assert int(grp[0]["ntasks_total"]) >= 3
        assert float(grp[0]["forks_sec"]) > 0   # the TOPFORK signal
    finally:
        for p in procs:
            p.kill()
            p.wait()


async def _real_task_session():
    rt = Runtime(CFG)
    srv = GytServer(rt, tick_interval=None)
    host, port = await srv.start()
    agent = NetAgent(real=True)
    try:
        await agent.connect(host, port)
        await agent.send_sweep()
        await asyncio.sleep(0.3)           # a real window for cpu deltas
        await agent.send_sweep()           # second sweep: cpu deltas
        await sweeps_fed(rt, 2)
        rt.flush()
        rt.run_tick()
        qc = QueryClient()
        await qc.connect(host, port)
        task = await qc.query({"subsys": "taskstate"})
        fork = await qc.query({"subsys": "topfork"})
        await qc.close()
        return task, fork
    finally:
        await agent.close()
        await srv.stop()


def test_real_tasks_end_to_end():
    """taskstate over the wire shows this box's real process groups by
    comm name; topfork is queryable and fork-sorted."""
    task, fork = asyncio.run(_real_task_session())
    assert task["nrecs"] >= 1
    comms = {r["comm"] for r in task["recs"]}
    assert any(c.startswith("python") or c == "pytest" for c in comms), \
        comms
    # topfork: a valid (possibly empty-forks) preset view, sorted desc
    forks = [r["forks"] for r in fork["recs"]]
    assert forks == sorted(forks, reverse=True)
