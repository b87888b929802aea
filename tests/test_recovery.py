"""Recovery e2e: checkpoint → kill → restore → reconnect → converge.

VERDICT r3 task 8 done-criterion, the documented recovery story as ONE
test: server checkpoints and dies; a replacement restores the
checkpoint; agents reconnect (sticky ids via the hostmap), re-announce
their inventory, stream fresh sweeps; the fleet view converges to the
pre-kill one. Ref: re-registration resend semantics
``gy_socket_stat.h:1235-1270`` (notify_init_*), parmon respawn
``gypartha.cc:965`` (deploy-level: compose ``restart`` +
``--restore-latest``).
"""

from __future__ import annotations

import asyncio

import numpy as np

from gyeeta_tpu.engine.aggstate import EngineCfg
from gyeeta_tpu.net import GytServer, NetAgent, QueryClient
from gyeeta_tpu.runtime import Runtime
from gyeeta_tpu.server_main import latest_checkpoint
from gyeeta_tpu.utils import checkpoint as ckpt
from waiting import sweeps_fed

CFG = EngineCfg(n_hosts=8, svc_capacity=256, task_capacity=256,
                conn_batch=256, resp_batch=512, listener_batch=64,
                fold_k=2)


async def _query(host, port, req):
    qc = QueryClient()
    await qc.connect(host, port)
    out = await qc.query(req)
    await qc.close()
    return out


async def _recovery(tmp_path):
    hostmap = str(tmp_path / "hostmap.json")
    ckpt_dir = tmp_path / "ckpt"
    ckpt_dir.mkdir()

    # ---- epoch 1: fleet runs, state accumulates, checkpoint, "crash"
    rt1 = Runtime(CFG)
    srv1 = GytServer(rt1, tick_interval=None, hostmap_path=hostmap)
    host, port = await srv1.start()
    agents = [NetAgent(seed=i, n_svcs=2, n_groups=3) for i in range(3)]
    hids1 = [await a.connect(host, port) for a in agents]
    for i in range(3):
        for a in agents:
            await a.send_sweep(n_conn=128, n_resp=256)
        await sweeps_fed(rt1, (i + 1) * len(agents))
        rt1.flush()
        rt1.run_tick()
    pre = await _query(host, port, {"subsys": "svcstate",
                                    "sortcol": "svcid"})
    pre_hosts = await _query(host, port, {"subsys": "hoststate"})
    pre_nconn = float(np.asarray(rt1.state.n_conn))
    assert pre["nrecs"] == 6 and pre_hosts["nrecs"] == 3

    tick1 = rt1._tick_no
    path = ckpt.save(str(ckpt_dir / f"gyt_final_{tick1:08d}.npz"),
                     CFG, rt1.state, extra={"tick": tick1})
    # crash: server vanishes; agents' conns break mid-stream
    await srv1.stop()

    # ---- epoch 2: replacement restores the LATEST checkpoint
    found = latest_checkpoint(str(ckpt_dir))
    assert str(found) == str(path)
    rt2 = Runtime(CFG)
    extra = rt2.restore(found)
    assert extra["tick"] == tick1
    assert float(np.asarray(rt2.state.n_conn)) == pre_nconn
    srv2 = GytServer(rt2, tick_interval=None, hostmap_path=hostmap)
    host2, port2 = await srv2.start()

    # agents reconnect: sticky ids, full re-announce, fresh sweeps
    hids2 = []
    for a in agents:
        hids2.append(await a.connect(host2, port2))
    assert hids2 == hids1                       # sticky placement
    for i in range(2):
        for a in agents:
            await a.send_sweep(n_conn=128, n_resp=256)
        await sweeps_fed(rt2, (i + 1) * len(agents))
        rt2.flush()
        rt2.run_tick()

    post = await _query(host2, port2, {"subsys": "svcstate",
                                       "sortcol": "svcid"})
    post_hosts = await _query(host2, port2, {"subsys": "hoststate"})
    for a in agents:
        await a.close()
    await srv2.stop()
    return pre, post, pre_hosts, post_hosts, pre_nconn, rt2


def test_recovery_end_to_end(tmp_path):
    pre, post, pre_hosts, post_hosts, pre_nconn, rt2 = asyncio.run(
        _recovery(tmp_path))
    # the fleet view CONVERGES: same services, same hosts, resolved
    # names (re-announced inventory), all hosts back Up
    assert {r["svcid"] for r in post["recs"]} \
        == {r["svcid"] for r in pre["recs"]}
    assert all(r["svcname"].startswith("svc-") for r in post["recs"])
    assert post_hosts["nrecs"] == pre_hosts["nrecs"] == 3
    assert all(r["state"] != "Down" for r in post_hosts["recs"])
    # cumulative device counters RESUMED from the checkpoint and then
    # advanced with the fresh sweeps (not reset to zero)
    assert float(np.asarray(rt2.state.n_conn)) > pre_nconn


def test_restore_drops_stale_staged_bytes(tmp_path):
    """Bytes staged before a restore must not double-count into the
    restored state (restore() clears backlogs + partial frames)."""
    from gyeeta_tpu.sim.partha import ParthaSim

    rt = Runtime(CFG)
    sim = ParthaSim(n_hosts=4, n_svcs=2, seed=3)
    rt.feed(sim.conn_frames(256))
    rt.flush()
    path = ckpt.save(str(tmp_path / "gyt_a.npz"), CFG, rt.state,
                     extra={"tick": rt._tick_no})
    n0 = float(np.asarray(rt.state.n_conn))
    rt.feed(sim.conn_frames(64))      # staged but never flushed…
    rt.restore(path)                  # …must vanish on restore
    rt.flush()
    assert float(np.asarray(rt.state.n_conn)) == n0
