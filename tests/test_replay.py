"""Wire capture/replay harness + per-connection frame reassembly."""

import asyncio

import numpy as np

from gyeeta_tpu.engine.aggstate import EngineCfg
from gyeeta_tpu.ingest import wire
from gyeeta_tpu.net.agent import NetAgent, QueryClient
from gyeeta_tpu.net.server import GytServer
from gyeeta_tpu.runtime import Runtime
from gyeeta_tpu.sim.partha import ParthaSim
from gyeeta_tpu.utils import replay
from waiting import counter, sweeps_fed, until

CFG = EngineCfg(n_hosts=8, svc_capacity=64, conn_batch=64, resp_batch=64,
                fold_k=2)


def test_complete_prefix():
    sim = ParthaSim(n_hosts=2, n_svcs=2, seed=1)
    buf = sim.conn_frames(32) + sim.resp_frames(32)
    assert wire.complete_prefix(buf) == len(buf)
    assert wire.complete_prefix(buf[:-5]) < len(buf) - 5
    assert wire.complete_prefix(b"") == 0
    assert wire.complete_prefix(buf[:10]) == 0      # partial header
    try:
        wire.complete_prefix(b"\x00" * 32)
        assert False, "bad magic must raise"
    except wire.FrameError:
        pass


def test_interleaved_fragmented_conns():
    """Two connections, frames split at arbitrary byte boundaries and
    interleaved — per-conn reassembly must keep both streams intact."""

    async def main():
        rt = Runtime(CFG)
        srv = GytServer(rt, tick_interval=3600)
        host, port = await srv.start()
        a1 = NetAgent(seed=0)
        a2 = NetAgent(seed=1)
        await a1.connect(host, port)
        await a2.connect(host, port)
        n_ev = 64
        b1 = a1.sim.conn_frames(n_ev)
        b2 = a2.sim.conn_frames(n_ev)
        # write in tiny alternating slices — every frame crosses many
        # writes of its conn, interleaved with the other conn's bytes
        step = 97
        for i in range(0, max(len(b1), len(b2)), step):
            if i < len(b1):
                a1._writer.write(b1[i:i + step])
                await a1._writer.drain()
            if i < len(b2):
                a2._writer.write(b2[i:i + step])
                await a2._writer.drain()
            await asyncio.sleep(0)
        await until(lambda: counter(rt, "conn_events") >= 2 * n_ev,
                    what="both conns' frames")
        rt.flush()
        assert rt.stats.counters.get("frames_bad", 0) == 0
        assert rt.stats.counters["conn_events"] == 2 * n_ev
        await a1.close()
        await a2.close()
        await srv.stop()

    asyncio.run(main())


def test_record_replay_equivalence(tmp_path):
    """Server-side capture replayed into a fresh Runtime reproduces the
    same query results."""
    cap = tmp_path / "cap.gytrec"

    async def record():
        rt = Runtime(CFG)
        srv = GytServer(rt, tick_interval=3600, record_path=str(cap))
        host, port = await srv.start()
        agents = [NetAgent(seed=i) for i in range(2)]
        for a in agents:
            await a.connect(host, port)
            await a.send_sweep(n_conn=64, n_resp=64)
        await sweeps_fed(rt, 2)
        rt.run_tick()
        qc = QueryClient()
        await qc.connect(host, port)
        out = await qc.query({"subsys": "svcstate", "maxrecs": 64})
        await qc.close()
        for a in agents:
            await a.close()
        await srv.stop()
        return out

    live = asyncio.run(record())
    rt2 = Runtime(CFG)
    fed = replay.play(cap, rt2.feed)
    assert fed > 0
    rt2.run_tick()
    out2 = rt2.query({"subsys": "svcstate", "maxrecs": 64})
    assert out2["ntotal"] == live["ntotal"]
    by_id = {r["svcid"]: r for r in live["recs"]}
    for r in out2["recs"]:
        assert r["svcid"] in by_id
        assert r["nqry5s"] == by_id[r["svcid"]]["nqry5s"]


def test_replay_host_remap(tmp_path):
    """host_id translation multiplies one capture into extra hosts."""
    sim = ParthaSim(n_hosts=2, n_svcs=2, seed=9)
    cap = tmp_path / "h.gytrec"
    rec = replay.StreamRecorder(cap, clock=lambda: 1.0)
    rec.write(wire.encode_frame(wire.NOTIFY_HOST_STATE,
                                sim.host_state_records()))
    rec.close()
    rt = Runtime(CFG)
    replay.play(cap, rt.feed)
    replay.play(cap, rt.feed, host_id_offset=4)
    rt.flush()
    last = np.asarray(rt.state.host_last_tick)
    assert set(np.nonzero(last >= 0)[0]) == {0, 1, 4, 5}


def test_thin_client_imports_are_jax_free():
    """Query/agent/replay clients must not pull in jax (CLI latency;
    they must work even when the accelerator backend is unreachable)."""
    import subprocess
    import sys
    code = (
        "import sys; sys.modules['jax'] = None\n"
        "from gyeeta_tpu.net.agent import QueryClient, NetAgent\n"
        "from gyeeta_tpu.utils import replay\n"
        "from gyeeta_tpu.cli import main\n"
        "print('ok')\n"
    )
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"
