"""CI smoke: the ``--shards`` fleet serving mode against a REAL
server process on a simulated 8-device mesh.

Boots ``python -m gyeeta_tpu serve --shards 8`` (per-shard ingest
loops + per-shard WAL subdirs + once-per-tick collective roll-up)
under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``, feeds
wire traffic from TWO agents whose sticky hids hash to different
shards, then asserts the MERGED fleet view end-to-end:

- svcstate and topk rows are non-empty and carry BOTH agents' hosts
  (the cross-shard merge actually merged);
- the stock NM edge (sim/nodeweb.py) and the REST gateway render the
  same requests byte-equal (same snapshot tick);
- the per-shard WAL subdirs exist and hold both agents' chunks on
  their layout shards;
- per-shard fold-rate gauges ride the exposition.

Run by ci.sh; standalone: ``JAX_PLATFORMS=cpu python _multichip_smoke.py``.
"""

from __future__ import annotations

import asyncio
import json
import os
import socket
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
N_SHARDS = 8


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _spawn_server(port: int, tmp: str):
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count="
                  f"{N_SHARDS}",
        # small mesh geometry: smoke compiles must stay in CI budget
        GYT_N_HOSTS="16", GYT_SVC_CAPACITY="256",
        GYT_TASK_CAPACITY="256", GYT_CONN_BATCH="256",
        GYT_RESP_BATCH="512", GYT_LISTENER_BATCH="64", GYT_FOLD_K="2",
        GYT_DEP_PAIR_CAPACITY="2048", GYT_DEP_EDGE_CAPACITY="1024")
    cmd = [sys.executable, "-m", "gyeeta_tpu", "serve",
           "--host", "127.0.0.1", "--port", str(port),
           "--shards", str(N_SHARDS),
           "--journal-dir", os.path.join(tmp, "wal"),
           "--hostmap", os.path.join(tmp, "hostmap.json"),
           "--tick-interval", "1.0",
           "--handshake-timeout", "5", "--idle-timeout", "600",
           "--stats-interval", "60", "--log-level", "WARNING"]
    return subprocess.Popen(cmd, cwd=HERE, env=env)


async def _wait_ready(port: int, proc, timeout: float = 600.0) -> None:
    from gyeeta_tpu.net.agent import QueryClient
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise SystemExit(
                f"server exited early (rc={proc.returncode})")
        try:
            qc = QueryClient(connect_timeout=2.0, request_timeout=30.0)
            await qc.connect("127.0.0.1", port)
            await qc.query({"subsys": "serverstatus"})
            await qc.close()
            return
        except Exception:
            await asyncio.sleep(1.0)
    raise SystemExit("sharded server never became ready")


async def _rest_query(gh, gp, req: dict) -> tuple:
    reader, writer = await asyncio.open_connection(gh, gp)
    body = json.dumps(req).encode()
    writer.write(
        b"POST /query HTTP/1.1\r\nHost: s\r\nConnection: close\r\n"
        + f"Content-Length: {len(body)}\r\n\r\n".encode() + body)
    await writer.drain()
    raw = await reader.read(-1)
    writer.close()
    head, _, rbody = raw.partition(b"\r\n\r\n")
    assert b" 200 " in head.splitlines()[0], head
    return rbody, json.loads(rbody)


async def scenario(port: int, proc, tmp: str) -> None:
    from gyeeta_tpu.net.agent import NetAgent, QueryClient
    from gyeeta_tpu.net.webgw import WebGateway
    from gyeeta_tpu.sim.nodeweb import NodeWebSim

    await _wait_ready(port, proc)
    host = "127.0.0.1"

    # two agents → two sticky hids (0, 1) → different layout shards.
    # Generous dial deadline: the serving loop stalls for minutes while
    # the first tick compiles the mesh programs in a cold process.
    agents = [NetAgent(machine_id=0x5111 + i, seed=3 + i, n_svcs=3,
                       connect_timeout=420.0)
              for i in range(2)]
    hids = []
    for a in agents:
        hids.append(await a.connect(host, port))
        await a.send_sweep(n_conn=192, n_resp=256)
    assert len(set(h % N_SHARDS for h in hids)) == 2, hids

    # wait for a data-carrying merged snapshot on the serving edge
    qc = QueryClient(connect_timeout=5.0, request_timeout=60.0)
    await qc.connect(host, port)
    deadline = time.monotonic() + 600.0
    while time.monotonic() < deadline:
        for a in agents:
            await a.send_sweep(n_conn=64, n_resp=64)
        out = await qc.query({"subsys": "svcstate", "maxrecs": 100})
        hosts_seen = {r["hostid"] for r in out.get("recs", [])}
        if out.get("nrecs", 0) >= 6 and len(hosts_seen) >= 2:
            break
        await asyncio.sleep(1.0)
    else:
        raise SystemExit("merged svcstate never carried both shards")
    assert {float(h) for h in hids} <= hosts_seen, (hids, hosts_seen)

    # NM vs REST byte-equality on the MERGED view (same snapshot tick)
    gw = WebGateway(host, port)
    gh, gp = await gw.start()
    nw = NodeWebSim(hostname="ci-multichip")
    hs = await nw.connect(host, port)
    assert hs["error_code"] == 0, hs
    for subsys in ("svcstate", "topk"):
        ok = False
        for _ in range(12):      # ticks advance under us: align+retry
            nm = await nw.query_web(subsys, maxrecs=50)
            rest_raw, rest = await _rest_query(
                gh, gp, {"subsys": subsys, "maxrecs": 50})
            if nm.get("snaptick") == rest.get("snaptick"):
                assert nm["nrecs"] > 0, f"{subsys}: empty over NM"
                assert json.dumps(nm).encode() == rest_raw, \
                    f"{subsys}: NM vs REST bytes differ"
                ok = True
                break
            await asyncio.sleep(0.3)
        if not ok:
            raise SystemExit(
                f"{subsys}: never aligned NM/REST on one snapshot")

    # per-shard WAL subdirs hold each agent's chunks on its shard
    from gyeeta_tpu.utils import journal as J
    subdirs = J.sharded_subdirs(os.path.join(tmp, "wal"))
    assert len(subdirs) == N_SHARDS, subdirs
    seen_shards = set()
    for s, d in enumerate(subdirs):
        for _seg, _off, _t, hid, _tick, _cid, _chunk in J.read_sealed(
                d, None, None):
            assert hid % N_SHARDS == s, (hid, s)
            seen_shards.add(s)
    assert {h % N_SHARDS for h in hids} <= seen_shards, \
        (hids, seen_shards)

    # per-shard fold gauges + roll-up timing ride the exposition
    _raw, met = await _rest_query(gh, gp, {"subsys": "metrics"})
    text = met["text"]
    assert "gyt_rollup_seconds" in text, "no roll-up timing gauge"
    assert 'gyt_shard_fold_ev_per_sec{shard="0"}' in text, \
        "no per-shard fold gauges"

    await nw.close()
    await gw.stop()
    await qc.close()
    for a in agents:
        await a.close()
    print("multichip smoke: OK — --shards 8 serve, merged "
          f"svcstate ({out['nrecs']} rows, hosts {sorted(hosts_seen)}), "
          "NM/REST byte-equal svcstate+topk, per-shard WAL routed, "
          "per-shard gauges exposed", file=sys.stderr)


def main() -> int:
    tmp = tempfile.mkdtemp(prefix="gyt_multichip_smoke_")
    port = _free_port()
    proc = _spawn_server(port, tmp)
    try:
        asyncio.run(scenario(port, proc, tmp))
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
        import shutil
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
