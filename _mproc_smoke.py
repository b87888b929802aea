"""CI smoke: the ``--ingest-procs`` multi-process ingest edge against
a REAL server process.

Boots ``python -m gyeeta_tpu serve --shards 8 --ingest-procs 2`` (two
ingest worker processes owning sticky shard groups; wire validation,
native deframe/decode and the per-shard WAL append run near the wire,
decoded record batches cross shared-memory rings into the fold), feeds
from TWO agents whose sticky hids land on DIFFERENT shard groups, then
asserts end-to-end:

- the merged svcstate carries both agents' hosts and renders
  byte-equal over the REST gateway and a stock NM conn (same snapshot
  tick) — the worker path changes nothing the edges can see;
- the per-worker heartbeat/liveness gauges
  (``gyt_ingest_proc_heartbeat_age_seconds{proc=...}``) and the
  worker ledger counters ride /metrics;
- the per-shard WAL subdirs were written BY THE WORKERS in the stock
  layout (chunks on their layout shards).

Run by ci.sh; standalone: ``JAX_PLATFORMS=cpu python _mproc_smoke.py``.
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
N_PROCS = 2


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
        GYT_N_HOSTS="16", GYT_SVC_CAPACITY="256",
        GYT_TASK_CAPACITY="256", GYT_CONN_BATCH="256",
        GYT_RESP_BATCH="512", GYT_LISTENER_BATCH="64", GYT_FOLD_K="2",
        GYT_DEP_PAIR_CAPACITY="2048", GYT_DEP_EDGE_CAPACITY="1024")
    cmd = [sys.executable, "-m", "gyeeta_tpu", "serve",
           "--host", "127.0.0.1", "--port", str(port),
           "--shards", str(N_SHARDS), "--ingest-procs", str(N_PROCS),
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
    raise SystemExit("mproc server never became ready")


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

    # hids 0 and 1 → shards 0 and 1 → worker groups 0 and 1
    agents = [NetAgent(machine_id=0x7A11 + i, seed=13 + i, n_svcs=3,
                       connect_timeout=420.0)
              for i in range(2)]
    hids = []
    for a in agents:
        hids.append(await a.connect(host, port))
        await a.send_sweep(n_conn=192, n_resp=256)
    assert len({h % N_SHARDS % N_PROCS for h in hids}) == 2, hids

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
        raise SystemExit("merged svcstate never carried both workers' "
                         "shards")
    assert {float(h) for h in hids} <= hosts_seen, (hids, hosts_seen)

    # NM vs REST byte-equality through the worker-fed fold
    gw = WebGateway(host, port)
    gh, gp = await gw.start()
    nw = NodeWebSim(hostname="ci-mproc")
    hs = await nw.connect(host, port)
    assert hs["error_code"] == 0, hs
    ok = False
    for _ in range(12):
        nm = await nw.query_web("svcstate", maxrecs=50)
        rest_raw, rest = await _rest_query(
            gh, gp, {"subsys": "svcstate", "maxrecs": 50})
        if nm.get("snaptick") == rest.get("snaptick"):
            assert nm["nrecs"] > 0, "svcstate empty over NM"
            assert json.dumps(nm).encode() == rest_raw, \
                "svcstate: NM vs REST bytes differ"
            ok = True
            break
        await asyncio.sleep(0.3)
    if not ok:
        raise SystemExit("never aligned NM/REST on one snapshot")

    # per-worker heartbeat gauges + ledger counters in /metrics
    _raw, met = await _rest_query(gh, gp, {"subsys": "metrics"})
    text = met["text"]
    for w in range(N_PROCS):
        assert (f'gyt_ingest_proc_heartbeat_age_seconds{{proc="{w}"}}'
                in text), f"no heartbeat gauge for worker {w}"
        assert f'gyt_ingest_proc_up{{proc="{w}"}} 1' in text, \
            f"worker {w} not up in /metrics"
    assert 'gyt_ingest_proc_accepted_records_total' in text, \
        "no worker ledger counters in /metrics"

    # worker-owned per-shard WAL: stock layout, chunks on their shards
    from gyeeta_tpu.utils import journal as J
    subdirs = J.sharded_subdirs(os.path.join(tmp, "wal"))
    assert len(subdirs) == N_SHARDS, subdirs
    seen = set()
    for s, d in enumerate(subdirs):
        for _seg, _off, _t, hid, _tick, _cid, _chunk in J.read_sealed(
                d, None, None):
            assert hid % N_SHARDS == s, (hid, s)
            seen.add(s)
    assert {h % N_SHARDS for h in hids} <= seen, (hids, seen)

    await nw.close()
    await gw.stop()
    await qc.close()
    for a in agents:
        await a.close()
    print("mproc smoke: OK — --shards 8 --ingest-procs 2 serve, "
          f"merged svcstate ({out['nrecs']} rows, hosts "
          f"{sorted(hosts_seen)}), NM/REST byte-equal, per-worker "
          "heartbeat gauges exposed, worker-owned WAL routed",
          file=sys.stderr)


def main() -> int:
    tmp = tempfile.mkdtemp(prefix="gyt_mproc_smoke_")
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
