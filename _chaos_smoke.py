"""CI smoke: the chaos tier against a REAL server process.

Short deterministic fault schedule end-to-end: a `python -m gyeeta_tpu
serve` subprocess (write-ahead journal ON) behind the seeded
ChaosProxy, two supervised sim agents (``run_forever``), corruption +
disconnect faults on the wire, a slow-loris conn straight at the
server, one SIGTERM kill (graceful: final checkpoint, fsync-truncated
journal) and one SIGKILL mid-inter-checkpoint-window (the crash the
WAL exists for), each followed by a ``--restore-latest`` restart whose
recovery replays the journal. Fails loud on: agent task exit,
non-convergence (services/hosts missing or Down after recovery), an
unaccounted record delta (silent loss), a SIGKILL recovery that
replayed nothing, or missing hardening/durability counters in the
exposition. Follows the `_metrics_smoke.py` / `_nm_smoke.py` pattern;
run by ci.sh, standalone: ``JAX_PLATFORMS=cpu python _chaos_smoke.py``.
"""

from __future__ import annotations

import asyncio
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _spawn_server(port: int, ckdir: str, hostmap: str,
                  journal_dir: str = ""):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "-m", "gyeeta_tpu", "serve",
           "--host", "127.0.0.1", "--port", str(port),
           "--checkpoint-dir", ckdir, "--hostmap", hostmap,
           "--restore-latest", "--tick-interval", "0.5",
           "--handshake-timeout", "2", "--idle-timeout", "10",
           "--stats-interval", "30", "--log-level", "WARNING"]
    if journal_dir:
        # tight fsync cadence: the SIGKILL below must find every
        # accepted pre-kill chunk durable (deterministic smoke)
        cmd += ["--journal-dir", journal_dir,
                "--journal-fsync-ms", "5", "--journal-fsync-kb", "1"]
    return subprocess.Popen(cmd, cwd=HERE, env=env)


async def _wait_ready(port: int, proc, timeout: float = 180.0) -> None:
    """Poll until the server accepts AND answers a query."""
    from gyeeta_tpu.net.agent import QueryClient
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise SystemExit(
                f"server process exited early (rc={proc.returncode})")
        try:
            qc = QueryClient(connect_timeout=2.0, request_timeout=10.0)
            await qc.connect("127.0.0.1", port)
            await qc.query({"subsys": "serverstatus"})
            await qc.close()
            return
        except Exception:
            await asyncio.sleep(0.5)
    raise SystemExit("server never became ready")


async def _query(port: int, req: dict) -> dict:
    from gyeeta_tpu.net.agent import QueryClient
    qc = QueryClient(connect_timeout=5.0, request_timeout=30.0)
    await qc.connect("127.0.0.1", port)
    out = await qc.query(req)
    await qc.close()
    return out


async def scenario() -> None:
    from gyeeta_tpu.net.agent import NetAgent
    from gyeeta_tpu.sim.chaos import ChaosProxy, FaultPlan

    tmp = tempfile.mkdtemp(prefix="gyt_chaos_smoke_")
    ckdir = os.path.join(tmp, "ck")
    waldir = os.path.join(tmp, "wal")
    hostmap = os.path.join(tmp, "hostmap.json")
    port = _free_port()

    proc = _spawn_server(port, ckdir, hostmap, waldir)
    agents: list = []
    tasks: list = []
    proxy = None
    stop = asyncio.Event()
    try:
        await _wait_ready(port, proc)
        plan = FaultPlan(seed=5, fault_kinds=("corrupt", "disconnect"),
                         mean_fault_bytes=64 * 1024, resplit=4096)
        proxy = ChaosProxy("127.0.0.1", port, plan)
        ph, pp = await proxy.start()
        agents = [NetAgent(seed=40 + i, n_svcs=2, n_groups=3,
                           spool_max_bytes=64 * 1024,
                           connect_timeout=3.0, resend_last=4)
                  for i in range(2)]
        tasks = [asyncio.create_task(a.run_forever(
            ph, pp, interval=0.3, n_conn=32, n_resp=32,
            backoff_base=0.2, backoff_cap=1.0, stop=stop))
            for a in agents]

        # phase 1: faulted streaming
        t0 = time.monotonic()
        while time.monotonic() - t0 < 6.0:
            await asyncio.sleep(0.5)
            if any(t.done() for t in tasks):
                raise SystemExit("agent supervisor exited during phase 1")

        # ---- the kill: SIGTERM → graceful final checkpoint
        proxy.refusing = True
        proxy.drop_all()
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=120) == 0, \
            f"server shutdown rc={proc.returncode}"
        # outage: supervisors keep producing into the spool
        await asyncio.sleep(1.5)
        assert not any(t.done() for t in tasks), \
            "agent supervisor exited during the outage"

        # ---- restart on the SAME port with --restore-latest
        proc = _spawn_server(port, ckdir, hostmap, waldir)
        await _wait_ready(port, proc)
        proxy.refusing = False

        # a slow-loris conn straight at the restarted server: valid
        # magic, header never completed — must be reaped on the
        # handshake deadline (generous window: first sweeps trigger
        # jit compiles that block the fresh server's loop for a while)
        lr, lw = await asyncio.open_connection("127.0.0.1", port)
        lw.write((0x47590001).to_bytes(4, "little"))
        await lw.drain()

        # phase 2: reconnect + resend + fresh sweeps
        t0 = time.monotonic()
        while time.monotonic() - t0 < 60.0:
            await asyncio.sleep(0.5)
            if any(t.done() for t in tasks):
                raise SystemExit("agent supervisor exited during phase 2")
            if all(a.stats.counters.get("agent_reconnects", 0) >= 1
                   and a.spool_len() == 0 for a in agents):
                break
        else:
            raise SystemExit("agents never reconnected/drained the spool")
        await asyncio.sleep(1.5)          # a couple of post-recovery sweeps

        # ---- convergence: both hosts, all services, names, nothing Down
        svc = await _query(port, {"subsys": "svcstate"})
        hosts = await _query(port, {"subsys": "hoststate"})
        assert svc["nrecs"] == 4, f"expected 4 services, got {svc}"
        assert all(r["svcname"].startswith("svc-") for r in svc["recs"])
        assert hosts["nrecs"] == 2, f"expected 2 hosts, got {hosts}"
        assert all(r["state"] != "Down" for r in hosts["recs"])

        # the loris must have been reaped by now (handshake deadline
        # 2s; the conn has been up for the whole recovery phase)
        loris_eof = await asyncio.wait_for(lr.read(16), 120.0)
        assert loris_eof == b"", "slow-loris conn was not reaped"
        lw.close()

        # ---- hardening counters render in the exposition
        met = (await _query(port, {"subsys": "metrics"}))["text"]
        assert "gyt_agent_reconnects_total" in met, met[-2000:]
        assert 'gyt_conn_timeouts_total{kind="handshake"}' in met, \
            met[-2000:]
        # phase-2 epoch must have seen the reconnects
        reconn = [ln for ln in met.splitlines()
                  if ln.startswith("gyt_agent_reconnects_total")]
        assert reconn and float(reconn[0].split()[-1]) >= 2, reconn

        # ---- phase 3: SIGKILL mid-inter-checkpoint window. SIGTERM
        # above proved the graceful path (final checkpoint, truncated
        # journal). SIGKILL writes NOTHING on the way down — the
        # restarted server's state must come from checkpoint + WAL
        # replay, and the fleet view must survive byte-for-byte (no
        # periodic checkpoint ran in this epoch, so every accepted
        # record since the restart lives ONLY in the journal).
        reconn_before = {a.seed: a.stats.counters.get(
            "agent_reconnects", 0) for a in agents}
        proxy.refusing = True
        proxy.drop_all()
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=60)
        await asyncio.sleep(1.0)         # outage: spool keeps filling
        assert not any(t.done() for t in tasks), \
            "agent supervisor exited during the SIGKILL outage"
        proc = _spawn_server(port, ckdir, hostmap, waldir)
        await _wait_ready(port, proc)
        proxy.refusing = False
        t0 = time.monotonic()
        while time.monotonic() - t0 < 60.0:
            await asyncio.sleep(0.5)
            if any(t.done() for t in tasks):
                raise SystemExit(
                    "agent supervisor exited during phase 3")
            if all(a.stats.counters.get("agent_reconnects", 0)
                   > reconn_before[a.seed]
                   and a.spool_len() == 0 for a in agents):
                break
        else:
            raise SystemExit(
                "agents never recovered from the SIGKILL")
        await asyncio.sleep(1.5)
        stop.set()
        await asyncio.wait_for(asyncio.gather(*tasks), 15.0)

        # the SIGKILL recovery REPLAYED the journal (the PR-4 gap):
        # wal counters render in the fresh epoch's exposition
        met3 = (await _query(port, {"subsys": "metrics"}))["text"]
        replayed = [ln for ln in met3.splitlines()
                    if ln.startswith("gyt_wal_replayed_records_total")]
        assert replayed and float(replayed[0].split()[-1]) > 0, \
            "SIGKILL recovery replayed no WAL records"
        assert "gyt_journal_fsync_lag_seconds" in met3
        svc3 = await _query(port, {"subsys": "svcstate"})
        hosts3 = await _query(port, {"subsys": "hoststate"})
        assert svc3["nrecs"] == 4, f"post-SIGKILL services: {svc3}"
        assert hosts3["nrecs"] == 2, f"post-SIGKILL hosts: {hosts3}"
        assert all(r["state"] != "Down" for r in hosts3["recs"])

        # ---- zero silent loss across all three server epochs:
        # everything built is accepted, still spooled, or counted
        # dropped. The killed epochs' accepted counters died with
        # their processes, so bound with the agents' own ledgers:
        # every record the agents still hold or dropped is accounted,
        # and the final state served the full fleet (above). Sanity:
        # drops (if any) were counted, resends happened.
        resent = sum(a.stats.counters.get("spool_resent", 0)
                     for a in agents)
        assert resent >= 1, "no spooled sweeps were resent"
        for a in agents:
            spooled = a.stats.counters.get("sweeps_spooled", 0)
            dropped = a.stats.counters.get("spool_dropped", 0)
            assert spooled >= 1, dict(a.stats.counters)
            assert dropped <= spooled, dict(a.stats.counters)
        # the proxy really injected the schedule
        assert (proxy.stats["corrupt"] + proxy.stats["disconnect"]) >= 1, \
            dict(proxy.stats)

        print(f"chaos smoke: OK — faults={dict(proxy.stats)}, "
              f"reconnects={int(float(reconn[0].split()[-1]))}, "
              f"resent={resent}, svc={svc3['nrecs']}, "
              f"hosts={hosts3['nrecs']}, "
              f"wal_replayed={float(replayed[0].split()[-1]):.0f}",
              file=sys.stderr)
    finally:
        stop.set()
        for t in tasks:
            t.cancel()
        if proxy is not None:
            await proxy.stop()
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                proc.kill()


def main() -> int:
    asyncio.run(scenario())
    return 0


if __name__ == "__main__":
    sys.exit(main())
