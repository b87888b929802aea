"""CI smoke: fault-domain hardening of the distributed fabric (ISSUE 15).

Two phases over the inter-tier hops the PR-4 chaos tier never touched:

**Phase A — serving fabric** (2 replicas + 2 REAL gateway
subprocesses, one replica behind a wedge-capable chaos proxy):

- gateway SIGKILL mid-subscription → the supervised
  ``SubscribeStream`` hops to the peer gateway with ``last_snaptick``
  (the continuation gap is a COUNTED resync, never silent);
- the killed gateway RESTARTS over its ``--sub-persist`` ring and
  answers a reconnect inside the restored window with a DELTA;
- one replica WEDGED (stalled, not dead — the hard case): hedged
  reads bound query latency off the healthy replica;
- one replica KILLED: the circuit breaker marks it down after K real
  failures (flap counted, state visible in /metrics) and queries
  keep succeeding off the survivor;
- a strong-consistency query poller runs through EVERY fault window:
  zero queries surface an upstream error while >=1 replica is live,
  and p99 stays bounded;
- every subscriber's reassembled stream converges BYTE-EQUAL to an
  uninterrupted control subscription on the serve tier.

**Phase B — process tier under combined load** (a REAL ``serve
--shards 2 --ingest-procs 2`` subprocess, the PR-12 subprocess
methodology):

- ingest worker SIGKILL mid-feed (targeted from OUTSIDE via the new
  ``gyt_ingest_proc_pid`` gauge) while a subscription streams: the
  supervisor respawns it, the ring ledger closes EXACTLY
  (published == consumed + counted drops — zero silent record loss),
  and the subscriber's reassembled view matches a fresh query;
- compaction worker death at a shard boundary (the
  ``GYT_COMPACT_DIE_SHARD`` crash hook): the parallel pass fails
  LOUDLY, the parted store stays consistent, and a rerun converges.

Run by ci.sh; standalone: ``JAX_PLATFORMS=cpu python
_fabric_chaos_smoke.py [a|b]``.
"""

from __future__ import annotations

import asyncio
import json
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


async def _until(cond, timeout=60.0, msg="condition"):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        got = cond()
        if got:
            return got
        await asyncio.sleep(0.05)
    raise AssertionError(f"fabric smoke: timed out waiting for {msg}")


async def _http(port, method, path, body=b"", timeout=20.0):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    try:
        req = (f"{method} {path} HTTP/1.1\r\nHost: s\r\n"
               f"Connection: close\r\n"
               f"Content-Length: {len(body)}\r\n\r\n").encode() + body
        writer.write(req)
        await writer.drain()
        raw = await asyncio.wait_for(reader.read(-1), timeout)
    finally:
        writer.close()
    head, _, rbody = raw.partition(b"\r\n\r\n")
    return int(head.split()[1]), rbody


# ======================================================== phase A


def _spawn_gateway(listen_port, upstreams, peer_port, persist, tmp):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "-m", "gyeeta_tpu", "gateway",
           "--listen-port", str(listen_port),
           "--poll-s", "0.1", "--gw-down-after", "2",
           "--hedge-ms", "100", "--sub-persist", persist,
           "--advertise", f"127.0.0.1:{listen_port}",
           "--peer", f"127.0.0.1:{peer_port}"]
    for h, p in upstreams:
        cmd += ["--upstream", f"{h}:{p}"]
    return subprocess.Popen(cmd, cwd=HERE, env=env,
                            stderr=subprocess.DEVNULL)


async def phase_a(tmp: str) -> None:
    from gyeeta_tpu.engine.aggstate import EngineCfg
    from gyeeta_tpu.ingest import wire
    from gyeeta_tpu.net.server import GytServer
    from gyeeta_tpu.net.subs import SubscribeClient, SubscribeStream
    from gyeeta_tpu.query import delta as D
    from gyeeta_tpu.runtime import Runtime
    from gyeeta_tpu.sim.chaos import ChaosProxy, FaultPlan
    from gyeeta_tpu.sim.partha import ParthaSim

    cfg = EngineCfg(n_hosts=8, svc_capacity=256, task_capacity=256,
                    conn_batch=256, resp_batch=512, listener_batch=64,
                    fold_k=2)
    sim = ParthaSim(n_hosts=8, n_svcs=4, seed=15)

    def feed(rt):
        rt.feed(sim.conn_frames(256) + sim.resp_frames(512)
                + wire.encode_frame(wire.NOTIFY_HOST_STATE,
                                    sim.host_state_records()))

    # two replicas fed IDENTICALLY; replica 0 fronted by the chaos
    # proxy (wedge capability), replica 1 dialed directly
    replicas, servers = [], []
    for _ in range(2):
        rt = Runtime(cfg)
        rt.feed(sim.name_frames())
        rt.feed(sim.listener_frames())
        feed(rt)
        rt.run_tick()
        srv = GytServer(rt, tick_interval=None, idle_timeout=600.0)
        await srv.start()
        replicas.append(rt)
        servers.append(srv)
    proxy = ChaosProxy("127.0.0.1", servers[0].port, FaultPlan())
    ph, pp = await proxy.start()

    async def tick(only=None):
        for i, (rt, srv) in enumerate(zip(replicas, servers)):
            if only is not None and i != only:
                continue
            feed(rt)
            rt.run_tick()
        await servers[0].push_subscriptions()   # the control's hub

    gp1, gp2 = _free_port(), _free_port()
    persist1 = os.path.join(tmp, "gw1_subs.jsonl")
    persist2 = os.path.join(tmp, "gw2_subs.jsonl")
    ups = [("127.0.0.1", pp), ("127.0.0.1", servers[1].port)]
    gw1 = _spawn_gateway(gp1, ups, gp2, persist1, tmp)
    gw2 = _spawn_gateway(gp2, ups, gp1, persist2, tmp)

    async def healthy(port):
        try:
            st, body = await _http(port, "GET", "/healthz",
                                   timeout=5.0)
            return st == 200
        except OSError:
            return False

    async def wait_healthy(port, proc, msg):
        t0 = time.monotonic()
        while time.monotonic() - t0 < 60.0:
            if proc.poll() is not None:
                raise AssertionError(f"{msg}: gateway exited rc="
                                     f"{proc.returncode}")
            if await healthy(port):
                return
            await asyncio.sleep(0.2)
        raise AssertionError(f"{msg}: never healthy")

    await wait_healthy(gp1, gw1, "gw1 boot")
    await wait_healthy(gp2, gw2, "gw2 boot")
    print("fabric smoke[a]: gateways up", file=sys.stderr)

    # ---- the query poller: strong-consistency (uncached → the real
    # failover/hedge path) through EVERY fault window. Contract:
    # zero upstream errors surface while >=1 replica is live; a DEAD
    # GATEWAY is the client's problem (it fails over to the peer).
    lat: list = []
    perrs: list = []
    pstop = asyncio.Event()

    async def poller():
        body = json.dumps({"subsys": "hoststate", "maxrecs": 8,
                           "consistency": "strong"}).encode()
        while not pstop.is_set():
            for port in (gp1, gp2):
                t0 = time.monotonic()
                try:
                    st, rb = await _http(port, "POST", "/query",
                                         body, timeout=15.0)
                except (OSError, asyncio.TimeoutError,
                        TimeoutError, ConnectionError):
                    continue            # dead/killed gateway: fail over
                if st == 200 and b'"error"' not in rb[:64]:
                    lat.append(time.monotonic() - t0)
                else:
                    perrs.append((port, st, rb[:160]))
                break
            await asyncio.sleep(0.1)

    ptask = asyncio.create_task(poller())

    # ---- control subscription: UNINTERRUPTED, direct on replica 0
    q = {"subsys": "svcstate", "sortcol": "qps5s", "sortdesc": True,
         "maxrecs": 50}
    ctl = SubscribeClient()
    await ctl.connect("127.0.0.1", servers[0].port)
    await ctl.subscribe(dict(q))
    control = {"held": None}

    async def ctl_loop():
        async for ev in ctl.events():
            control["held"] = D.apply_event(control["held"], ev)

    ctl_task = asyncio.create_task(ctl_loop())

    # ---- faulted subscriber: supervised stream over BOTH gateways
    stream = SubscribeStream([("127.0.0.1", gp1), ("127.0.0.1", gp2)],
                             q, stall_timeout=3.0, backoff_base=0.1)
    latest = {"held": None}

    async def stream_loop():
        async for held in stream.responses():
            latest["held"] = held

    stask = asyncio.create_task(stream_loop())

    # ---- a second subscription on gw1 whose ring will prove the
    # persisted continuation: hostlist rows are stable, so the
    # post-restart resume MUST be a delta
    q2 = {"subsys": "hostlist", "maxrecs": 64}
    sc2 = SubscribeClient()
    await sc2.connect("127.0.0.1", gp1)
    await sc2.subscribe(dict(q2))
    hl = {"held": None, "n": 0}

    async def hl_loop():
        try:
            async for ev in sc2.events():
                hl["held"] = D.apply_event(hl["held"], ev)
                hl["n"] += 1
        except (ConnectionError, OSError, RuntimeError):
            pass                        # gw1 dies below — expected

    hl_task = asyncio.create_task(hl_loop())

    await _until(lambda: latest["held"] and control["held"]
                 and hl["held"], msg="initial fulls")
    print("fabric smoke[a]: initial fulls received", file=sys.stderr)
    for _ in range(3):
        await tick()
        await asyncio.sleep(0.5)
    await _until(lambda: latest["held"]["snaptick"]
                 == control["held"]["snaptick"], timeout=30.0,
                 msg="pre-fault convergence")
    t_kill = hl["held"]["snaptick"]
    print(f"fabric smoke[a]: pre-fault converged at tick {t_kill}",
          file=sys.stderr)

    # ---- fault 1: gateway SIGKILL mid-subscription
    gw1.kill()
    gw1.wait(timeout=30)
    await tick()
    await asyncio.sleep(0.3)
    await tick()
    await _until(lambda: stream.counters["reconnects"] >= 1
                 and latest["held"]["snaptick"]
                 == control["held"]["snaptick"], timeout=45.0,
                 msg="stream continuation via gw2")
    assert json.dumps(latest["held"]) == json.dumps(control["held"]), \
        "faulted stream diverged from the control subscription"
    # the continuation gap was COUNTED, never silent (gw2 had no ring
    # for this key at the missed ticks)
    assert stream.counters.get("resyncs", 0) \
        + stream.counters.get("forced_resyncs", 0) >= 1, \
        dict(stream.counters)
    print(f"fabric smoke[a]: gateway SIGKILL OK — stream hopped to "
          f"gw2, byte-equal at tick {latest['held']['snaptick']}, "
          f"resyncs counted ({stream.counters.get('resyncs', 0)})",
          file=sys.stderr)

    # ---- fault 1b: the killed gateway RESTARTS over its persisted
    # ring and resumes an old subscriber with a DELTA (hostlist rows
    # are stable: a resync here would mean continuation failed)
    gw1 = _spawn_gateway(gp1, ups, gp2, persist1, tmp)
    await wait_healthy(gp1, gw1, "gw1 restart")
    st, mtext = await _http(gp1, "GET", "/metrics")
    assert st == 200
    assert b"gyt_gw_sub_persist_restored_keys" in mtext, \
        "restarted gateway did not restore the persisted ring"
    sc3 = SubscribeClient()
    await sc3.connect("127.0.0.1", gp1)
    await sc3.subscribe(dict(q2), last_snaptick=t_kill)
    agen = sc3.events(stall_timeout=30.0)
    ev = await agen.__anext__()
    assert ev["t"] == "delta" and ev["base"] == t_kill, (
        f"restarted gateway answered {ev.get('t')!r} "
        f"(base {ev.get('base')}) — expected a delta from the "
        f"persisted ring at {t_kill}")
    resumed = D.apply_event(hl["held"], ev)
    st, rb = await _http(gp1, "GET", "/v1/hostlist?maxrecs=64")
    fresh_hl = json.loads(rb)
    if fresh_hl["snaptick"] == resumed["snaptick"]:
        assert json.dumps(resumed) == json.dumps(fresh_hl)
    await sc3.close()
    print("fabric smoke[a]: restart continuation OK — persisted ring "
          f"replayed a delta from tick {t_kill}", file=sys.stderr)

    # ---- fault 2: replica 0 WEDGED (stalled, not dead). Hedged
    # reads bound latency off replica 1; nothing errors.
    proxy.wedged = True
    wedge_lat = []
    body = json.dumps({"subsys": "hoststate", "maxrecs": 8,
                       "consistency": "strong"}).encode()
    for _ in range(20):
        t0 = time.monotonic()
        st, rb = await _http(gp2, "POST", "/query", body, timeout=15.0)
        assert st == 200, rb[:200]
        wedge_lat.append(time.monotonic() - t0)
        await asyncio.sleep(0.05)
    proxy.wedged = False
    wedge_lat.sort()
    p99w = wedge_lat[int(0.99 * (len(wedge_lat) - 1))]
    assert p99w < 3.0, f"wedged-replica p99 {p99w:.2f}s unbounded"
    st, mtext = await _http(gp2, "GET", "/metrics")
    hedges = [ln for ln in mtext.decode().splitlines()
              if ln.startswith("gyt_gw_hedged_requests_total")]
    assert hedges and float(hedges[0].split()[-1]) >= 1, \
        "wedge phase fired no hedges"
    print(f"fabric smoke[a]: wedged replica OK — 20/20 strong "
          f"queries, p99 {p99w * 1e3:.0f}ms, "
          f"hedges {float(hedges[0].split()[-1]):.0f}",
          file=sys.stderr)

    # ---- fault 3: replica 1 KILLED outright. The breaker opens
    # after K real failures (flap counted, visible in /metrics);
    # queries keep succeeding off replica 0.
    await servers[1].stop()
    for _ in range(10):
        st, rb = await _http(gp2, "POST", "/query", body, timeout=15.0)
        assert st == 200, rb[:200]
        await asyncio.sleep(0.1)
    r1label = f"127.0.0.1:{servers[1].port}"

    async def breaker_open():
        st, mtext = await _http(gp2, "GET", "/metrics")
        t = mtext.decode()
        return (f'gyt_gw_upstream_state{{state="down",'
                f'upstream="{r1label}"}} 1' in t
                or f'gyt_gw_upstream_state{{upstream="{r1label}",'
                f'state="down"}} 1' in t)

    t0 = time.monotonic()
    while time.monotonic() - t0 < 30.0:
        if await breaker_open():
            break
        await asyncio.sleep(0.3)
    else:
        raise AssertionError("dead replica never marked down in "
                             "gw2 /metrics")
    st, mtext = await _http(gp2, "GET", "/metrics")
    assert b"gyt_gw_upstream_flaps_total" in mtext, \
        "no flap counter in /metrics"
    print("fabric smoke[a]: replica kill OK — circuit open + flap "
          "counted in /metrics, queries kept succeeding",
          file=sys.stderr)

    # ---- final convergence: feed replica 0 only, every stream
    # byte-equal to the control
    for _ in range(2):
        await tick(only=0)
        await asyncio.sleep(0.5)
    await _until(lambda: latest["held"]["snaptick"]
                 == control["held"]["snaptick"], timeout=45.0,
                 msg="final convergence")
    assert json.dumps(latest["held"]) == json.dumps(control["held"]), \
        "post-fault stream diverged from the control subscription"

    pstop.set()
    await asyncio.sleep(0.2)
    ptask.cancel()
    assert not perrs, (
        f"{len(perrs)} queries surfaced upstream errors with a live "
        f"replica: {perrs[:3]}")
    lat.sort()
    p99 = lat[int(0.99 * (len(lat) - 1))] if lat else 0.0
    assert len(lat) >= 50, f"poller only completed {len(lat)} queries"
    assert p99 < 3.0, f"campaign-wide query p99 {p99:.2f}s unbounded"
    print(f"fabric smoke[a]: OK — {len(lat)} polled queries, 0 "
          f"upstream errors, p99 {p99 * 1e3:.0f}ms, stream "
          f"counters {dict(stream.counters)}", file=sys.stderr)

    stream.stop()
    for t in (stask, ctl_task, hl_task):
        t.cancel()
    await ctl.close()
    await sc2.close()
    for p in (gw1, gw2):
        if p.poll() is None:
            p.terminate()
            try:
                p.wait(timeout=15)
            except subprocess.TimeoutExpired:
                p.kill()
    await proxy.stop()
    for srv in servers:
        if srv._server is not None:
            await srv.stop()


# ======================================================== phase B

N_SHARDS = 2
N_PROCS = 2


def _serve_env():
    return dict(
        os.environ, JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count="
                  f"{N_SHARDS}",
        GYT_N_HOSTS="16", GYT_SVC_CAPACITY="256",
        GYT_TASK_CAPACITY="256", GYT_CONN_BATCH="256",
        GYT_RESP_BATCH="512", GYT_LISTENER_BATCH="64", GYT_FOLD_K="2",
        GYT_DEP_PAIR_CAPACITY="2048", GYT_DEP_EDGE_CAPACITY="1024")


def _metric_value(text: str, prefix: str) -> float:
    total = 0.0
    for ln in text.splitlines():
        if ln.startswith(prefix) and not ln.startswith("# "):
            total += float(ln.split()[-1])
    return total


async def phase_b(tmp: str) -> None:
    from gyeeta_tpu.net.agent import NetAgent, QueryClient
    from gyeeta_tpu.net.subs import SubscribeStream

    port = _free_port()
    waldir = os.path.join(tmp, "wal")
    env = _serve_env()
    cmd = [sys.executable, "-m", "gyeeta_tpu", "serve",
           "--host", "127.0.0.1", "--port", str(port),
           "--shards", str(N_SHARDS), "--ingest-procs", str(N_PROCS),
           "--journal-dir", waldir,
           "--hostmap", os.path.join(tmp, "hostmap.json"),
           "--tick-interval", "0.5",
           "--handshake-timeout", "5", "--idle-timeout", "600",
           "--stats-interval", "60", "--log-level", "WARNING"]
    proc = subprocess.Popen(cmd, cwd=HERE, env=env)
    stop = asyncio.Event()
    tasks: list = []

    async def query(req, deadline_s=300.0):
        # fresh conn per call, retried against a DEADLINE: a cold-
        # cache serve loop blocks while mesh programs compile, so
        # individual requests time out without anything being wrong
        # — a shared conn would also desync after the first timeout
        last = None
        t0 = time.monotonic()
        while time.monotonic() - t0 < deadline_s:
            if proc.poll() is not None:
                raise AssertionError(
                    f"serve exited rc={proc.returncode}")
            c = QueryClient(connect_timeout=10.0,
                            request_timeout=120.0)
            try:
                await c.connect("127.0.0.1", port)
                return await c.query(dict(req))
            except Exception as e:      # noqa: BLE001 — retried
                last = e
                await asyncio.sleep(3.0)
            finally:
                await c.close()
        raise AssertionError(f"query {req} kept failing: {last}")

    try:
        deadline = time.monotonic() + 600.0
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise AssertionError(
                    f"serve exited early rc={proc.returncode}")
            try:
                c = QueryClient(connect_timeout=2.0,
                                 request_timeout=30.0)
                await c.connect("127.0.0.1", port)
                await c.query({"subsys": "serverstatus"})
                await c.close()
                break
            except Exception:
                await asyncio.sleep(1.0)
        else:
            raise AssertionError("serve never became ready")

        # supervised agents on BOTH shard groups (sticky hids 0/1)
        agents = [NetAgent(machine_id=0x7B21 + i, seed=33 + i,
                           n_svcs=3, connect_timeout=420.0,
                           spool_max_bytes=1 << 20)
                  for i in range(2)]
        tasks = [asyncio.create_task(a.run_forever(
            "127.0.0.1", port, interval=0.5, n_conn=32, n_resp=32,
            backoff_base=0.2, backoff_cap=1.0, stop=stop))
            for a in agents]

        # the combined load: a SUPERVISED subscription through the
        # kill (reconnects across compile stalls with last_snaptick)
        stream = SubscribeStream(
            [("127.0.0.1", port)],
            {"subsys": "hoststate", "maxrecs": 16},
            stall_timeout=90.0, backoff_base=1.0)
        sub = {"held": None, "n": 0}

        async def sub_loop():
            async for held in stream.responses():
                sub["held"] = held
                sub["n"] += 1

        sub_task = asyncio.create_task(sub_loop())

        async def metrics_text():
            out = await query({"subsys": "metrics"})
            return out["text"]

        # wait until both hosts fold and the worker pid gauges are up
        async def pids():
            t = await metrics_text()
            out = {}
            for ln in t.splitlines():
                if ln.startswith("gyt_ingest_proc_pid{"):
                    w = ln.split('proc="')[1].split('"')[0]
                    out[w] = int(float(ln.split()[-1]))
            return out

        t0 = time.monotonic()
        while time.monotonic() - t0 < 300.0:
            hosts = await query({"subsys": "hoststate",
                                  "maxrecs": 16})
            if (hosts.get("nrecs", 0) >= 2
                    and len(await pids()) == N_PROCS
                    and sub["n"] >= 1):
                break
            await asyncio.sleep(1.0)
        else:
            raise AssertionError("phase b never reached steady state")

        # ---- SIGKILL one ingest worker mid-feed, targeted from
        # OUTSIDE via the pid gauge (the operator's path)
        p0 = await pids()
        victim = p0["0"]
        os.kill(victim, signal.SIGKILL)
        t0 = time.monotonic()
        while time.monotonic() - t0 < 120.0:
            t = await metrics_text()
            cur = await pids()
            if (_metric_value(t, "gyt_ingest_proc_respawns_total"
                              '{proc="0"}') >= 1
                    and cur.get("0") and cur["0"] != victim):
                break
            await asyncio.sleep(1.0)
        else:
            raise AssertionError("worker never respawned after "
                                 "SIGKILL")
        await asyncio.sleep(4.0)        # reconnects + fresh sweeps

        # ---- the cross-process ledger closes EXACTLY (zero silent
        # record loss across the SIGKILL window). The supervisor
        # folds worker-counter deltas at ~1s cadence, so poll.
        stop.set()
        await asyncio.wait_for(asyncio.gather(*tasks), 30.0)
        tasks = []
        ledger = None
        t0 = time.monotonic()
        while time.monotonic() - t0 < 60.0:
            t = await metrics_text()
            published = _metric_value(
                t, "gyt_ingest_proc_published_records_total")
            consumed = _metric_value(
                t, "gyt_ingest_ring_consumed_records_total")
            dropped = _metric_value(
                t, "gyt_ingest_ring_dropped_records")
            ledger = (published, consumed, dropped)
            if published > 0 and published == consumed + dropped:
                break
            await asyncio.sleep(1.0)
        else:
            raise AssertionError(
                f"ring ledger never closed: published={ledger[0]} "
                f"consumed={ledger[1]} dropped={ledger[2]}")

        # both hosts present after the kill; the subscriber's
        # reassembled view matches a fresh render at its tick
        hosts = await query({"subsys": "hoststate", "maxrecs": 16})
        assert hosts.get("nrecs", 0) >= 2, hosts
        ok = False
        for _ in range(20):
            fresh = await query({"subsys": "hoststate",
                                 "maxrecs": 16,
                                 "consistency": "snapshot"})
            if sub["held"] is not None and \
                    fresh.get("snaptick") == sub["held"].get(
                        "snaptick"):
                assert json.dumps(sub["held"]) == json.dumps(
                    json.loads(json.dumps(fresh)))
                ok = True
                break
            await asyncio.sleep(0.5)
        assert ok, "subscriber never aligned with a fresh render"
        stream.stop()
        sub_task.cancel()
        print(f"fabric smoke[b]: worker SIGKILL OK — respawned, "
              f"ledger exact (published={ledger[0]:.0f} == "
              f"consumed={ledger[1]:.0f} + dropped={ledger[2]:.0f}), "
              f"subscription byte-equal through the kill",
              file=sys.stderr)

        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=120) == 0, \
            f"serve shutdown rc={proc.returncode}"

        # ---- compaction worker death at a shard boundary: the
        # parallel pass fails LOUDLY, the store stays consistent, a
        # rerun converges (no --checkpoint-dir → the full WAL
        # survived the SIGTERM for offline compaction)
        shdir = os.path.join(tmp, "shards")
        base = [sys.executable, "-m", "gyeeta_tpu", "compact", "run",
                "--journal-dir", waldir, "--shard-dir", shdir,
                "--procs", str(N_PROCS), "--window-ticks", "4"]
        env_die = dict(_serve_env(),
                       GYT_COMPACT_DIE_SHARD="1")
        r = subprocess.run(base, cwd=HERE, env=env_die,
                           capture_output=True, timeout=600)
        assert r.returncode != 0, \
            "compaction worker death did not fail the pass loudly"
        env_ok = _serve_env()
        r2 = subprocess.run(base, cwd=HERE, env=env_ok,
                            capture_output=True, timeout=600)
        assert r2.returncode == 0, r2.stderr[-2000:]
        r3 = subprocess.run(
            [sys.executable, "-m", "gyeeta_tpu", "compact", "list",
             "--shard-dir", shdir], cwd=HERE, env=env_ok,
            capture_output=True, timeout=120)
        assert r3.returncode == 0, r3.stderr[-1000:]
        listing = json.loads(r3.stdout)
        assert listing.get("shards"), \
            f"no windows in the converged store: {listing}"
        print(f"fabric smoke[b]: compaction worker death OK — pass "
              f"failed loudly (rc={r.returncode}), rerun converged "
              f"({len(listing['shards'])} window(s))",
              file=sys.stderr)
    finally:
        stop.set()
        for t in tasks:
            t.cancel()
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                proc.kill()


def main() -> int:
    which = sys.argv[1] if len(sys.argv) > 1 else "ab"
    tmp = tempfile.mkdtemp(prefix="gyt_fabric_smoke_")
    try:
        if "a" in which:
            os.makedirs(os.path.join(tmp, "a"), exist_ok=True)
            asyncio.run(phase_a(os.path.join(tmp, "a")))
        if "b" in which:
            os.makedirs(os.path.join(tmp, "b"), exist_ok=True)
            asyncio.run(phase_b(os.path.join(tmp, "b")))
    finally:
        import shutil
        shutil.rmtree(tmp, ignore_errors=True)
    print("fabric smoke: OK")
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except AssertionError as e:
        print(f"fabric smoke: FAIL — {e}", file=sys.stderr)
        sys.exit(1)
