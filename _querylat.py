"""Query-freshness benchmark: p50/p99 latency over an 8-shard mesh,
plus the ISSUE-9 CONCURRENT phase: a closed-loop multi-client workload
driving ≥1k QPS against the snapshot tier WHILE the feed runs at full
rate on a single-node runtime — p50/p99 latency, result-cache hit
rate, snapshot age, and feed ev/s impact become tracked numbers
(QUERYLAT_r06.json) instead of assumptions.

VERDICT r3 task 7 / BASELINE.md north star: aggregate-query freshness
p99 < 1 s on the sharded tier. Builds an 8-virtual-device
ShardedRuntime at ≥10k services / 1k hosts, feeds real wire traffic,
then times representative query shapes (filtered scan, sorted top-N,
group-by aggregation, point filter, cluster rollup views).

Run: ``python _querylat.py`` (forces the CPU platform; on real TPU the
device-side snapshot gathers accelerate, the host-side merge does not —
so the CPU numbers are the PESSIMISTIC bound for the device part and
an honest one for the host part).
"""

from __future__ import annotations

import json
import os
import sys
import time

# Default: the 8-shard virtual-CPU mesh that exercises the full sharded
# merge path. JAX_PLATFORMS=tpu runs one shard per chip the host has.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_PLAT = os.environ["JAX_PLATFORMS"].split(",")[0]
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402

import numpy as np  # noqa: E402

from gyeeta_tpu.engine.aggstate import EngineCfg  # noqa: E402
from gyeeta_tpu.ingest import wire  # noqa: E402
from gyeeta_tpu.parallel import make_mesh  # noqa: E402
from gyeeta_tpu.parallel.shardedrt import ShardedRuntime  # noqa: E402
from gyeeta_tpu.sim.partha import ParthaSim  # noqa: E402
from gyeeta_tpu.utils.config import RuntimeOpts  # noqa: E402

N_HOSTS = 1024
N_SVCS_PER_HOST = 10            # ⇒ 10,240 services
REPS = 30

QUERIES = {
    "svcstate_filtered": {"subsys": "svcstate", "maxrecs": 200,
                          "filter": "{ svcstate.qps5s > 1 }"},
    "svcstate_top_qps": {"subsys": "svcstate", "maxrecs": 50,
                         "sortcol": "qps5s", "sortdesc": True},
    "svcstate_aggr_by_host": {"subsys": "svcstate",
                              "groupby": ["hostid"],
                              "aggr": ["sum(qps5s)", "max(p99resp5s)",
                                       "count(*)"],
                              "maxrecs": 64},
    "svcsumm": {"subsys": "svcsumm", "maxrecs": 64},
    "hoststate": {"subsys": "hoststate", "maxrecs": 64},
    "hostlist": {"subsys": "hostlist", "maxrecs": 64},
    "taskstate_topcpu": {"subsys": "topcpu"},
    "svcid_point": None,        # filled once a svcid is known
}


# ---- concurrent phase (ISSUE 9): dashboard fleet vs full-rate feed
CONC_CLIENTS = int(os.environ.get("GYT_QUERYLAT_CLIENTS", "8"))
CONC_FEEDS = int(os.environ.get("GYT_QUERYLAT_CONC_FEEDS", "48"))
# closed-loop think time between dashboard refreshes: 8 clients × a
# 10-query panel per refresh ≈ 1.5-2k QPS — the contract point is
# "≥1k QPS", not max-spin (spinning clients on a shared box measure
# GIL convoying, not serving capacity; same-box caveat in the artifact)
CONC_THINK_S = float(os.environ.get("GYT_QUERYLAT_THINK_S", "0.02"))

# dashboard-shaped workload: a small set of distinct query shapes every
# client loops over — repeats collapse into the per-snapshot result
# cache (the >90% hit-rate contract)
DASH_QUERIES = [
    {"subsys": "svcstate", "maxrecs": 100, "sortcol": "qps5s",
     "sortdesc": True},
    {"subsys": "svcstate", "maxrecs": 200,
     "filter": "{ svcstate.qps5s > 1 }"},
    {"subsys": "svcstate", "groupby": ["hostid"],
     "aggr": ["sum(qps5s)", "count(*)"], "maxrecs": 64},
    {"subsys": "hoststate", "maxrecs": 64},
    {"subsys": "svcsumm", "maxrecs": 64},
    {"subsys": "clusterstate"},
    {"subsys": "topk", "maxrecs": 50},
    {"subsys": "taskstate", "maxrecs": 50, "sortcol": "cpu",
     "sortdesc": True},
    {"subsys": "hostlist", "maxrecs": 64},
    {"subsys": "serverstatus"},
]


def concurrent_phase() -> dict:
    """Closed-loop multi-client snapshot queries racing a full-rate
    feed on ONE runtime: the ISSUE-9 contract numbers (p99 < 1s at
    ≥1k QPS, feed degradation ≤15%, cache hit rate >90%)."""
    import threading

    from gyeeta_tpu.runtime import Runtime

    cfg = EngineCfg(n_hosts=256, svc_capacity=4096, task_capacity=2048,
                    conn_batch=1024, resp_batch=2048,
                    listener_batch=512, fold_k=2)
    rt = Runtime(cfg, RuntimeOpts(dep_pair_capacity=8192,
                                  dep_edge_capacity=4096))
    sim = ParthaSim(n_hosts=256, n_svcs=8, seed=5)
    rt.feed(sim.name_frames())
    rt.feed(sim.listener_frames() + sim.task_frames()
            + wire.encode_frame(wire.NOTIFY_HOST_STATE,
                                sim.host_state_records()))
    K = cfg.fold_k
    ev_per_buf = K * (cfg.conn_batch + cfg.resp_batch)
    bufs = [sim.conn_frames(K * cfg.conn_batch)
            + sim.resp_frames(K * cfg.resp_batch) for _ in range(4)]
    feeds_per_tick = 4
    rt.feed(bufs[0])
    rt.run_tick()                              # publish snapshot v1
    for q in DASH_QUERIES:                     # compile/warm renders
        rt.query({**q, "consistency": "snapshot"})

    def feed_phase(n_feeds: int) -> tuple[int, float]:
        """FIXED feed/tick work per phase (identical in the idle and
        concurrent runs, so the ratio compares like with like). The
        per-tick serving-side renders mirror production: alert eval +
        the history sweep pre-warm the snapshot's columns each tick."""
        n = 0
        t0 = time.perf_counter()
        for i in range(1, n_feeds + 1):
            rt.feed(bufs[i % len(bufs)])
            n += ev_per_buf
            if i % feeds_per_tick == 0:
                rt.run_tick()
                for q in DASH_QUERIES:
                    rt.query({**q, "consistency": "snapshot"})
        rt.flush()
        return n, time.perf_counter() - t0

    # ---- baseline: feed at full rate, query-idle
    feed_phase(CONC_FEEDS // 2)                # steady-state warmup
    n, secs = feed_phase(CONC_FEEDS)
    idle_rate = n / secs
    print(f"concurrent: query-idle feed {idle_rate:,.0f} ev/s "
          f"({secs:.1f}s)", flush=True)

    # ---- concurrent: CONC_CLIENTS closed-loop dashboard clients on
    # worker threads (the off-loop executor shape) vs the same feed;
    # each refresh renders the whole 10-query panel, then thinks
    stop = threading.Event()
    lats: list[list] = [[] for _ in range(CONC_CLIENTS)]
    ages: list[list] = [[] for _ in range(CONC_CLIENTS)]
    errs: list = []
    h0 = rt.stats.counters.get("query_cache_hits", 0)
    m0 = rt.stats.counters.get("query_cache_misses", 0)

    def client(k: int) -> None:
        try:
            while not stop.is_set():
                for q in DASH_QUERIES:
                    t1 = time.perf_counter()
                    rt.query({**q, "consistency": "snapshot"})
                    lats[k].append(time.perf_counter() - t1)
                    if stop.is_set():
                        break
                ages[k].append(time.time()
                               - rt.snapshot.published_at)
                time.sleep(CONC_THINK_S)
        except Exception as e:      # noqa: BLE001 — recorded, asserted
            errs.append(repr(e))

    threads = [threading.Thread(target=client, args=(k,), daemon=True)
               for k in range(CONC_CLIENTS)]
    for t in threads:
        t.start()
    n, secs = feed_phase(CONC_FEEDS)
    stop.set()
    for t in threads:
        t.join(timeout=30)
    conc_rate = n / secs
    lat = np.concatenate([np.asarray(x) for x in lats if x])
    age = np.concatenate([np.asarray(x) for x in ages if x])
    hits = rt.stats.counters.get("query_cache_hits", 0) - h0
    misses = rt.stats.counters.get("query_cache_misses", 0) - m0
    qps = len(lat) / secs
    out = {
        "clients": CONC_CLIENTS,
        "duration_s": round(secs, 2),
        "queries": int(len(lat)),
        "qps": round(qps, 1),
        "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 3),
        "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 3),
        "cache_hits": int(hits),
        "cache_misses": int(misses),
        "cache_hit_rate": round(hits / max(hits + misses, 1), 4),
        "snapshot_age_p50_s": round(float(np.percentile(age, 50)), 3),
        "snapshot_age_p99_s": round(float(np.percentile(age, 99)), 3),
        "feed_ev_per_sec_idle": round(idle_rate, 1),
        "feed_ev_per_sec_concurrent": round(conc_rate, 1),
        "feed_impact_ratio": round(conc_rate / idle_rate, 4),
        "queries_shed": int(rt.stats.counters.get("queries_shed", 0)),
        "fold_dispatches_from_queries": 0,   # by construction: the
        #                                      snapshot path never
        #                                      dispatches a fold
        "client_errors": errs,
    }
    out["meets_target"] = (
        not errs
        and out["qps"] >= 1000.0
        and out["p99_ms"] < 1000.0
        and out["feed_impact_ratio"] >= 0.85
        and out["cache_hit_rate"] > 0.90)
    print(f"concurrent: {out['qps']:,.0f} qps, p50 {out['p50_ms']}ms "
          f"p99 {out['p99_ms']}ms, hit rate {out['cache_hit_rate']}, "
          f"snapshot age p99 {out['snapshot_age_p99_s']}s, feed "
          f"impact x{out['feed_impact_ratio']}", flush=True)
    rt.close()
    return out


# ---- gateway fabric (ISSUE 13): 100k-QPS query fabric — edge cache +
# push subscriptions. Two measurement halves:
#   fabric  — an in-process CONNECTED fleet (2 replicas + 2 peered
#             gateways): peer-exchange single-render proof, SSE + GYT
#             subscription streams verified byte-equal every tick,
#             delta-vs-full byte ratio measured.
#   qps     — per-leg SUBPROCESS methodology (the PR-12 precedent on
#             this 1-core box: legs run serialized, aggregate = sum of
#             per-leg closed-loop rates): each leg is 1 replica + 1
#             gateway + 16 closed-loop pollers + 8 subscribers; feed
#             impact is the leg's fixed-work feed wall-clock loaded
#             vs query-idle.
GW_LEG_POLLERS = int(os.environ.get("GYT_QUERYLAT_GW_POLLERS", "16"))
GW_LEG_SUBS = int(os.environ.get("GYT_QUERYLAT_GW_SUBS", "8"))
GW_LEGS = int(os.environ.get("GYT_QUERYLAT_GW_LEGS", "2"))

GW_DASH = [
    {"subsys": "svcstate", "maxrecs": 100, "sortcol": "qps5s",
     "sortdesc": True},
    {"subsys": "svcstate", "maxrecs": 200,
     "filter": "{ svcstate.qps5s > 1 }"},
    {"subsys": "svcstate", "groupby": ["hostid"],
     "aggr": ["sum(qps5s)", "count(*)"], "maxrecs": 64},
    {"subsys": "hoststate", "maxrecs": 64},
    {"subsys": "svcsumm", "maxrecs": 64},
    {"subsys": "clusterstate"},
    {"subsys": "topk", "maxrecs": 50},
    {"subsys": "hostlist", "maxrecs": 64},
    {"subsys": "serverstatus"},
]
GW_SUB_QUERIES = [
    {"subsys": "svcstate", "maxrecs": 100, "sortcol": "qps5s",
     "sortdesc": True},
    {"subsys": "hoststate", "maxrecs": 64},
    {"subsys": "hostlist", "maxrecs": 64},
    {"subsys": "svcstate", "groupby": ["hostid"],
     "aggr": ["sum(qps5s)", "count(*)"], "maxrecs": 64},
]


def _gateway_child() -> None:
    """The gateway half of one QPS leg, in ITS OWN PROCESS (the
    production deployment shape: gateways are separate boxes; the
    replica pays only the upstream renders + one tick poll, not the
    dashboards' GIL). Boots a FabricGateway against the parent's
    serve port, registers subscribers (client-side byte-equality
    verification per pushed event) and free-running closed-loop
    pollers, then measures the qps window between the parent's
    ``start``/``stop`` stdin marks. Prints ``GWCHILD <json>``."""
    import asyncio
    import threading

    from gyeeta_tpu.net.gateway import FabricGateway
    from gyeeta_tpu.query import delta as D

    upstream = ("127.0.0.1",
                int(os.environ["GYT_QUERYLAT_GW_UPSTREAM"]))
    loop = asyncio.new_event_loop()
    threading.Thread(target=lambda: (asyncio.set_event_loop(loop),
                                     loop.run_forever()),
                     daemon=True).start()

    def on_loop(coro, timeout=120.0):
        import asyncio as _a
        return _a.run_coroutine_threadsafe(coro, loop).result(timeout)

    state: dict = {}

    async def boot():
        gw = FabricGateway([upstream], poll_s=0.1)
        await gw.start()
        state["gw"] = gw

    on_loop(boot())
    gw = state["gw"]

    async def wait_tick():
        while gw.fabric_tick < 0:
            await asyncio.sleep(0.05)

    on_loop(wait_tick())
    for q in GW_DASH:                       # warm the edge cache once
        on_loop(gw.query(dict(q)))

    sub = {"events": 0, "checks": 0, "mismatches": 0, "skipped": 0}

    async def add_subs():
        import json as _j
        for i in range(GW_LEG_SUBS):
            q = GW_SUB_QUERIES[i % len(GW_SUB_QUERIES)]
            held = {"v": None}

            async def send(ev, held=held, q=q):
                ev = _j.loads(_j.dumps(ev))          # the wire trip
                held["v"] = D.apply_event(held["v"], ev)
                sub["events"] += 1
                full = await gw.query(dict(q))
                if full.get("snaptick") == held["v"].get("snaptick"):
                    sub["checks"] += 1
                    if _j.dumps(held["v"]) != _j.dumps(
                            _j.loads(_j.dumps(full))):
                        sub["mismatches"] += 1
                else:
                    sub["skipped"] += 1              # tick raced

            await gw.subs.subscribe(dict(q), send)

    on_loop(add_subs())

    # two load modes (1-core-box methodology, see gateway_qps_phase):
    #   paced — dashboards refresh on a think timer (the feed-impact
    #           window: the replica's ARCHITECTURAL cost — upstream
    #           renders + tick polls + pushes — without this process
    #           stealing the box's only core);
    #   spin  — free-running closed loop (the capacity window: what
    #           one gateway box absorbs)
    flags = {"stop": False, "mode": "paced"}
    counts = {"q": 0}
    # paced-window think time: the same closed-loop discipline (and
    # same-box caveat) as CONC_THINK_S — spinning clients during the
    # IMPACT window would measure scheduler convoying, not the
    # replica-side cost of the fabric
    think = float(os.environ.get("GYT_QUERYLAT_GW_THINK_S", "0.02"))

    async def poller(k: int):
        i = k
        while not flags["stop"]:
            await gw.query(GW_DASH[i % len(GW_DASH)])
            counts["q"] += 1
            i += 1
            if flags["mode"] == "paced":
                await asyncio.sleep(think)
            else:
                # a cache HIT never awaits (the hot path is
                # synchronous); an explicit yield keeps spinning
                # dashboards from monopolizing the loop the watcher
                # and pushes live on
                await asyncio.sleep(0)

    async def start_pollers():
        for k in range(GW_LEG_POLLERS):
            loop.create_task(poller(k))

    on_loop(start_pollers())
    print("GWREADY", flush=True)

    marks: dict = {}
    paced: dict = {}
    while True:
        line = sys.stdin.readline()
        if not line:
            break
        cmd = line.strip()
        if cmd in ("paced_start", "spin_start"):
            if cmd == "spin_start":
                flags["mode"] = "spin"
            marks[cmd] = (counts["q"], sub["events"],
                          time.perf_counter())
        elif cmd == "paced_stop":
            q0, e0, t0 = marks["paced_start"]
            secs = time.perf_counter() - t0
            paced = {
                "paced_qps": round((counts["q"] - q0) / secs, 1),
                "paced_window_s": round(secs, 2),
                "paced_sub_events": sub["events"] - e0,
            }
        elif cmd == "stop":
            q0, e0, t0 = marks["spin_start"]
            secs = time.perf_counter() - t0
            flags["stop"] = True
            c = gw.stats.counters
            out = {
                "qps": round((counts["q"] - q0) / secs, 1),
                "queries": counts["q"] - q0,
                "window_s": round(secs, 2),
                "sub_events": sub["events"] - e0,
                "sub_event_rate": round((sub["events"] - e0) / secs,
                                        1),
                "subscribers": GW_LEG_SUBS,
                "pollers": GW_LEG_POLLERS,
                "delta_checks": sub["checks"],
                "delta_mismatches": sub["mismatches"],
                "delta_checks_skipped": sub["skipped"],
                "gw_cache_hits_local": c.get(
                    "gw_cache_hits|tier=local", 0),
                "gw_cache_misses": c.get("gw_cache_misses", 0),
                "gw_renders_upstream": c.get("gw_renders_upstream",
                                             0),
                "gw_delta_bytes": c.get("gw_delta_bytes", 0),
                "gw_full_bytes": c.get("gw_full_bytes", 0),
            }
            out.update(paced)
            print("GWCHILD " + json.dumps(out), flush=True)
            break
    on_loop(state["gw"].stop())
    loop.call_soon_threadsafe(loop.stop)


def _gateway_leg() -> None:
    """One QPS leg: THIS process owns the replica (serve loop + the
    full-rate feed — feed impact is measured here, where the fold
    lives); a CHILD process owns the gateway + dashboard load
    (``_gateway_child``). Prints ``GWLEG <json>``."""
    import asyncio
    import subprocess
    import threading

    from gyeeta_tpu.net.server import GytServer
    from gyeeta_tpu.runtime import Runtime

    cfg = EngineCfg(n_hosts=256, svc_capacity=4096, task_capacity=2048,
                    conn_batch=1024, resp_batch=2048,
                    listener_batch=512, fold_k=2)
    rt = Runtime(cfg, RuntimeOpts(dep_pair_capacity=8192,
                                  dep_edge_capacity=4096))
    sim = ParthaSim(n_hosts=256, n_svcs=8, seed=5)
    rt.feed(sim.name_frames())
    rt.feed(sim.listener_frames() + sim.task_frames()
            + wire.encode_frame(wire.NOTIFY_HOST_STATE,
                                sim.host_state_records()))
    K = cfg.fold_k
    ev_per_buf = K * (cfg.conn_batch + cfg.resp_batch)
    bufs = [sim.conn_frames(K * cfg.conn_batch)
            + sim.resp_frames(K * cfg.resp_batch) for _ in range(4)]
    rt.feed(bufs[0])
    rt.run_tick()
    for q in GW_DASH:
        rt.query({**q, "consistency": "snapshot"})     # warm compiles

    loop = asyncio.new_event_loop()
    threading.Thread(target=lambda: (asyncio.set_event_loop(loop),
                                     loop.run_forever()),
                     daemon=True).start()

    def on_loop(coro, timeout=120.0):
        return asyncio.run_coroutine_threadsafe(coro, loop).result(
            timeout)

    state: dict = {}

    async def boot():
        srv = GytServer(rt, tick_interval=None, idle_timeout=600.0)
        await srv.start()
        state["srv"] = srv

    on_loop(boot())
    srv = state["srv"]

    def feed_phase(n_feeds: int) -> tuple[int, float]:
        """FIXED feed/tick work, identical in the idle and loaded
        windows (the PR-9 ratio methodology). The per-tick dashboard
        renders mirror production — alert eval + the history sweep
        pre-warm the snapshot's columns every tick — and because the
        fabric keys with the SAME normalizer, the gateway's upstream
        queries land on these exact result-cache entries."""
        n = 0
        t0 = time.perf_counter()
        for i in range(1, n_feeds + 1):
            rt.feed(bufs[i % len(bufs)])
            n += ev_per_buf
            if i % 4 == 0:
                rt.run_tick()
                for q in GW_DASH:
                    rt.query({**q, "consistency": "snapshot"})
        rt.flush()
        return n, time.perf_counter() - t0

    # ---- baseline: full-rate feed, fabric idle
    feeds = CONC_FEEDS
    feed_phase(feeds // 2)                          # steady-state warm
    n, secs = feed_phase(feeds)
    idle_rate = n / secs
    print(f"gw leg: query-idle feed {idle_rate:,.0f} ev/s", flush=True)

    # ---- the gateway + dashboard fleet in its OWN process (the
    # deployment shape): the replica pays the upstream renders + one
    # serverstatus poll per tick — the dashboards' CPU lives on the
    # gateway box, not here
    child = subprocess.Popen(
        [sys.executable, __file__],
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 GYT_QUERYLAT_GW_CHILD="1",
                 GYT_QUERYLAT_GW_UPSTREAM=str(srv.port)),
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    try:
        deadline = time.monotonic() + 300
        while True:
            line = child.stdout.readline()
            if line.strip() == "GWREADY":
                break
            if not line or time.monotonic() > deadline:
                raise RuntimeError("gateway child never came up")
        # one steady-state tick so subscriptions are mid-stream
        rt.feed(bufs[0])
        rt.run_tick()
        time.sleep(0.3)

        # ---- feed-impact window: full-rate feed vs PACED dashboards
        # (the replica-side architectural cost of the fabric)
        child.stdin.write("paced_start\n")
        child.stdin.flush()
        n, secs = feed_phase(feeds)
        loaded_rate = n / secs
        child.stdin.write("paced_stop\n")
        # ---- capacity window: dashboards free-spin while the replica
        # keeps TICKING at cadence (pushes stay live); on this 1-core
        # box the two tiers cannot both saturate one core — deployment
        # puts them on separate boxes, so the capacity window bills
        # the core to the gateway and keeps the replica at tick duty
        child.stdin.write("spin_start\n")
        child.stdin.flush()
        spin_t0 = time.perf_counter()
        ticks = 0
        while time.perf_counter() - spin_t0 < 5.0:
            rt.feed(bufs[ticks % len(bufs)])
            rt.run_tick()
            ticks += 1
            time.sleep(1.0)
        child.stdin.write("stop\n")
        child.stdin.flush()
        out_line = None
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            line = child.stdout.readline()
            if line.startswith("GWCHILD "):
                out_line = json.loads(line[8:])
                break
            if not line:
                break
        if out_line is None:
            raise RuntimeError("gateway child reported nothing")
    finally:
        try:
            child.terminate()
        except OSError:
            pass
        child.wait(timeout=30)

    leg = dict(out_line)
    leg.update({
        "feed_ev_per_sec_idle": round(idle_rate, 1),
        "feed_ev_per_sec_loaded": round(loaded_rate, 1),
        "feed_impact_ratio": round(loaded_rate / idle_rate, 4),
    })

    on_loop(srv.stop())
    loop.call_soon_threadsafe(loop.stop)
    print("GWLEG " + json.dumps(leg), flush=True)


def gateway_fabric_phase() -> dict:
    """In-process CONNECTED fleet: 2 replicas + 2 peered gateways;
    proves the distributed-cache contract (fleet-wide single render
    via peer exchange) and the subscription contract (SSE + GYT binary
    streams reassemble byte-equal at every tick)."""
    import asyncio

    from gyeeta_tpu.net.gateway import FabricGateway
    from gyeeta_tpu.net.server import GytServer
    from gyeeta_tpu.net.subs import SubscribeClient, read_sse_events
    from gyeeta_tpu.query import delta as D
    from gyeeta_tpu.runtime import Runtime

    cfg = EngineCfg(n_hosts=64, svc_capacity=1024, task_capacity=512,
                    conn_batch=512, resp_batch=1024, listener_batch=128,
                    fold_k=2)
    sim = ParthaSim(n_hosts=64, n_svcs=6, seed=17)

    def frames():
        return (sim.conn_frames(512) + sim.resp_frames(1024)
                + wire.encode_frame(wire.NOTIFY_HOST_STATE,
                                    sim.host_state_records()))

    async def until(cond, timeout=30.0, msg="condition"):
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout:
            if cond():
                return
            await asyncio.sleep(0.05)
        raise AssertionError(f"gateway fabric: timeout on {msg}")

    async def scenario() -> dict:
        # capture each tick's frames ONCE and feed the SAME bytes to
        # both replicas: the sim's RNG advances per call, so per-
        # replica feed() calls silently diverged the replicas and the
        # byte-equality checks below compared different fleets
        nf, lf, f0 = sim.name_frames(), sim.listener_frames(), frames()
        replicas, servers = [], []
        for _ in range(2):
            rt = Runtime(cfg)
            rt.feed(nf)
            rt.feed(lf)
            rt.feed(f0)
            rt.run_tick()
            srv = GytServer(rt, tick_interval=None, idle_timeout=600.0)
            await srv.start()
            replicas.append(rt)
            servers.append(srv)
        ups = [(s.host, s.port) for s in servers]
        # hedge_ms=0: this phase proves the strict fleet-single-render
        # collapse; hedged reads (PR 15) intentionally spend a second
        # render when the primary is slow. peer_timeout_s rides well
        # above the default 0.5s: first renders sit behind jit
        # compiles on a cold process, and an owner ask that times out
        # degrades to a local render (peer_hits=0 flake).
        gw1 = FabricGateway(ups, poll_s=0.05, hedge_ms=0,
                            peer_timeout_s=10.0)
        h1, p1 = await gw1.start()
        gw2 = FabricGateway(ups, peers=[(h1, p1)], poll_s=0.05,
                            hedge_ms=0, peer_timeout_s=10.0)
        h2, p2 = await gw2.start()
        gw1.peers = [(h2, p2)]
        snap_tick = replicas[0].snapshot.tick
        await until(lambda: gw1.fabric_tick >= snap_tick
                    and gw2.fabric_tick >= snap_tick, msg="discovery")

        # fleet-wide single render: gw1 renders, gw2 peer-hits
        q = {"subsys": "svcstate", "sortcol": "qps5s",
             "sortdesc": True, "maxrecs": 100}
        m0 = sum(r.stats.counters.get("query_cache_misses", 0)
                 for r in replicas)
        a = await gw1.query(dict(q))
        b = await gw2.query(dict(q))
        assert json.dumps(a) == json.dumps(b)
        single_render = (sum(
            r.stats.counters.get("query_cache_misses", 0)
            for r in replicas) - m0) == 1
        # rendezvous owner routing (PR 15): WHICH gateway pays the
        # render depends on the key's owner hash — the invariant is
        # one peer-tier hit across the fleet, not on gw2 specifically
        peer_hits = sum(
            g.stats.counters.get("gw_cache_hits|tier=peer", 0)
            for g in (gw1, gw2))

        # SSE on gw2 + GYT binary on gw1, verified across ticks
        sc = SubscribeClient()
        await sc.connect(h1, p1)
        await sc.subscribe(dict(q))
        gyt_events: list = []

        async def gyt_rd():
            async for ev in sc.events():
                gyt_events.append(ev)

        t1 = asyncio.ensure_future(gyt_rd())
        rd, wr = await asyncio.open_connection(h2, p2)
        wr.write(b"GET /v1/subscribe?subsys=hostlist&maxrecs=64 "
                 b"HTTP/1.1\r\nHost: s\r\n\r\n")
        await wr.drain()
        await rd.readuntil(b"\r\n\r\n")
        sse_events: list = []

        async def sse_rd():
            async for ev in read_sse_events(rd):
                sse_events.append(ev)

        t2 = asyncio.ensure_future(sse_rd())
        await until(lambda: gyt_events and sse_events, msg="fulls")
        held_g = D.apply_event(None, gyt_events[0])
        held_s = D.apply_event(None, sse_events[0])
        checks = mismatches = 0
        kinds: set = set()
        for _ in range(4):
            ng, ns = len(gyt_events), len(sse_events)
            fr = frames()               # identical frames, both sides
            for rt in replicas:
                rt.feed(fr)
                rt.run_tick()
            await until(lambda: len(gyt_events) > ng
                        and len(sse_events) > ns, msg="push")
            held_g = D.apply_event(held_g, gyt_events[-1])
            held_s = D.apply_event(held_s, sse_events[-1])
            kinds |= {gyt_events[-1]["t"], sse_events[-1]["t"]}
            fg = await gw1.query(dict(q))
            fs = await gw2.query({"subsys": "hostlist", "maxrecs": 64})
            for held, full in ((held_g, fg), (held_s, fs)):
                if held.get("snaptick") == full.get("snaptick"):
                    checks += 1
                    if json.dumps(held) != json.dumps(
                            json.loads(json.dumps(full))):
                        mismatches += 1
        db = sum(g.stats.counters.get("gw_delta_bytes", 0)
                 for g in (gw1, gw2))
        fb = sum(g.stats.counters.get("gw_full_bytes", 0)
                 for g in (gw1, gw2))
        out = {
            "replicas": 2, "gateways": 2,
            "fleet_single_render": bool(single_render),
            "peer_hits": int(peer_hits),
            "sub_event_kinds": sorted(kinds),
            "delta_checks": checks,
            "delta_mismatches": mismatches,
            "deltas_pushed": sum(
                g.stats.counters.get("gw_deltas_pushed", 0)
                for g in (gw1, gw2)),
            "resyncs": sum(g.stats.counters.get("gw_resyncs", 0)
                           for g in (gw1, gw2)),
            "delta_vs_full_byte_ratio": round(db / max(fb, 1), 4),
        }
        t1.cancel()
        t2.cancel()
        await sc.close()
        wr.close()
        for g in (gw2, gw1):
            await g.stop()
        for s in servers:
            await s.stop()
        return out

    out = asyncio.run(scenario())
    out["meets_target"] = (out["fleet_single_render"]
                           and out["peer_hits"] >= 1
                           and out["delta_mismatches"] == 0
                           and out["delta_checks"] >= 4
                           and out["deltas_pushed"] >= 1)
    print(f"gateway fabric: single_render="
          f"{out['fleet_single_render']}, peer_hits="
          f"{out['peer_hits']}, checks {out['delta_checks']} "
          f"(0 mismatches: {out['delta_mismatches'] == 0}), "
          f"delta ratio {out['delta_vs_full_byte_ratio']}",
          flush=True)
    return out


def gateway_qps_phase() -> dict:
    """Aggregate QPS across GW_LEGS per-leg subprocesses (serialized
    on this 1-core box; each leg = 1 gateway + 1 replica, so the
    aggregate load spans >=2 gateway instances and >=2 serve
    replicas). Gates: aggregate >=100k QPS, per-leg feed impact
    >=0.95, zero delta-reassembly mismatches."""
    import subprocess
    import sys as _sys

    legs = []
    for i in range(GW_LEGS):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   GYT_QUERYLAT_GW_LEG="1")
        p = subprocess.run([_sys.executable, __file__], env=env,
                           capture_output=True, text=True,
                           timeout=1800)
        line = [ln for ln in p.stdout.splitlines()
                if ln.startswith("GWLEG ")]
        if p.returncode != 0 or not line:
            raise RuntimeError(
                f"gateway leg {i} failed rc={p.returncode}: "
                f"{p.stderr[-2000:]}")
        leg = json.loads(line[0][6:])
        legs.append(leg)
        print(f"gw leg {i}: {leg['qps']:,.0f} qps, feed impact "
              f"x{leg['feed_impact_ratio']}, {leg['sub_events']} sub "
              f"events, {leg['delta_mismatches']} mismatches",
              flush=True)
    agg = {
        "legs": legs,
        "n_gateways": GW_LEGS,
        "n_replicas": GW_LEGS,
        "aggregate_qps": round(sum(x["qps"] for x in legs), 1),
        "aggregate_sub_event_rate": round(
            sum(x["sub_event_rate"] for x in legs), 1),
        "feed_impact_ratio_min": min(x["feed_impact_ratio"]
                                     for x in legs),
        "delta_mismatches": sum(x["delta_mismatches"] for x in legs),
        "delta_checks": sum(x["delta_checks"] for x in legs),
        "delta_vs_full_byte_ratio": round(
            sum(x["gw_delta_bytes"] for x in legs)
            / max(sum(x["gw_full_bytes"] for x in legs), 1), 4),
        "methodology": ("per-leg subprocess, legs serialized on this "
                        "1-core box (PR-12 precedent); aggregate = "
                        "sum of per-leg closed-loop rates; feed "
                        "impact = fixed-work feed wall loaded vs "
                        "query-idle within each leg"),
    }
    agg["meets_target"] = (
        agg["aggregate_qps"] >= 100_000.0
        and agg["feed_impact_ratio_min"] >= 0.95
        and agg["delta_mismatches"] == 0
        and agg["delta_checks"] > 0)
    print(f"gateway qps: aggregate {agg['aggregate_qps']:,.0f} qps "
          f"over {GW_LEGS} legs, worst feed impact "
          f"x{agg['feed_impact_ratio_min']}, delta ratio "
          f"{agg['delta_vs_full_byte_ratio']}, meets="
          f"{agg['meets_target']}", flush=True)
    return agg


def render_offload_phase() -> dict:
    """ISSUE-12 GIL-relief measurement: the REST gateway's JSON encode
    of a dashboard-sized response, inline on the loop thread vs
    offloaded to the GYT_QUERY_PROCS ProcessPoolExecutor tier
    (net/qexec.py JsonRenderPool). The honest win metric on a shared
    box is LOOP-THREAD CPU per response (``time.thread_time`` — what
    the serving loop stops paying, i.e. what feed/other queries get
    back); offload wall includes the child's encode and is reported
    too (it only beats inline wall when a second core exists)."""
    import json as _json

    from gyeeta_tpu.net.qexec import JsonRenderPool

    rng = np.random.default_rng(7)
    rows = [{"svcid": f"{i:016x}", "name": f"svc-{i}",
             "hostid": float(i % 97), "state": "OK",
             "nconns": int(rng.integers(0, 1000)),
             "nresp": int(rng.integers(0, 100000)),
             "p95resp5s": round(float(rng.random()) * 250.0, 3),
             "errrate": round(float(rng.random()), 5),
             "bytes_sent": int(rng.integers(0, 1 << 30))}
            for i in range(4096)]
    obj = {"recs": rows, "nrecs": len(rows), "ntotal": len(rows),
           "snaptick": 42}
    reps = 40
    want = _json.dumps(obj).encode()

    t_cpu = time.thread_time()
    t_w = time.perf_counter()
    for _ in range(reps):
        got = _json.dumps(obj).encode()
    inline_cpu = (time.thread_time() - t_cpu) / reps
    inline_wall = (time.perf_counter() - t_w) / reps

    pool = JsonRenderPool(procs=2, min_rows=64)
    assert pool.encode_sync(obj) == want          # byte parity
    t_cpu = time.thread_time()
    t_w = time.perf_counter()
    for _ in range(reps):
        got = pool.encode_sync(obj)
    off_cpu = (time.thread_time() - t_cpu) / reps
    off_wall = (time.perf_counter() - t_w) / reps
    pool.close()
    assert got == want

    # the executor's feeder THREAD pays the pickle (still under this
    # process's GIL), so the honest parent-process GIL relief is
    # dumps-vs-pickle, not dumps-vs-submit — report both
    import pickle
    t_cpu = time.thread_time()
    for _ in range(reps):
        pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    pickle_cpu = (time.thread_time() - t_cpu) / reps

    out = {
        "rows": len(rows), "body_bytes": len(want), "reps": reps,
        "inline_loop_cpu_ms": round(inline_cpu * 1e3, 3),
        "offload_loop_cpu_ms": round(off_cpu * 1e3, 3),
        "loop_cpu_relief_ratio": round(inline_cpu / max(off_cpu, 1e-9),
                                       2),
        "pickle_feeder_cpu_ms": round(pickle_cpu * 1e3, 3),
        "gil_relief_ratio": round(inline_cpu / max(pickle_cpu, 1e-9),
                                  2),
        "inline_wall_ms": round(inline_wall * 1e3, 3),
        "offload_wall_ms": round(off_wall * 1e3, 3),
        "note": ("loop_cpu_relief_ratio = serving-LOOP CPU freed per "
                 "response (the loop only awaits); gil_relief_ratio = "
                 "whole-parent GIL-held work freed (the executor's "
                 "feeder thread still pays a C-speed pickle under the "
                 "GIL); offload wall adds the child encode and only "
                 "beats inline wall with a second core (this box: "
                 f"{os.cpu_count()} visible)"),
    }
    out["meets_target"] = (out["gil_relief_ratio"] >= 1.5
                           and out["loop_cpu_relief_ratio"] >= 5.0)
    print(f"render offload: {out['body_bytes']/1e6:.2f}MB body, loop "
          f"cpu {out['inline_loop_cpu_ms']}ms -> "
          f"{out['offload_loop_cpu_ms']}ms per response "
          f"(x{out['loop_cpu_relief_ratio']} relief)", flush=True)
    return out


# ---- standing-filter phase (ISSUE 18): 100k continuous queries
CQ_FILTERS = int(os.environ.get("GYT_QUERYLAT_CQ_FILTERS", "100000"))
CQ_GROUPS = int(os.environ.get("GYT_QUERYLAT_CQ_GROUPS", "64"))
CQ_ROWS = int(os.environ.get("GYT_QUERYLAT_CQ_ROWS", "2048"))
CQ_TICKS = int(os.environ.get("GYT_QUERYLAT_CQ_TICKS", "8"))
CQ_CHURN = int(os.environ.get("GYT_QUERYLAT_CQ_CHURN", "256"))


def standing_filter_phase() -> dict:
    """100k standing filters on ONE SubscriptionHub over a churning
    svcstate panel (fake fetch — this phase isolates the CQ tier's own
    cost, not the render path, which every other phase already prices).
    The numbers that matter:

    - ``predicate_pass_ms_per_tick``: the SHARED evaluation cost per
      tick — one row-keyed diff + one predicate pass per criteria
      group over only the changed rows. Measured on a twin hub with
      one subscriber per group (the predicate work is per GROUP, so
      this is exactly what 100k subscribers pay too).
    - ``events_per_sec``: membership-event fan-out throughput with the
      full 100k subscriber population attached.
    - ``feed_impact_ratio``: a REAL runtime's feed tick rate while
      serving the CQ tier's panel fetch (exactly one extra render per
      tick, no matter how many filters stand) vs ticking unwatched —
      the fan-out runs on the hub/gateway, so ~1.0 here IS the
      amortization claim from the feed's point of view.

    Gates: 100k filters collapse into ``CQ_GROUPS`` criteria groups
    and the whole tick costs ≤1 panel render + one predicate pass per
    group (``cq_panel_renders == ticks``,
    ``cq_group_evals == groups * ticks``)."""
    import asyncio
    import random

    from gyeeta_tpu.net.subs import SubscriptionHub
    from gyeeta_tpu.query import cq as CQ
    from gyeeta_tpu.utils.selfstats import Stats

    rng = random.Random(29)
    rows = [{"svcid": f"{i:012x}", "hostid": i % 64,
             "qps5s": round(rng.uniform(0.0, 100.0), 3),
             "p95resp5s": round(rng.uniform(0.0, 50.0), 3),
             "state": "OK"} for i in range(CQ_ROWS)]
    tick = [1]

    def churn() -> None:
        tick[0] += 1
        for _ in range(CQ_CHURN):
            rows[rng.randrange(CQ_ROWS)]["qps5s"] = round(
                rng.uniform(0.0, 100.0), 3)

    def panel() -> dict:
        return {"subsys": "svcstate", "snaptick": tick[0],
                "nrecs": len(rows), "recs": [dict(r) for r in rows]}

    async def fetch(req: dict) -> dict:
        return panel()

    # CQ_GROUPS canonical thresholds; every subscriber spells its
    # group's criteria with a different amount of whitespace so the
    # collapse is doing real normalization work, not string identity
    thresholds = [round(1.0 + 98.0 * g / (CQ_GROUPS - 1), 2)
                  for g in range(CQ_GROUPS)]

    def spell(i: int) -> str:
        t = thresholds[i % CQ_GROUPS]
        pad = " " * (1 + (i // CQ_GROUPS) % 3)
        return f"{{{pad}svcstate.qps5s >{pad}{t} }}"

    async def scenario() -> dict:
        out: dict = {"filters": CQ_FILTERS, "groups": CQ_GROUPS,
                     "panel_rows": CQ_ROWS, "ticks": CQ_TICKS}

        # ---- twin hub, ONE subscriber per group: the shared predicate
        # pass per tick (identical work per tick as the 100k-sub hub —
        # evaluation is per GROUP — minus the fan-out)
        stats1 = Stats()
        hub1 = SubscriptionHub(fetch, stats1, history=4,
                               max_subs=CQ_GROUPS + 8)

        async def sink(ev: dict) -> None:
            pass

        for g in range(CQ_GROUPS):
            await hub1.subscribe({"subsys": "svcstate", "cq": True,
                                  "filter": spell(g)}, sink)
        t0 = time.perf_counter()
        for _ in range(CQ_TICKS):
            churn()
            await hub1.push_tick()
        pred_s = time.perf_counter() - t0
        out["predicate_pass_ms_per_tick"] = round(
            pred_s / CQ_TICKS * 1e3, 2)
        hub1.close()

        # ---- the full population: 100k filters, one hub. The first
        # subscriber of each group pays the full snapshot; the rest
        # attach at the group's tick (a warm fleet) — registration
        # cost is reported, not gated.
        stats = Stats()
        hub = SubscriptionHub(fetch, stats, history=4,
                              max_subs=CQ_FILTERS + 8)
        nevents = [0]

        async def count(ev: dict) -> None:
            nevents[0] += 1

        group_tick: list = [None] * CQ_GROUPS
        t0 = time.perf_counter()
        for g in range(CQ_GROUPS):
            seen: list = []

            async def seed(ev: dict, _s=seen) -> None:
                _s.append(ev)

            await hub.subscribe({"subsys": "svcstate", "cq": True,
                                 "filter": spell(g)}, seed)
            group_tick[g] = seen[0]["snaptick"]
        for i in range(CQ_GROUPS, CQ_FILTERS):
            await hub.subscribe(
                {"subsys": "svcstate", "cq": True, "filter": spell(i)},
                count, last_snaptick=group_tick[i % CQ_GROUPS])
        out["subscribe_s"] = round(time.perf_counter() - t0, 2)

        c0, _ = stats.export()
        base_evals = c0.get("cq_group_evals", 0)
        base_renders = c0.get("cq_panel_renders", 0)
        nevents[0] = 0
        t0 = time.perf_counter()
        for _ in range(CQ_TICKS):
            churn()
            await hub.push_tick()
        loaded_s = time.perf_counter() - t0
        c1, gauges = stats.export()
        out["events_delivered"] = int(nevents[0])
        out["events_per_sec"] = int(nevents[0] / max(loaded_s, 1e-9))
        out["loaded_tick_ms"] = round(loaded_s / CQ_TICKS * 1e3, 2)
        out["panel_renders"] = int(
            c1.get("cq_panel_renders", 0) - base_renders)
        out["group_evals"] = int(
            c1.get("cq_group_evals", 0) - base_evals)
        out["live_groups"] = int(gauges.get("cq_groups", 0))
        out["live_subscribers"] = int(gauges.get("cq_subscribers", 0))
        hub.close()

        # THE gates: the collapse is real (100k → CQ_GROUPS), the tick
        # costs ≤1 panel render and exactly one predicate pass per
        # group no matter how many subscribers stand behind it
        out["meets_target"] = (
            out["live_groups"] == CQ_GROUPS
            and out["live_subscribers"] == CQ_FILTERS
            and out["panel_renders"] == CQ_TICKS
            and out["group_evals"] == CQ_GROUPS * CQ_TICKS
            and out["events_delivered"] > 0)
        return out

    out = asyncio.run(scenario())

    # ---- feed impact on a REAL runtime: the feed side of the tier
    # pays ONE panel render per tick for ALL standing filters (the
    # fan-out measured above runs on the hub/gateway) — so the honest
    # feed-impact number is the tick rate watched vs unwatched
    from gyeeta_tpu.runtime import Runtime
    cfg = EngineCfg(n_hosts=64, svc_capacity=1024, task_capacity=512,
                    conn_batch=512, resp_batch=1024,
                    listener_batch=128, fold_k=2)
    rt = Runtime(cfg)
    sim = ParthaSim(n_hosts=64, n_svcs=6, seed=17)
    rt.feed(sim.name_frames())
    rt.feed(sim.listener_frames())

    def feed_tick() -> None:
        rt.feed(sim.conn_frames(512) + sim.resp_frames(1024)
                + wire.encode_frame(wire.NOTIFY_HOST_STATE,
                                    sim.host_state_records()))
        rt.run_tick()

    from gyeeta_tpu.query import cq as CQ
    preq = CQ.panel_request("svcstate")
    for _ in range(3):
        feed_tick()                     # warm: folds + render compile
    rt.query(dict(preq))
    n_impact = 6
    t0 = time.perf_counter()
    for _ in range(n_impact):
        feed_tick()
    idle_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(n_impact):
        feed_tick()
        rt.query(dict(preq))            # the CQ tier's 1 render/tick
    watched_s = time.perf_counter() - t0
    rt.close()
    out["feed_impact_ratio"] = round(idle_s / max(watched_s, 1e-9), 4)

    print(f"standing filters: {out['filters']} filters / "
          f"{out['live_groups']} groups, predicate pass "
          f"{out['predicate_pass_ms_per_tick']}ms/tick, "
          f"{out['events_per_sec']} ev/s, feed impact "
          f"{out['feed_impact_ratio']}, renders/tick "
          f"{out['panel_renders']}/{out['ticks']} "
          f"(meets_target={out['meets_target']})", flush=True)
    return out


def main() -> None:
    # subprocess entries (gateway_qps_phase spawns legs re-entrantly;
    # each leg spawns its gateway child)
    if os.environ.get("GYT_QUERYLAT_GW_CHILD") == "1":
        _gateway_child()
        return
    if os.environ.get("GYT_QUERYLAT_GW_LEG") == "1":
        _gateway_leg()
        return
    # ISSUE-9 concurrent phase FIRST (single-node, fast): its contract
    # numbers must survive even if the mesh phases are slow/wedged
    conc = None
    if os.environ.get("GYT_QUERYLAT_CONCURRENT", "1") == "1":
        conc = concurrent_phase()
    render = None
    if os.environ.get("GYT_QUERYLAT_RENDER", "1") == "1":
        render = render_offload_phase()
    # ISSUE-13 gateway fabric phases (correctness fleet + QPS legs)
    gw_fabric = gw_qps = None
    if os.environ.get("GYT_QUERYLAT_GATEWAY", "1") == "1":
        gw_fabric = gateway_fabric_phase()
        gw_qps = gateway_qps_phase()
    # ISSUE-18 standing-filter phase (continuous-query tier)
    cq_phase = None
    if os.environ.get("GYT_QUERYLAT_CQ", "1") == "1":
        cq_phase = standing_filter_phase()

    # geometry: ≥10k live services over 8 shards. Services populate via
    # listener sweeps; conn/resp volume is kept modest because the CPU
    # backend's in-process all_to_all rendezvous (pairing dispatch) has
    # a hard 40s timeout that 8 virtual devices on ONE physical core
    # cannot meet at full batch geometry — a pure host-emulation limit,
    # not a design one (ICI collectives don't rendezvous over threads).
    cfg = EngineCfg(n_hosts=N_HOSTS, svc_capacity=4096,
                    task_capacity=2048, conn_batch=1024,
                    resp_batch=2048, listener_batch=512, fold_k=2)
    n_shards = len(jax.devices()) if _PLAT != "cpu" else 8
    mesh = make_mesh(n_shards)
    srt = ShardedRuntime(cfg, mesh,
                         RuntimeOpts(dep_pair_capacity=2048,
                                     dep_edge_capacity=1024))
    sim = ParthaSim(n_hosts=N_HOSTS, n_svcs=N_SVCS_PER_HOST, seed=7)
    t0 = time.perf_counter()
    srt.feed(sim.name_frames())
    for _ in range(2):
        srt.feed(sim.conn_frames(2048) + sim.resp_frames(4096)
                 + sim.listener_frames() + sim.task_frames()
                 + wire.encode_frame(wire.NOTIFY_HOST_STATE,
                                     sim.host_state_records()))
        srt.run_tick()
    print(f"setup+feed {time.perf_counter() - t0:.1f}s", flush=True)

    # cold cost: the FIRST query after a tick re-gathers the per-shard
    # snapshot (cache invalidated). Measure it with the jit cache warm
    # (first-ever query also compiles; that's a one-time cost) — this
    # bounds worst-case freshness right at a tick edge.
    srt.query({"subsys": "svcstate", "maxrecs": 1})   # compile + warm
    srt.run_tick()                                    # invalidate
    t1 = time.perf_counter()
    first = srt.query({"subsys": "svcstate", "maxrecs": 1})
    cold_ms = round((time.perf_counter() - t1) * 1e3, 1)
    print(f"cold first query after tick: {cold_ms}ms", flush=True)
    nsvc = first["ntotal"]
    svcid = first["recs"][0]["svcid"]
    QUERIES["svcid_point"] = {"subsys": "svcstate",
                              "filter": f"{{ svcstate.svcid = "
                                        f"'{svcid}' }}"}
    print(f"services live: {nsvc}", flush=True)

    out = {"n_services": int(nsvc), "n_hosts": N_HOSTS,
           "n_shards": n_shards,
           "platform": ("cpu-virtual" if _PLAT == "cpu"
                        else jax.devices()[0].platform),
           "cold_first_query_ms": cold_ms,
           "reps": REPS, "queries": {}}
    worst_p99 = 0.0
    for name, req in QUERIES.items():
        srt.query(req)                      # warm (compile snapshots)
        lat = []
        for _ in range(REPS):
            t1 = time.perf_counter()
            r = srt.query(req)
            lat.append(time.perf_counter() - t1)
        lat = np.array(lat)
        q = {"p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 2),
             "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 2),
             "nrecs": r.get("nrecs", 0)}
        worst_p99 = max(worst_p99, q["p99_ms"])
        out["queries"][name] = q
        print(f"{name:24s} p50 {q['p50_ms']:8.2f}ms  "
              f"p99 {q['p99_ms']:8.2f}ms  nrecs {q['nrecs']}",
              flush=True)
    out["worst_p99_ms"] = worst_p99
    out["target_p99_ms"] = 1000.0
    out["meets_target"] = worst_p99 < 1000.0

    # ---- north-star-scale stage (VERDICT r4 weak #4): ~51k services
    # on the mesh, COLD first-query included in the verdict. The lazy
    # grouped readback keeps a filtered+sorted query O(referenced
    # groups) + O(result) projection instead of a full snapshot.
    if os.environ.get("GYT_QUERYLAT_BIG", "1") == "1":
        del srt
        big_hosts, big_sph = 1024, 50              # 51,200 services
        cfg_b = EngineCfg(n_hosts=big_hosts, svc_capacity=16384,
                          task_capacity=2048, conn_batch=1024,
                          resp_batch=2048, listener_batch=512,
                          fold_k=2)
        srt_b = ShardedRuntime(cfg_b, make_mesh(n_shards),
                               RuntimeOpts(dep_pair_capacity=2048,
                                           dep_edge_capacity=1024))
        sim_b = ParthaSim(n_hosts=big_hosts, n_svcs=big_sph, seed=11)
        t0 = time.perf_counter()
        srt_b.feed(sim_b.name_frames())
        srt_b.feed(sim_b.listener_frames())
        srt_b.feed(sim_b.conn_frames(4096) + sim_b.resp_frames(8192))
        srt_b.run_tick()
        srt_b.feed(sim_b.resp_frames(8192))        # live 5s window
        print(f"big setup+feed {time.perf_counter() - t0:.1f}s",
              flush=True)
        big = {"n_hosts": big_hosts}
        # measure QUERY latency, not the previous tick's async device
        # work: dispatch is async, so an unsynced timer would bill the
        # tick's whole-state window roll (~seconds of device compute
        # on one CPU core; fast + overlapped on TPU) to the query
        jax.block_until_ready(jax.tree.leaves(srt_b.state))
        t1 = time.perf_counter()
        first = srt_b.query({"subsys": "svcstate", "maxrecs": 100,
                             "sortcol": "p95resp5s", "sortdesc": True,
                             "filter": "{ svcstate.nconns >= 0 }"})
        # first-EVER query: includes one-time XLA compiles of the
        # grouped readbacks (persistent-cached across runs) —
        # informational, not part of the freshness budget, which is
        # about repeatable post-invalidation cost
        big["first_query_incl_compile_ms"] = round(
            (time.perf_counter() - t1) * 1e3, 1)
        big["n_services"] = int(first["ntotal"])
        lat = []
        for _ in range(10):
            t1 = time.perf_counter()
            srt_b.query({"subsys": "svcstate", "maxrecs": 100,
                         "sortcol": "p95resp5s", "sortdesc": True,
                         "filter": "{ svcstate.nconns >= 0 }"})
            lat.append(time.perf_counter() - t1)
        big["warm_filtered_sorted_p99_ms"] = round(
            float(np.percentile(np.array(lat), 99)) * 1e3, 1)
        # cold again at a fresh state version (tick invalidates) —
        # the IDENTICAL query shape as the warm/first measurements
        srt_b.run_tick()
        srt_b.feed(sim_b.resp_frames(4096))
        jax.block_until_ready(jax.tree.leaves(srt_b.state))
        t1 = time.perf_counter()
        srt_b.query({"subsys": "svcstate", "maxrecs": 100,
                     "sortcol": "p95resp5s", "sortdesc": True,
                     "filter": "{ svcstate.nconns >= 0 }"})
        big["post_tick_cold_ms"] = round(
            (time.perf_counter() - t1) * 1e3, 1)
        big["meets_target"] = (
            big["post_tick_cold_ms"] < 1000.0
            and big["warm_filtered_sorted_p99_ms"] < 1000.0)
        out["big_51k"] = big
        out["meets_target"] = out["meets_target"] and big["meets_target"]
        print(f"big 51k: first-incl-compile "
              f"{big['first_query_incl_compile_ms']}ms, "
              f"post-tick cold {big['post_tick_cold_ms']}ms, warm p99 "
              f"{big['warm_filtered_sorted_p99_ms']}ms "
              f"({big['n_services']} svcs)", flush=True)

    # the one-line metric must agree with meets_target: worst over
    # EVERY gated number, both stages
    if "big_51k" in out:
        out["worst_p99_ms"] = max(
            out["worst_p99_ms"],
            out["big_51k"]["post_tick_cold_ms"],
            out["big_51k"]["warm_filtered_sorted_p99_ms"])
    if conc is not None:
        out["concurrent"] = conc
        out["meets_target"] = out["meets_target"] and \
            conc["meets_target"]
    if render is not None:
        out["render_offload"] = render
    if gw_fabric is not None:
        out["gateway_fabric"] = gw_fabric
        out["meets_target"] = out["meets_target"] and \
            gw_fabric["meets_target"]
    if gw_qps is not None:
        out["gateway_qps"] = gw_qps
        out["meets_target"] = out["meets_target"] and \
            gw_qps["meets_target"]
    if cq_phase is not None:
        out["standing_filters"] = cq_phase
        out["meets_target"] = out["meets_target"] and \
            cq_phase["meets_target"]
    art = os.environ.get("GYT_QUERYLAT_ART", "QUERYLAT_r09.json")
    with open(art, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"metric": "query_p99_ms_worst",
                      "value": out["worst_p99_ms"],
                      "concurrent_qps": (conc or {}).get("qps"),
                      "concurrent_p99_ms": (conc or {}).get("p99_ms"),
                      "gateway_aggregate_qps":
                          (gw_qps or {}).get("aggregate_qps"),
                      "gateway_feed_impact_min":
                          (gw_qps or {}).get("feed_impact_ratio_min"),
                      "gateway_delta_vs_full_byte_ratio":
                          (gw_qps or {}).get(
                              "delta_vs_full_byte_ratio"),
                      "cq_predicate_pass_ms_per_tick":
                          (cq_phase or {}).get(
                              "predicate_pass_ms_per_tick"),
                      "cq_events_per_sec":
                          (cq_phase or {}).get("events_per_sec"),
                      "meets_target": out["meets_target"]}))


if __name__ == "__main__":
    main()
