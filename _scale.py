"""Fleet-scale harness: 50k/1M simulated agents + the MULTICHIP row.

Phases, each a killable subprocess (the bench.py isolation
discipline), merged into ``MULTICHIP_r08.json`` (``GYT_SCALE_PHASES``
selects; unselected phases carry forward from the previous artifact
when their code paths are unchanged — the PR-11 precedent):

- ``mproc``   — ISSUE-12 feed-rate-per-ingest-process scaling: the
  same stream through 1/2/4 ingest worker processes, per-worker
  saturation rate in records per worker-CPU-second (one subprocess
  per leg, mirrored slot order — see ``_phase_mproc``), exact
  cross-process ledger including a SIGKILL/respawn window.
- ``million`` — 2^20 simulated agents over 64 batched relay conns
  through 4 ingest workers into a live 8-shard mesh: every agent's
  host row lands, uniform shard placement, zero silent loss.

- ``fold``  — the sharded ns-geometry fold on a simulated 8-device
  mesh: ONE compiled mesh program (per-shard fused fold_all + dep
  a2a), measured twice — single-shard-loaded (only shard 0's lanes
  carry events: the pre-sharding shape, every other shard provisioned
  but idle) vs all-shards-loaded (host-partitioned ingest fills every
  shard's lanes). The acceptance gate is aggregate ≥ 3x the
  single-shard rate of the SAME program — the win host-partitioning
  actually buys: a mesh program's wall-clock is the max over shards,
  not the sum, so filling the idle shards' provisioned lanes is ~free.
  The once-per-tick fleet roll-up collective is timed alongside
  (rolled-up ev/s = aggregate including the roll-up cadence cost).

- ``fleet`` — 50,048 simulated agents (sim/partha) through the chaos
  proxy (latency + chunk-resplit faults; no corruption, so accounting
  is exact) over BATCHED conns (each conn aggregates ~1565 hosts — the
  relay shape; 32 sockets, not 50k) into a REAL ``--shards`` serving
  stack (GytServer + ShardFeeder + ShardedRuntime + per-shard WAL),
  ticking live. Gate: ZERO silent event loss —
  accepted + counted-drops + spooled == records_built, exactly.

Legacy single-chip north-star geometry test (the old _scale.py):
``python _scale.py --northstar``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ART = os.path.join(HERE, "MULTICHIP_r08.json")
N_SHARDS = int(os.environ.get("GYT_SCALE_SHARDS", "8"))
# cfg.n_hosts of the ns geometry; override for quick dev runs
N_AGENTS = int(os.environ.get("GYT_SCALE_AGENTS", "50048"))
N_CONNS = int(os.environ.get("GYT_SCALE_CONNS", "32"))
# the ISSUE-12 million-agent leg: 2^20 simulated agents over batched
# relay conns through 4 ingest worker processes
N_MILLION = int(os.environ.get("GYT_SCALE_MILLION_AGENTS",
                               str(1 << 20)))
MILLION_CONNS = int(os.environ.get("GYT_SCALE_MILLION_CONNS", "64"))

PHASE_TIMEOUT = {"fold": 3600, "fleet": 3600, "preagg": 1800,
                 "mproc": 1800, "million": 3600}


def _usage() -> dict:
    """Fold-vs-worker CPU split + peak RSS (the bench.py satellite —
    per-process numbers on a shared box need it to be interpretable)."""
    import resource
    s = resource.getrusage(resource.RUSAGE_SELF)
    c = resource.getrusage(resource.RUSAGE_CHILDREN)
    return {"rss_peak_mb": round(s.ru_maxrss / 1024.0, 1),
            "fold_cpu_s": round(s.ru_utime + s.ru_stime, 2),
            "worker_cpu_s": round(c.ru_utime + c.ru_stime, 2)}


# --------------------------------------------------------------- fold phase
def _phase_fold() -> dict:
    """Sharded ns-geometry fold: single-shard-loaded vs all-loaded on
    ONE mesh program + the fleet roll-up cadence cost."""
    import jax
    import numpy as np

    from gyeeta_tpu.engine.aggstate import EngineCfg
    from gyeeta_tpu.ingest import decode
    from gyeeta_tpu.parallel import depgraph as dg
    from gyeeta_tpu.parallel import rollup, sharded
    from gyeeta_tpu.parallel.mesh import make_mesh
    from gyeeta_tpu.parallel.partition import ShardLayout
    from gyeeta_tpu.sim.partha import ParthaSim

    # the ns fleet PARTITIONED: each shard owns 1/8 of the host space
    # and a slab sized for its slice (the host-partitioning dividend:
    # per-shard working set fits closer to cache than one 131k slab)
    cfg = EngineCfg(svc_capacity=16384, n_hosts=N_AGENTS,
                    task_capacity=8192, conn_batch=2048,
                    resp_batch=4096, fold_k=4)
    # per-shard dep capacities: the roll-up merges n_shards × edge
    # capacity gathered lanes per tick — sized for the bounded caller
    # fan-in of the partitioned fleet, not the single-node maximum
    dep_pairs, dep_edges = 65536, 16384
    mesh = make_mesh(N_SHARDS)
    layout = ShardLayout(mesh)
    t0 = time.perf_counter()
    st = sharded.init_sharded(cfg, mesh)
    dep = layout.put(jax.tree.map(
        lambda x: np.broadcast_to(np.asarray(x)[None],
                                  (N_SHARDS,) + np.asarray(x).shape),
        dg.init(dep_pairs, dep_edges)))
    fold = sharded.fold_step_dep_sharded(
        cfg, mesh, cap_per_dest=cfg.conn_batch * cfg.fold_k)
    # (batches are flat (lanes,) per shard — the slab-width variant)
    flush = sharded.td_flush_sharded(cfg, mesh)
    froll = rollup.fleet_rollup_fn(cfg, mesh, dep_edges)

    # per-shard record streams: every shard folds ITS OWN host range
    # (distinct universes — what host-partitioned ingest delivers)
    per_shard_hosts = N_AGENTS // N_SHARDS // 8     # ~40% slab load
    sims = [ParthaSim(n_hosts=per_shard_hosts, n_svcs=8,
                      n_clients=4096,
                      host_base=s * (N_AGENTS // N_SHARDS),
                      seed=100 + s)
            for s in range(N_SHARDS)]
    K = cfg.fold_k
    lanes_c, lanes_r = K * cfg.conn_batch, K * cfg.resp_batch

    def shard_batch(sim):
        # the sharded slab shape: ONE flat wide batch per shard
        # (fold_k microbatches' worth of lanes — shardedrt's
        # _dispatch_slab discipline)
        return (decode.conn_batch(sim.conn_records(lanes_c), lanes_c),
                decode.resp_batch(sim.resp_records(lanes_r), lanes_r))

    def empty_batch():
        return (decode.conn_batch(sims[0].conn_records(0), lanes_c),
                decode.resp_batch(sims[0].resp_records(0), lanes_r))

    def stacked(loaded_shards):
        """(n_shards, K, B, ...) batches with only ``loaded_shards``
        carrying events."""
        per = []
        e = empty_batch()
        for s in range(N_SHARDS):
            per.append(shard_batch(sims[s])
                       if s in loaded_shards else e)
        cb = jax.tree.map(lambda *xs: np.stack(xs),
                          *[p[0] for p in per])
        rb = jax.tree.map(lambda *xs: np.stack(xs),
                          *[p[1] for p in per])
        return layout.put(cb), layout.put(rb)

    n_distinct = 2
    slabs_one = [stacked({0}) for _ in range(n_distinct)]
    slabs_all = [stacked(set(range(N_SHARDS)))
                 for _ in range(n_distinct)]
    ev_shard = K * (cfg.conn_batch + cfg.resp_batch)

    # warmup/compile both legs on the SAME executable + absorb inserts
    for i in range(2 * n_distinct):
        st, dep, _p = fold(st, dep, *slabs_all[i % n_distinct],
                           np.int32(i))
        st, dep, _p = fold(st, dep, *slabs_one[i % n_distinct],
                           np.int32(i))
    st = flush(st)
    fv = froll(st, dep)
    jax.block_until_ready(fv.health)
    print(f"scale[fold]: init+compile "
          f"{time.perf_counter() - t0:.1f}s", file=sys.stderr,
          flush=True)

    def leg(slabs, events_per_call, calls):
        nonlocal st, dep
        t0 = time.perf_counter()
        for i in range(calls):
            st, dep, _p = fold(st, dep, *slabs[i % n_distinct],
                               np.int32(i))
        jax.block_until_ready(jax.tree.leaves(st)[0])
        dt = time.perf_counter() - t0
        return calls * events_per_call / dt, dt / calls

    r_one, ms_one = leg(slabs_one, ev_shard, 6)
    r_all, ms_all = leg(slabs_all, N_SHARDS * ev_shard, 6)

    # fleet roll-up cadence cost (the once-per-tick collective)
    t0 = time.perf_counter()
    n_roll = 4
    for _ in range(n_roll):
        fv = froll(st, dep)
        jax.block_until_ready(fv.health)
    roll_s = (time.perf_counter() - t0) / n_roll
    # rolled-up rate: a 5s tick pays one roll-up per tick of folding
    tick_s = 5.0
    folds_per_tick = tick_s / ms_all
    rolled_rate = (folds_per_tick * N_SHARDS * ev_shard) \
        / (tick_s + roll_s)

    out = {
        "n_shards": N_SHARDS,
        "per_shard_geometry": {"svc_capacity": cfg.svc_capacity,
                               "n_hosts": cfg.n_hosts,
                               "conn_batch": cfg.conn_batch,
                               "resp_batch": cfg.resp_batch,
                               "fold_k": K},
        "events_per_dispatch_per_shard": ev_shard,
        "single_shard_ev_per_sec": round(r_one, 1),
        "single_shard_ms_per_dispatch": round(ms_one * 1e3, 2),
        "per_shard_ev_per_sec": round(r_all / N_SHARDS, 1),
        "aggregate_ev_per_sec": round(r_all, 1),
        "aggregate_ms_per_dispatch": round(ms_all * 1e3, 2),
        "aggregate_vs_single_shard": round(r_one and r_all / r_one, 3),
        "rollup_seconds": round(roll_s, 4),
        "rolledup_ev_per_sec": round(rolled_rate, 1),
        "meets_3x_gate": bool(r_all >= 3.0 * r_one),
        "device": f"{jax.devices()[0].platform}",
    }
    print(f"scale[fold]: single-shard {r_one:,.0f} ev/s "
          f"({ms_one * 1e3:.1f} ms), aggregate {r_all:,.0f} ev/s "
          f"({ms_all * 1e3:.1f} ms, {N_SHARDS} shards) = "
          f"x{out['aggregate_vs_single_shard']}, roll-up "
          f"{roll_s * 1e3:.0f} ms → rolled-up {rolled_rate:,.0f} ev/s",
          file=sys.stderr, flush=True)
    return out


# -------------------------------------------------------------- fleet phase
async def _fleet_scenario() -> dict:
    import numpy as np

    from gyeeta_tpu.engine.aggstate import EngineCfg
    from gyeeta_tpu.ingest import wire
    from gyeeta_tpu.net.agent import register
    from gyeeta_tpu.net.server import GytServer
    from gyeeta_tpu.parallel.mesh import make_mesh
    from gyeeta_tpu.parallel.shardedrt import ShardedRuntime
    from gyeeta_tpu.sim.chaos import ChaosProxy, FaultPlan
    from gyeeta_tpu.sim.partha import ParthaSim
    from gyeeta_tpu.utils.config import RuntimeOpts
    import asyncio

    tmp = tempfile.mkdtemp(prefix="gyt_fleet_")
    hosts_per_conn = N_AGENTS // N_CONNS            # 1564
    n_svcs = 2                                      # 100k services total
    cfg = EngineCfg(svc_capacity=32768, n_hosts=N_AGENTS,
                    task_capacity=4096, conn_batch=2048,
                    resp_batch=2048, listener_batch=512, fold_k=2)
    # dep-edge capacity bounds the per-tick roll-up's gather+merge —
    # the CPU sim pays all 8 shards' merge serially, so size it for
    # the bounded caller fan-in below, not the parity-test maximum
    opts = RuntimeOpts(dep_pair_capacity=32768, dep_edge_capacity=8192,
                       journal_dir=os.path.join(tmp, "wal"),
                       journal_backlog_mb=512)
    srt = ShardedRuntime(cfg, make_mesh(N_SHARDS), opts)
    srv = GytServer(srt, tick_interval=None, idle_timeout=3600.0,
                    hostmap_path=os.path.join(tmp, "hostmap.json"),
                    shard_ingest=True, shard_queue_mb=64.0)
    host, port = await srv.start()

    # chaos proxy: latency/jitter + chunk re-splitting at scale — no
    # corruption faults, so the no-silent-loss ledger balances exactly
    plan = FaultPlan(seed=11, latency_s=0.001, jitter_s=0.002,
                     resplit=1 << 15)
    proxy = ChaosProxy(host, port, plan=plan)
    ph, pp = await proxy.start()

    sims = [ParthaSim(n_hosts=hosts_per_conn, n_svcs=n_svcs,
                      n_clients=512, host_base=k * hosts_per_conn,
                      seed=500 + k, cli_groups_per_svc=2)
            for k in range(N_CONNS)]
    built = {"conn": 0, "resp": 0, "listener": 0, "host": 0}

    conns = []
    for k in range(N_CONNS):
        reader, writer, status, hid = await register(
            ph, pp, machine_id=0xF1EE7000 + k, conn_type=wire.CONN_EVENT)
        assert status == wire.REG_OK, (k, status)
        conns.append((reader, writer))

    async def drive(k: int, rounds: int, inventory: bool):
        _reader, writer = conns[k]
        sim = sims[k]
        for r in range(rounds):
            nc, nr = 1024, 1024
            buf = sim.conn_frames(nc) + sim.resp_frames(nr)
            built["conn"] += nc
            built["resp"] += nr
            if inventory and r == 0:
                lst = sim.listener_state_records()
                hst = sim.host_state_records()
                buf += wire.encode_frames_chunked(
                    wire.NOTIFY_LISTENER_STATE, lst)
                buf += wire.encode_frames_chunked(
                    wire.NOTIFY_HOST_STATE, hst)
                built["listener"] += len(lst)
                built["host"] += len(hst)
            writer.write(buf)
            await writer.drain()
            await asyncio.sleep(0)

    async def settle(want_key=None):
        for w in conns:
            await w[1].drain()
        for _ in range(600):
            srv._feed_barrier()
            srt.flush()
            c = srt.stats.counters
            got = c.get("conn_events", 0) + c.get("resp_events", 0)
            if got >= built["conn"] + built["resp"]:
                return
            await asyncio.sleep(0.5)

    # warmup: one full-shape round compiles every mesh program (fold,
    # classify, tick, roll-up, snapshot copy) OUTSIDE the measured wall
    await asyncio.gather(*(drive(k, 1, True)
                           for k in range(N_CONNS)))
    await settle()
    srt.run_tick()

    t_start = time.perf_counter()
    rounds = 4
    await asyncio.gather(*(drive(k, rounds, False)
                           for k in range(N_CONNS)))
    # settle: every byte through the proxy, the feeder and the fold
    await asyncio.sleep(1.0)
    await settle()
    feed_wall = time.perf_counter() - t_start
    t_tick = time.perf_counter()
    rep = srt.run_tick()
    tick_wall = time.perf_counter() - t_tick
    wall = time.perf_counter() - t_start
    measured = rounds * N_CONNS * 2048      # conn+resp of measured legs

    c = dict(srt.stats.counters)
    accepted = c.get("conn_events", 0) + c.get("resp_events", 0)
    dropped = sum(v for k, v in c.items()
                  if k.startswith(("shard_ingest_dropped|",
                                   "frames_rejected")))
    spooled = 0                       # raw conns: no agent spool tier
    records_built = built["conn"] + built["resp"]
    ledger_ok = (accepted + dropped + spooled) == records_built

    # the merged fleet view actually covers the fleet
    ss = srt.query({"subsys": "serverstatus"})["recs"][0]
    sl = srt.query({"subsys": "shardlist", "maxrecs": 16})["recs"]
    per_shard_hosts = [r["nhosts"] for r in sl]
    gauges = dict(srt.stats.gauges)
    per_shard_rates = {
        int(k.split("=")[-1]): v for k, v in gauges.items()
        if k.startswith("shard_fold_ev_per_sec|")}

    from gyeeta_tpu.utils import journal as J
    walshards = len(J.sharded_subdirs(opts.journal_dir))

    for _r, w in conns:
        w.close()
    await proxy.stop()
    await srv.stop()
    import shutil
    shutil.rmtree(tmp, ignore_errors=True)

    return {
        "agents": N_AGENTS, "conns": N_CONNS,
        "hosts_per_conn": hosts_per_conn,
        "records_built": records_built,
        "listener_records": built["listener"],
        "accepted": accepted, "dropped": dropped, "spooled": spooled,
        "zero_silent_loss": ledger_ok,
        "wall_s": round(wall, 2),
        "feed_wall_s": round(feed_wall, 2),
        "tick_wall_s": round(tick_wall, 2),
        "ev_per_sec": round(measured / feed_wall, 1),
        "ev_per_sec_with_tick": round(measured / wall, 1),
        "nhosts_reporting": ss["nhosts"],
        "nsvc": ss["nsvc"],
        "per_shard_hosts": per_shard_hosts,
        "per_shard_fold_ev_per_sec": per_shard_rates,
        "rollup_seconds": gauges.get("rollup_seconds"),
        "wal_shard_subdirs": walshards,
        "alerts_tick": rep.get("tick"),
    }


def _phase_fleet() -> dict:
    import asyncio
    return asyncio.run(_fleet_scenario())


# ------------------------------------------------------------ preagg phase
def _phase_preagg() -> dict:
    """Edge pre-aggregation row (ISSUE 11): the SAME simulated stream
    through raw mode and delta mode, measuring wire bytes + fold-lane
    consumption + fleet-view accuracy + errbound honesty.

    64 heavy hosts × fleet-scale sweeps (8192 conn + 16384 resp per
    sweep ≈ 4.9k ev/s/host at 5s cadence — the ROADMAP "2k ev/s/host"
    regime and up). Raw mode ships and folds every tuple; delta mode
    folds at the edge (sketch/edgefold.py) and ships mergeable
    partials. Gate: ≥20x reduction in BOTH wire bytes and fold lanes
    at equal fleet-view accuracy (HLL registers and loghist buckets
    BIT-equal; counters equal within float addition order; heavy-flow
    rows bound-honest vs an exact offline count)."""
    import numpy as np

    from gyeeta_tpu.engine.aggstate import EngineCfg
    from gyeeta_tpu.ingest import decode, wire
    from gyeeta_tpu.runtime import Runtime
    from gyeeta_tpu.sim.partha import ParthaSim
    from gyeeta_tpu.sketch import edgefold as EF

    # the ROADMAP regime: HEAVY hosts (≥2k ev/s/host). Per-host 1-host
    # sims with per-host EdgeFold state — exactly the shape of a real
    # preagg-negotiated agent fleet; events per sweep are PER HOST
    n_hosts = int(os.environ.get("GYT_PREAGG_HOSTS", "8"))
    sweeps = int(os.environ.get("GYT_PREAGG_SWEEPS", "6"))
    n_conn = int(os.environ.get("GYT_PREAGG_CONN", "32768"))
    n_resp = int(os.environ.get("GYT_PREAGG_RESP", "65536"))
    cfg = EngineCfg(svc_capacity=1024, n_hosts=max(n_hosts, 64))
    params = EF.params_of_cfg(cfg, env={})
    simsA = [ParthaSim(n_hosts=1, n_svcs=4, n_clients=2048,
                       host_base=h, seed=600 + h)
             for h in range(n_hosts)]
    simsB = [ParthaSim(n_hosts=1, n_svcs=4, n_clients=2048,
                       host_base=h, seed=600 + h)
             for h in range(n_hosts)]
    rtA, rtB = Runtime(cfg), Runtime(cfg)
    efs = [EF.EdgeFold(params, host_id=h) for h in range(n_hosts)]
    for h in range(n_hosts):
        rtA.feed(simsA[h].listener_frames())
        rtB.feed(simsB[h].listener_frames())
    raw_bytes = delta_bytes = 0
    exact: dict = {}
    t_edge = 0.0
    glob_ids = np.concatenate([s.glob_ids.reshape(-1) for s in simsA])
    for _ in range(sweeps):
        for h in range(n_hosts):
            conn = simsA[h].conn_records(n_conn)
            resp = simsA[h].resp_records(n_resp)
            conn2 = simsB[h].conn_records(n_conn)
            resp2 = simsB[h].resp_records(n_resp)
            raw = (wire.encode_frames_chunked(wire.NOTIFY_TCP_CONN,
                                              conn)
                   + wire.encode_frames_chunked(
                       wire.NOTIFY_RESP_SAMPLE, resp))
            raw_bytes += len(raw)
            rtA.feed(raw)
            t0 = time.time()
            d = efs[h].fold_sweep(conn2, resp2)
            t_edge += time.time() - t0
            db = wire.encode_frames_chunked(wire.NOTIFY_SKETCH_DELTA,
                                            d)
            delta_bytes += len(db)
            rtB.feed(db)
            # exact offline flow totals (accept side, the fold's view)
            cb = decode.conn_batch(conn, size=len(conn))
            acc = cb.valid & cb.is_accept
            k64 = ((cb.flow_hi.astype(np.uint64) << np.uint64(32))
                   | cb.flow_lo.astype(np.uint64))
            tot = (cb.bytes_sent + cb.bytes_rcvd).astype(np.float64)
            for k, v in zip(k64[acc].tolist(), tot[acc].tolist()):
                exact[k] = exact.get(k, 0.0) + v
    rtA.flush(), rtB.flush()

    # fold-lane consumption: raw = every conn/resp tuple occupies one
    # fold lane; delta = the expanded family lanes actually filled
    lanes_raw = (rtA.stats.counters["conn_events"]
                 + rtA.stats.counters["resp_events"])
    lanes_delta = rtB.stats.counters["preagg_lanes"]

    # ---- fleet-view accuracy (state-level: the strongest form)
    sA, sB = rtA.state, rtB.state
    import jax.numpy as jnp
    from gyeeta_tpu.engine import table as T
    keys = glob_ids
    def rows_of(rt):
        hi = (keys >> np.uint64(32)).astype(np.uint32)
        return np.asarray(T.lookup(
            rt.state.tbl, jnp.asarray(hi),
            jnp.asarray(keys.astype(np.uint32)),
            jnp.ones(len(keys), bool)))
    ra, rb = rows_of(rtA), rows_of(rtB)
    assert (ra >= 0).all() and (rb >= 0).all()
    hll_equal = bool(
        np.array_equal(np.asarray(sA.glob_hll.regs),
                       np.asarray(sB.glob_hll.regs))
        and np.array_equal(np.asarray(sA.svc_hll.regs)[ra],
                           np.asarray(sB.svc_hll.regs)[rb]))
    # loghist: exact per-svc totals; samples ON a bucket boundary may
    # round into the neighbor bucket (host-numpy vs XLA 1-ulp
    # transcendental differences, ~1e-5 of samples, within the spec's
    # stated quantile error) — counted as flips, gated at 1e-4
    ha_h = np.asarray(sA.resp_win.cur)[ra].astype(np.float64)
    hb_h = np.asarray(sB.resp_win.cur)[rb].astype(np.float64)
    hist_totals_equal = bool(np.array_equal(ha_h.sum(axis=1),
                                            hb_h.sum(axis=1)))
    hist_flips = float(np.abs(ha_h - hb_h).sum()) / 2
    hist_ok = hist_totals_equal and \
        hist_flips <= max(2.0, 1e-4 * ha_h.sum())
    ca = np.asarray(sA.ctr_win.cur)[ra].astype(np.float64)
    cvb = np.asarray(sB.ctr_win.cur)[rb].astype(np.float64)
    denom = np.maximum(np.abs(ca), 1.0)
    ctr_max_relerr = float(np.abs(ca - cvb).max() / denom.max()) \
        if ca.size else 0.0
    counts_equal = (float(sA.n_conn) == float(sB.n_conn)
                    and float(sA.n_resp) == float(sB.n_resp))

    # ---- errbound honesty of the delta-fed heavy-flow view: the HARD
    # guarantee is the undercount side (value never undercounts beyond
    # the evicted bound — deterministic through the agent-side
    # truncation); overcounts are bounded only in probability (the CMS
    # Markov term, same as raw mode) so they are REPORTED, not gated
    rec = rtB.heavy_recover()
    evicted, err_term = rec["evicted"], rec["err_term"]
    slack = 1e-6 * sum(exact.values())
    violations = 0
    overcounts_past_term = 0
    for key_hex, value, errbound, _src in rec["flows"]:
        tv = exact.get(int(key_hex, 16), 0.0)
        if tv - value > evicted + slack:
            violations += 1
        if value - tv > errbound + err_term + slack:
            overcounts_past_term += 1

    wire_ratio = raw_bytes / max(delta_bytes, 1)
    lane_ratio = lanes_raw / max(lanes_delta, 1)
    out = {
        "hosts": n_hosts, "sweeps": sweeps,
        "events_per_sweep_per_host": n_conn + n_resp,
        "wire_bytes_raw": raw_bytes, "wire_bytes_delta": delta_bytes,
        "wire_bytes_ratio": round(wire_ratio, 1),
        "fold_lanes_raw": int(lanes_raw),
        "fold_lanes_delta": int(lanes_delta),
        "fold_lane_ratio": round(lane_ratio, 1),
        "delta_records": int(
            rtB.stats.counters["preagg_delta_records"]),
        "edge_fold_ms_per_sweep": round(
            1e3 * t_edge / max(sweeps, 1), 1),
        "hll_registers_bit_equal": hll_equal,
        "loghist_totals_equal": hist_totals_equal,
        "loghist_boundary_flips": hist_flips,
        "event_counts_equal": counts_equal,
        "ctr_max_relerr": ctr_max_relerr,
        "resid_bytes": sum(e.stats["resid_bytes"] for e in efs),
        "topk_undercount_violations": violations,
        "topk_overcounts_past_cms_term": overcounts_past_term,
        "topk_rows_checked": len(rec["flows"]),
        "meets_20x_gate": bool(wire_ratio >= 20 and lane_ratio >= 20
                               and hll_equal and hist_ok
                               and counts_equal and violations == 0),
    }
    rtA.close(), rtB.close()
    return out


# ----------------------------------------------------------- mproc phase
def _phase_mproc() -> dict:
    """Parent half: one SUBPROCESS per measured leg (the bench.py
    isolation discipline). Measured in-process, later legs ran 2-3x
    slower per CPU-second on IDENTICAL work — the long-lived harness
    bloats past 10GB folding earlier legs and fresh workers then pay
    reclaim/compaction on every allocation; a crc32 calibration probe
    in the warm harness showed ~1.0 drift, pinning the contamination
    to process memory state, not the box. Fresh leg processes remove
    it; the mirrored slot order stays as belt-and-braces against
    real box drift."""
    slots = os.environ.get("GYT_SCALE_MPROC_LEGS",
                           "1,2,4,4,2,1").split(",")
    leg_runs: dict = {}
    crash_done = False
    for slot_i, n in enumerate(slots):
        env = dict(
            os.environ, GYT_SCALE_PHASE="mproc",
            GYT_SCALE_MPROC_CHILD="1", GYT_SCALE_MPROC_LEGS=n,
            GYT_SCALE_MPROC_SLOT=str(slot_i),
            GYT_SCALE_MPROC_CRASH=(
                "1" if int(n) >= 4 and not crash_done else "0"))
        if int(n) >= 4 and not crash_done:
            crash_done = True
        t0 = time.time()
        try:
            r = subprocess.run([sys.executable, __file__], env=env,
                               cwd=HERE, capture_output=True,
                               text=True, timeout=1500)
        except subprocess.TimeoutExpired:
            print(f"mproc: leg {n} (slot {slot_i}) timed out after "
                  f"{time.time() - t0:.0f}s", file=sys.stderr,
                  flush=True)
            continue
        sys.stderr.write(r.stderr or "")
        line = None
        for ln in (r.stdout or "").splitlines():
            if ln.strip().startswith("{"):
                line = ln.strip()
        if r.returncode != 0 or not line:
            print(f"mproc: leg {n} (slot {slot_i}) failed "
                  f"rc={r.returncode}", file=sys.stderr, flush=True)
            continue
        child = json.loads(line)
        for k, runs in child.get("leg_runs", {}).items():
            leg_runs.setdefault(int(k), []).extend(runs)

    # merge mirrored runs: the reported leg is the MEAN of its early
    # and late slot; raw runs ride along
    legs = {}
    for nprocs, runs in leg_runs.items():
        mean = lambda k: round(  # noqa: E731
            sum(r[k] for r in runs) / len(runs), 1)
        legs[str(nprocs)] = {
            "workers": nprocs,
            "aggregate_ev_per_cpu_sec": mean(
                "aggregate_ev_per_cpu_sec"),
            "aggregate_wall_ev_per_sec": mean(
                "aggregate_wall_ev_per_sec"),
            "wall_serialized_ev_per_sec": mean(
                "wall_serialized_ev_per_sec"),
            "zero_silent_loss": all(r["zero_silent_loss"]
                                    for r in runs),
            "crash_window": next((r["crash_window"] for r in runs
                                  if r.get("crash_window")), None),
            "runs": runs,
        }
    if "1" not in legs or "4" not in legs:
        return {"failed": True, "legs": legs}
    r1 = legs["1"]["aggregate_ev_per_cpu_sec"]
    r4 = legs["4"]["aggregate_ev_per_cpu_sec"]
    out = {
        "n_shards": N_SHARDS,
        "legs": legs,
        "scaling_4w_vs_1w": round(r4 / max(r1, 1e-9), 2),
        "wall_serialized_4w_vs_1w": round(
            legs["4"]["wall_serialized_ev_per_sec"]
            / max(legs["1"]["wall_serialized_ev_per_sec"], 1e-9), 2),
        "usage": _usage(),
        "methodology": (
            "per-worker saturation rates in records per worker "
            "CPU-second summed (workers are fully partitioned: own "
            "conns, own deframe/decode, own WAL files, own rings — N "
            "cores run them in parallel at their per-CPU rate); the "
            "1-core sim serializes them, so wall_serialized is the "
            "same-box control and wall windows carry scheduler "
            "noise. One subprocess per leg, mirrored slot order. "
            "MULTICHIP_r06 fleet methodology."),
    }
    out["meets_2p5x_gate"] = bool(
        out["scaling_4w_vs_1w"] >= 2.5
        and all(leg["zero_silent_loss"] for leg in legs.values())
        and legs["4"]["crash_window"] is not None)
    return out


def _phase_mproc_leaf() -> dict:
    """ISSUE-12 feed-rate-per-ingest-process scaling: the same wire
    stream through 1 / 2 / 4 ingest worker processes (sticky shard
    groups over an 8-shard mesh, worker-owned per-shard WAL on).

    Methodology on the 1-core CPU sim (the MULTICHIP_r06 discipline —
    the host serializes what real deployments run in parallel): each
    worker is measured at SATURATION on its own stream slice with the
    other workers idle and the fold drain deferred (the rings hold
    the leg). The PRIMARY per-worker rate is records per WORKER
    CPU-SECOND (/proc/<pid>/stat utime+stime across the window):
    wall windows of tens of ms on this shared box swing 10-20x with
    scheduler noise, while CPU-normalized cost per record is stable —
    and it is exactly the partitioning claim being measured (worker
    state shares no GIL, no locks, no WAL files, so N cores run N
    workers at their per-CPU rate; the aggregate is the sum).
    ``wall_ev_per_sec`` rides along per worker as the unnormalized
    control, and ``wall_serialized_ev_per_sec`` is the whole-leg
    1-core number. Ledger gate: zero silent loss at 4 processes
    INCLUDING a SIGKILL/respawn window."""
    import signal
    import socket as _socket
    import threading

    from gyeeta_tpu.engine.aggstate import EngineCfg
    from gyeeta_tpu.net.ingestproc import IngestSupervisor
    from gyeeta_tpu.parallel.mesh import make_mesh
    from gyeeta_tpu.parallel.shardedrt import ShardedRuntime
    from gyeeta_tpu.sim.partha import ParthaSim
    from gyeeta_tpu.utils.config import RuntimeOpts

    def proc_cpu_s(pid: int) -> float:
        """utime+stime of one process in seconds (scheduler-noise-
        immune base for the per-worker rate)."""
        with open(f"/proc/{pid}/stat") as f:
            parts = f.read().rsplit(")", 1)[1].split()
        hz = os.sysconf("SC_CLK_TCK")
        return (int(parts[11]) + int(parts[12])) / hz

    import zlib
    _cal_buf = os.urandom(1 << 20)

    def calibrate() -> float:
        """CPU-seconds-per-op of a FIXED C-speed reference (crc32 of
        1MiB) right now. This shared box derates 2-3x over a phase
        run (frequency/SMT/neighbor pressure — measured: identical
        worker windows slow monotonically regardless of worker
        count); dividing each window's rate by the box's concurrent
        derate factor makes windows minutes apart comparable."""
        t0 = time.thread_time()
        n = 0
        while time.thread_time() - t0 < 0.25:
            zlib.crc32(_cal_buf)
            n += 1
        return n / (time.thread_time() - t0)

    # rings sized to PARK one worker's whole measured stream: the
    # fold drains between windows, never during one — a concurrent
    # drain time-shares the core and its cache thrash inflates the
    # measured worker's cycles-per-record (stall cycles bill as CPU)
    os.environ.setdefault("GYT_SHM_RING_SLOTS", "192")
    os.environ.setdefault("GYT_SHM_RING_SLOT_KB", "192")
    cfg = EngineCfg(n_hosts=4096, svc_capacity=8192,
                    task_capacity=1024, conn_batch=2048,
                    resp_batch=2048, listener_batch=512, fold_k=2)
    # long enough that each worker's window spans >= dozens of
    # /proc/stat ticks (10ms granularity) — short windows quantize
    # the CPU-normalized rate into noise. FOUR conns per shard home:
    # every leg's workers then see the same deep-buffered interleave
    # (few conns per worker = shallow socket buffers = small recv
    # chunks = per-chunk overhead billed as phantom per-record cost)
    rounds = int(os.environ.get("GYT_SCALE_MPROC_ROUNDS", "12"))
    conns_per_home = 4
    ev_per_conn = rounds * (2048 + 2048)
    hosts_per_home = 4096 // N_SHARDS
    sims = [ParthaSim(n_hosts=hosts_per_home, n_svcs=2,
                      host_base=h * hosts_per_home, seed=700 + h)
            for h in range(N_SHARDS)]
    home_streams = [b"".join(sims[h].conn_frames(2048)
                             + sims[h].resp_frames(2048)
                             for _ in range(rounds))
                    for h in range(N_SHARDS)]
    # conn j: home hid j % N_SHARDS, stream = its home's bytes
    all_conns = list(range(conns_per_home * N_SHARDS))
    streams = {j: home_streams[j % N_SHARDS] for j in all_conns}

    # warm the mesh fold programs ONCE before any leg (process jit
    # memo): without this the first leg's drain bills multi-minute
    # XLA compiles to the wall numbers
    warm_rt = ShardedRuntime(cfg, make_mesh(N_SHARDS),
                             RuntimeOpts(dep_pair_capacity=8192,
                                         dep_edge_capacity=4096))
    warm_rt.feed(sims[0].conn_frames(2048) + sims[0].resp_frames(2048))
    warm_rt.flush()
    warm_rt.close()
    del warm_rt

    def settle(sup, srt) -> bool:
        """Drain until every accepted record is published AND every
        published record is consumed (checking backlog alone races a
        worker mid-chunk: accept is counted before its publishes).
        Returns False on deadline — callers surface it rather than
        letting a slow box masquerade as a ledger violation."""
        deadline = time.monotonic() + 600
        while time.monotonic() < deadline:
            sup.drain()
            acc = sum(h.shm.counter("accepted_records")
                      for h in sup.workers)
            pub = sum(h.shm.counter("published_records")
                      for h in sup.workers)
            drops = sum(v for k, v in srt.stats.counters.items()
                        if k.startswith("ingest_ring_dropped_records"))
            cons = srt.stats.counters.get(
                "ingest_ring_consumed_records", 0)
            if acc == pub and cons + drops == pub \
                    and sum(h.shm.backlog() for h in sup.workers) == 0:
                return True
            time.sleep(0.005)
        print("mproc: settle DEADLINE expired", file=sys.stderr,
              flush=True)
        return False

    leg_runs: dict = {}
    cal_ref = [None]                # first window's reference speed
    total_cpu0 = _usage()
    # mirrored leg order: every leg samples one early (cool) and one
    # late (derated) slot, so the box's monotone drift cancels in the
    # per-leg average instead of masquerading as a scaling trend
    leg_order = tuple(int(x) for x in os.environ.get(
        "GYT_SCALE_MPROC_LEGS", "1,2,4,4,2,1").split(","))
    for leg_i, nprocs in enumerate(leg_order):
        tmp = tempfile.mkdtemp(prefix=f"gyt_mproc_{nprocs}_")
        srt = ShardedRuntime(
            cfg, make_mesh(N_SHARDS),
            RuntimeOpts(dep_pair_capacity=8192, dep_edge_capacity=4096,
                        journal_dir=os.path.join(tmp, "wal")))
        sup = IngestSupervisor(srt, nprocs,
                               journal_dir=os.path.join(tmp, "wal"))
        sup.start(loop=None)
        # readiness gate: a freshly spawned worker spends seconds in
        # imports — measuring before its loop heartbeats would bill
        # python startup to the ingest rate
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if all(h.shm.counter("hb_seq") >= 2 for h in sup.workers):
                break
            time.sleep(0.05)

        # conn j (home hid = j % N_SHARDS) → worker of that home
        per_worker: dict = {}
        for j in all_conns:
            per_worker.setdefault(
                sup.worker_of_hid(j % N_SHARDS), []).append(j)

        rates = {}
        warm_chunk = {j: sims[j % N_SHARDS].conn_frames(256)
                      for j in all_conns}
        t_all0 = time.perf_counter()
        for w, conns in sorted(per_worker.items()):
            shm = sup.workers[w].shm
            socks = []
            death = threading.Event()
            for h in conns:
                a, b = _socket.socketpair()
                a.setsockopt(_socket.SOL_SOCKET, _socket.SO_SNDBUF,
                             1 << 20)
                assert sup.handoff(h, 1000 + h, b.fileno(), b"", death)
                b.close()
                socks.append((h, a))
            # unmeasured warmup: conn registered, first chunk decoded
            # (numpy import paths, journal open, ring first-touch)
            base = shm.counter("accepted_records")
            for h, a in socks:
                a.sendall(warm_chunk[h])
            while shm.counter("accepted_records") \
                    < base + 256 * len(conns):
                time.sleep(0.001)
            base = shm.counter("accepted_records")
            want = base + len(conns) * ev_per_conn
            writers = [threading.Thread(target=a.sendall,
                                        args=(streams[h],),
                                        daemon=True)
                       for h, a in socks]
            pid = sup.workers[w].proc.pid
            cal = calibrate()
            if cal_ref[0] is None:
                cal_ref[0] = cal
            derate = cal / cal_ref[0]
            cpu0 = proc_cpu_s(pid)
            t0 = time.perf_counter()
            for t in writers:
                t.start()
            while shm.counter("accepted_records") < want:
                time.sleep(0.001)
            dt = time.perf_counter() - t0
            cpu = max(proc_cpu_s(pid) - cpu0, 1e-6)
            nrec = len(conns) * ev_per_conn
            rates[w] = {"ev_per_cpu_sec": nrec / cpu / derate,
                        "ev_per_cpu_sec_raw": nrec / cpu,
                        "box_derate": round(derate, 3),
                        "wall_ev_per_sec": nrec / dt,
                        "cpu_s": round(cpu, 3)}
            for t in writers:
                t.join(timeout=30)
            for _h, s in socks:
                s.close()
        # ALL folding deferred to the leg end: the rings park every
        # window's records (sized above), so the measured windows run
        # back-to-back on a cool box — the fold drain is the phase's
        # big heater and this shared box visibly derates over minutes
        # (measured: identical worker windows run 2-3x slower late in
        # the phase regardless of worker count)
        t_drain0 = time.perf_counter()
        settle(sup, srt)
        srt.flush()
        drain_wall = time.perf_counter() - t_drain0
        wall_all = time.perf_counter() - t_all0

        crash = None
        if nprocs >= 4 \
                and os.environ.get("GYT_SCALE_MPROC_CRASH") == "1":
            # ---- SIGKILL/respawn window inside the ledger
            victim = sup.workers[2]
            pid0 = victim.proc.pid
            os.kill(pid0, signal.SIGKILL)
            victim.proc.wait(timeout=10)
            for _ in range(200):
                if sup.poll():
                    break
                time.sleep(0.05)
            assert victim.proc.pid != pid0, "respawn failed"
            time.sleep(1.0)                 # fresh worker attaches
            a, b = _socket.socketpair()
            death = threading.Event()
            assert sup.handoff(2, 9002, b.fileno(), b"", death)
            b.close()
            tail = sims[2].conn_frames(2048) + sims[2].resp_frames(2048)
            before = victim.shm.counter("accepted_records")
            a.sendall(tail)
            while victim.shm.counter("accepted_records") \
                    < before + 4096:
                time.sleep(0.005)
            settle(sup, srt)
            a.close()
            crash = {"respawned": True, "sticky_shards": victim.shards,
                     "respawns_counted": srt.stats.counters.get(
                         "ingest_proc_respawns|proc=2", 0)}

        sup.poll()
        published = sum(h.shm.counter("published_records")
                        for h in sup.workers)
        accepted = sum(h.shm.counter("accepted_records")
                       for h in sup.workers)
        c = srt.stats.counters
        consumed = c.get("ingest_ring_consumed_records", 0)
        ring_drops = sum(v for k, v in c.items()
                         if k.startswith("ingest_ring_dropped_records"))
        folded = c.get("conn_events", 0) + c.get("resp_events", 0)
        ledger_ok = (published == consumed + ring_drops
                     and accepted == published and folded == consumed)
        run = {
            "workers": nprocs,
            "per_worker": {str(w): {k: round(v, 1) for k, v
                                    in r.items()}
                           for w, r in rates.items()},
            "aggregate_ev_per_cpu_sec": round(
                sum(r["ev_per_cpu_sec"] for r in rates.values()), 1),
            "aggregate_wall_ev_per_sec": round(
                sum(r["wall_ev_per_sec"] for r in rates.values()), 1),
            "wall_serialized_ev_per_sec": round(
                len(all_conns) * ev_per_conn / wall_all, 1),
            "drain_wall_s": round(drain_wall, 2),
            "accepted": int(accepted), "published": int(published),
            "consumed": int(consumed), "ring_drops": int(ring_drops),
            "zero_silent_loss": bool(ledger_ok),
            "crash_window": crash,
        }
        run["records"] = len(all_conns) * ev_per_conn
        run["usage"] = {k: round(v - total_cpu0.get(k, 0), 2)
                        if k.endswith("_s") else v
                        for k, v in _usage().items()}
        leg_runs.setdefault(nprocs, []).append(run)
        print(f"mproc {nprocs}w (slot "
              f"{os.environ.get('GYT_SCALE_MPROC_SLOT', leg_i)}): "
              f"aggregate {run['aggregate_ev_per_cpu_sec']:,.0f} "
              f"ev/cpu-s (wall sum "
              f"{run['aggregate_wall_ev_per_sec']:,.0f},"
              f" serialized "
              f"{run['wall_serialized_ev_per_sec']:,.0f}"
              f"), ledger {'OK' if ledger_ok else 'BROKEN'}",
              file=sys.stderr, flush=True)
        sup.stop()
        sup.close()
        srt.close()
        import shutil
        shutil.rmtree(tmp, ignore_errors=True)
        os.sync()

    return {"leg_runs": {str(k): v for k, v in leg_runs.items()}}


# --------------------------------------------------------- million phase
def _phase_million() -> dict:
    """Toward the north star: 2^20 simulated agents over batched
    relay conns (the production shape: ~16k agents per relay conn)
    through 4 ingest worker processes into a live 8-shard mesh fold.
    Gates: every agent's host row lands (rollup n_hosts_up == 2^20),
    per-shard placement uniform, ledger exact."""
    import socket as _socket
    import threading

    from gyeeta_tpu.engine.aggstate import EngineCfg
    from gyeeta_tpu.ingest import wire
    from gyeeta_tpu.net.ingestproc import IngestSupervisor
    from gyeeta_tpu.parallel.mesh import make_mesh
    from gyeeta_tpu.parallel.shardedrt import ShardedRuntime
    from gyeeta_tpu.sim.partha import ParthaSim
    from gyeeta_tpu.utils.config import RuntimeOpts

    os.environ.setdefault("GYT_SHM_RING_SLOTS", "96")
    os.environ.setdefault("GYT_SHM_RING_SLOT_KB", "192")
    n_agents = N_MILLION
    n_conns = MILLION_CONNS
    hosts_per_conn = n_agents // n_conns
    cfg = EngineCfg(n_hosts=n_agents, svc_capacity=8192,
                    task_capacity=1024, conn_batch=2048,
                    resp_batch=2048, listener_batch=512, fold_k=2)
    srt = ShardedRuntime(cfg, make_mesh(N_SHARDS),
                         RuntimeOpts(dep_pair_capacity=8192,
                                     dep_edge_capacity=4096))
    sup = IngestSupervisor(srt, 4, journal_dir=None)
    sup.start(loop=None)
    time.sleep(1.0)

    # ONE sim generates the per-conn record template; each relay conn
    # rebases host ids into its own 16k block (one init, 64 rebases —
    # a per-conn ParthaSim would spend minutes just constructing)
    sim = ParthaSim(n_hosts=hosts_per_conn, n_svcs=2, seed=900)
    hs_template = sim.host_state_records()
    conn_sweep = sim.conn_frames(2048)      # svc traffic on conn 0 only
    t_gen0 = time.perf_counter()
    streams = []
    built = 0
    for k in range(n_conns):
        recs = hs_template.copy()
        recs["host_id"] = (recs["host_id"] % hosts_per_conn) \
            + k * hosts_per_conn
        buf = wire.encode_frames_chunked(wire.NOTIFY_HOST_STATE, recs)
        if k == 0:
            buf += conn_sweep
            built += 2048
        built += len(recs)
        streams.append(buf)
    gen_wall = time.perf_counter() - t_gen0

    death = threading.Event()
    socks = []
    writers = []
    t0 = time.perf_counter()
    for k in range(n_conns):
        hid = k * hosts_per_conn            # home hid spreads workers
        a, b = _socket.socketpair()
        a.setsockopt(_socket.SOL_SOCKET, _socket.SO_SNDBUF, 1 << 20)
        assert sup.handoff(hid, 2000 + k, b.fileno(), b"", death)
        b.close()
        socks.append(a)
        t = threading.Thread(target=a.sendall, args=(streams[k],),
                             daemon=True)
        writers.append(t)
        t.start()
    # drain concurrently: a million records of ring traffic cannot be
    # parked. Settle condition: every accepted record PUBLISHED and
    # every published record consumed (accept is counted before its
    # publishes — checking backlog alone races the last chunk)
    deadline = time.monotonic() + PHASE_TIMEOUT["million"] - 300
    while time.monotonic() < deadline:
        sup.drain(max_slots_per_ring=64)
        acc = sum(h.shm.counter("accepted_records")
                  for h in sup.workers)
        pub = sum(h.shm.counter("published_records")
                  for h in sup.workers)
        cons = srt.stats.counters.get("ingest_ring_consumed_records",
                                      0)
        drops = sum(v for k, v in srt.stats.counters.items()
                    if k.startswith("ingest_ring_dropped_records"))
        if acc >= built and pub == acc and cons + drops == pub \
                and sum(h.shm.backlog() for h in sup.workers) == 0:
            break
        time.sleep(0.001)
    for t in writers:
        t.join(timeout=30)
    for s in socks:
        s.close()
    srt.flush()
    feed_wall = time.perf_counter() - t0
    t_tick0 = time.perf_counter()
    srt.run_tick()
    tick_wall = time.perf_counter() - t_tick0

    sup.poll()
    published = sum(h.shm.counter("published_records")
                    for h in sup.workers)
    accepted = sum(h.shm.counter("accepted_records")
                   for h in sup.workers)
    c = srt.stats.counters
    consumed = c.get("ingest_ring_consumed_records", 0)
    ring_drops = sum(v for k, v in c.items()
                     if k.startswith("ingest_ring_dropped_records"))
    ledger_ok = (accepted == built and published == accepted
                 and published == consumed + ring_drops)
    ru = srt.rollup_stats()
    sl = srt.query({"subsys": "shardlist", "maxrecs": 16})["recs"]
    per_shard_hosts = [int(r["nhosts"]) for r in sl]
    sup.stop()
    sup.close()
    srt.close()

    out = {
        "agents": n_agents, "relay_conns": n_conns,
        "hosts_per_conn": hosts_per_conn,
        "ingest_workers": 4,
        "records_built": int(built),
        "accepted": int(accepted), "published": int(published),
        "consumed": int(consumed), "ring_drops": int(ring_drops),
        "zero_silent_loss": bool(ledger_ok),
        "gen_wall_s": round(gen_wall, 2),
        "feed_wall_s": round(feed_wall, 2),
        "tick_wall_s": round(tick_wall, 2),
        "ev_per_sec": round(built / feed_wall, 1),
        "n_hosts_up": int(ru["n_hosts_up"]),
        "all_agents_reporting": bool(int(ru["n_hosts_up"])
                                     == n_agents),
        "per_shard_hosts": per_shard_hosts,
        "per_shard_uniform": bool(
            max(per_shard_hosts) - min(per_shard_hosts)
            <= max(1, n_agents // N_SHARDS // 100)),
        "usage": _usage(),
    }
    out["meets_gate"] = bool(ledger_ok and out["all_agents_reporting"])
    print(f"million: {n_agents:,} agents over {n_conns} relay conns / "
          f"4 workers — {out['ev_per_sec']:,.0f} ev/s, hosts up "
          f"{out['n_hosts_up']:,}, ledger "
          f"{'OK' if ledger_ok else 'BROKEN'}",
          file=sys.stderr, flush=True)
    return out


# ------------------------------------------------------------- orchestrator
def _run_phase_subproc(phase: str) -> dict:
    env = dict(
        os.environ, GYT_SCALE_PHASE=phase,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                   + " --xla_force_host_platform_device_count="
                   f"{N_SHARDS}").strip())
    t0 = time.time()
    try:
        r = subprocess.run([sys.executable, __file__], env=env,
                           cwd=HERE, capture_output=True, text=True,
                           timeout=PHASE_TIMEOUT[phase])
    except subprocess.TimeoutExpired:
        print(f"scale: phase {phase} TIMED OUT after "
              f"{time.time() - t0:.0f}s", file=sys.stderr, flush=True)
        return {"timeout": True}
    sys.stderr.write(r.stderr or "")
    line = None
    for ln in (r.stdout or "").splitlines():
        if ln.strip().startswith("{"):
            line = ln.strip()
    if r.returncode != 0 or not line:
        print(f"scale: phase {phase} failed rc={r.returncode}",
              file=sys.stderr, flush=True)
        return {"failed": True, "rc": r.returncode}
    try:
        return json.loads(line)
    except ValueError:
        return {"failed": True, "bad_json": True}


def main() -> int:
    if "--northstar" in sys.argv:
        # legacy single-chip 65k-service geometry test
        env = dict(os.environ, GYT_SCALE_TEST="1")
        r = subprocess.run(
            [sys.executable, "-m", "pytest", "tests/test_scale.py",
             "-x", "-q", "-s", "-p", "no:cacheprovider"],
            cwd=HERE, env=env)
        return r.returncode

    phase = os.environ.get("GYT_SCALE_PHASE")
    if phase == "mproc" and os.environ.get("GYT_SCALE_MPROC_CHILD") \
            == "1":
        print(json.dumps(_phase_mproc_leaf()))
        return 0
    if phase == "fold":
        print(json.dumps(_phase_fold()))
        return 0
    if phase == "fleet":
        print(json.dumps(_phase_fleet()))
        return 0
    if phase == "preagg":
        print(json.dumps(_phase_preagg()))
        return 0
    if phase == "mproc":
        print(json.dumps(_phase_mproc()))
        return 0
    if phase == "million":
        print(json.dumps(_phase_million()))
        return 0

    result = {
        "metric": "multichip_sharded_fold",
        "n_shards": N_SHARDS,
        "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    # GYT_SCALE_PHASES selects; "carry" pulls a phase's row from the
    # previous artifact when its code paths are unchanged this round
    # (the PR-11 precedent — reruns on this shared box cost an hour+
    # and add no information when the measured path didn't move)
    want = os.environ.get(
        "GYT_SCALE_PHASES", "fold,fleet,preagg,mproc,million").split(",")
    prev = {}
    prev_art = os.path.join(HERE, os.environ.get(
        "GYT_SCALE_CARRY_FROM", "MULTICHIP_r07.json"))
    if os.path.exists(prev_art):
        with open(prev_art) as f:
            prev = json.load(f)
    for ph in ("fold", "fleet", "preagg", "mproc", "million"):
        if ph in want:
            result[ph] = _run_phase_subproc(ph)
        elif ph in prev:
            result[ph] = dict(prev[ph])
            result[ph]["carried_from"] = os.path.basename(prev_art)
    fold = result.get("fold", {})
    fleet = result.get("fleet", {})
    preagg = result.get("preagg", {})
    mproc = result.get("mproc", {})
    million = result.get("million", {})
    result["ok"] = bool(fold.get("meets_3x_gate")
                        and fleet.get("zero_silent_loss")
                        and preagg.get("meets_20x_gate")
                        and mproc.get("meets_2p5x_gate")
                        and million.get("meets_gate"))
    with open(ART, "w") as f:
        f.write(json.dumps(result, indent=1) + "\n")
    print(json.dumps(result))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
