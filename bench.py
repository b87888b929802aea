"""Flagship benchmark: flow-event ingest throughput on one chip.

Measures the jitted ``fold_many`` hot loop (K stacked microbatches of
TCP_CONN + response samples folded into full AggState: entity-table
upsert, windowed counters, per-svc loghist + HLL + staged t-digest,
global HLL/CMS/top-K) with HBM-resident state donation — the device
half of the north-star path (BASELINE.md: 100M flow-events/sec on
v5e-8 ⇒ 12.5M/s/chip).

BOTH geometries report every run (VERDICT r4 #1):
  - north-star: 131072-row slab, 65k-service fleet, 50k hosts — THE
    geometry the targets are defined at; this is the headline `value`.
  - toy: 1024-row slab, 512 services — the microbenchmark floor.
The measured loop includes the production digest-flush policy
(pressure-triggered ``td_flush_partial``, same lagged host-side check
the runtime uses), so digest compression cost is billed to the number.

Phase isolation: the default invocation orchestrates each phase as a
SUBPROCESS with its own timeout, appends every completed phase to
``GYT_BENCH_PARTIAL`` (default bench_partial.jsonl) immediately, and
merges what completed into the final contract line. The orchestrator
imports no jax: one process owns the chip at a time, and each phase
leaf is that process while it runs.

No fallback: a leaf that finds no accelerator exits non-zero and the
run stops there; a phase that fails or times out makes the whole run
exit non-zero. ``GYT_BENCH_PLATFORM=cpu`` is the one way to a CPU run
(rehearsal, counts); it is named as such and prints no per-chip metric.

Prints ONE JSON line:
  {"metric": "flow_events_per_sec_per_chip", "value": N,
   "unit": "events/sec", "vs_baseline": N / 12.5e6,
   "device": "<platform>:<device_kind>", ...}
(``flow_events_per_sec_cpu_backend`` and no ``vs_baseline`` when the
device is a CPU.)
"""

from __future__ import annotations

import json
import os
import sys
import time

PER_CHIP_TARGET = 12.5e6  # BASELINE.md north star / 8 chips
HERE = os.path.dirname(os.path.abspath(__file__))

# a leaf that finds no accelerator (and was not sent to the CPU by
# GYT_BENCH_PLATFORM=cpu) exits with this code; the run stops there
RC_NO_ACCELERATOR = 3

# per-phase subprocess timeouts (seconds), compiles included
PHASE_TIMEOUT = {"fold_toy": 1500, "fold_ns": 2700,
                 "feed_toy": 900, "feed_ns": 1500,
                 "feed_toy_wal": 900, "topk_recover": 900,
                 "compact": 1200, "compact_par": 2400,
                 "timeview_aggr": 900}
PHASE_ORDER = ("fold_toy", "fold_ns", "feed_ns", "feed_toy",
               "feed_toy_wal", "topk_recover", "compact",
               "compact_par", "timeview_aggr")


def _geometry(which: str):
    """→ (cfg, sim, dep_pair_capacity, dep_edge_capacity).

    Dep capacities scale with the geometry: the edge working set is
    ≈ fleet_services × per-svc caller fan-in (sim cli_groups_per_svc),
    sized at ~50% load like the service slab."""
    from gyeeta_tpu.engine.aggstate import EngineCfg
    from gyeeta_tpu.sim.partha import ParthaSim

    if which == "ns":
        # slab = 2× services (≤70% open-addressing load, table.py)
        cfg = EngineCfg(svc_capacity=131072, n_hosts=50048,
                        task_capacity=65536)
        sim = ParthaSim(n_hosts=512, n_svcs=128, n_clients=8192,
                        cli_groups_per_svc=4)
        return cfg, sim, 65536, 524288   # 256k steady edges at 50%
    cfg = EngineCfg()
    sim = ParthaSim(n_hosts=64, n_svcs=8, n_clients=4096)
    return cfg, sim, 65536, 16384


def _bench_fold(cfg, sim, dev, label: str, dep_pairs: int,
                dep_edges: int) -> dict:
    """Steady-state ingest-fold throughput: the PRODUCTION dispatch
    (engine fold + dependency-graph fold in one jit, both donated —
    ``step.fold_all``'s connresp-only variant) with the production flush
    policy (lagged pressure check → partial flush). The dep fold used
    to be billed only to the feed path, making feed_vs_fold compare
    different machines. Returns {rate, ms_per_dispatch, n_flushes}."""
    import jax
    import numpy as np

    from gyeeta_tpu.engine import aggstate, step
    from gyeeta_tpu.parallel import depgraph as dg

    K = cfg.fold_k

    def stage():
        from gyeeta_tpu.ingest import decode
        cbs = [decode.conn_batch(sim.conn_records(cfg.conn_batch))
               for _ in range(K)]
        rbs = [decode.resp_batch(sim.resp_records(cfg.resp_batch))
               for _ in range(K)]
        stack = lambda bs: jax.tree.map(  # noqa: E731
            lambda *xs: np.stack(xs), *bs)
        return (jax.device_put(stack(cbs), dev),
                jax.device_put(stack(rbs), dev))

    n_distinct = 2  # cycle staged slabs so inputs aren't degenerate
    slabs = [stage() for _ in range(n_distinct)]

    # the PRODUCTION fused megakernel (engine fold + dep fold +
    # pressure scalar as a graph OUTPUT — Runtime._dispatch_fused's
    # connresp-only variant): one device dispatch per slab, no
    # observation dispatch
    fold = jax.jit(
        lambda s, d, c, r: step.fold_all(cfg, s, d, 0,
                                         connresp=(c, r)),
        donate_argnums=(0, 1))
    flushp = jax.jit(lambda s: step.td_flush_partial(cfg, s),
                     donate_argnums=(0,))
    # state materializes ON the device (jnp zeros) — no host-side
    # multi-GiB buffer crosses to it
    st = jax.device_put(aggstate.init(cfg), dev)
    dep = jax.device_put(dg.init(dep_pairs, dep_edges), dev)

    # warmup / compile — also makes every slab key table-resident, so
    # the measured loop runs the steady-state upsert fast path
    t0 = time.perf_counter()
    for i in range(2 * n_distinct):
        st, dep, _p = fold(st, dep, *slabs[i % n_distinct])
    st = flushp(st)
    jax.block_until_ready(st)
    print(f"bench[{label}]: warmup+compile {time.perf_counter() - t0:.1f}s",
          file=sys.stderr, flush=True)

    events_per_call = K * (cfg.conn_batch + cfg.resp_batch)
    # calibrate call count for ~2s of measurement, bounded for slow hosts
    t0 = time.perf_counter()
    for i in range(4):
        st, dep, _p = fold(st, dep, *slabs[i % n_distinct])
    jax.block_until_ready(st)
    per_call = (time.perf_counter() - t0) / 4
    calls = max(4, min(500, int(2.0 / max(per_call, 1e-6))))

    # production flush policy: check the pressure scalar from two
    # dispatches back (a fold OUTPUT, materialized — no pipeline sync)
    # and flush the fullest stages when headroom is low
    from collections import deque
    pressures: deque = deque()
    n_flushes = 0
    t0 = time.perf_counter()
    for i in range(calls):
        if len(pressures) >= 2 and \
                int(pressures.popleft()) > cfg.td_stage_cap // 2:
            st = flushp(st)
            n_flushes += 1
        st, dep, press = fold(st, dep, *slabs[i % n_distinct])
        pressures.append(press)
    jax.block_until_ready(st)
    elapsed = time.perf_counter() - t0

    rate = calls * events_per_call / elapsed
    # device dispatches per fed slab batch: the fused fold + the
    # amortized share of td_flush_partial dispatches (contract: ≤ 2)
    dpb = (calls + n_flushes) / calls
    print(f"bench[{label}]: {calls} calls x {K} microbatches in "
          f"{elapsed:.2f}s ({elapsed / calls * 1e3:.2f}ms/dispatch, "
          f"{n_flushes} partial flushes, {dpb:.3f} dispatches/batch, "
          f"{rate:,.0f} ev/s)",
          file=sys.stderr, flush=True)
    del st, dep, slabs
    return {"rate": rate, "ms_per_dispatch": elapsed / calls * 1e3,
            "n_flushes": n_flushes, "per_call_s": per_call,
            "dispatches_per_batch": round(dpb, 4)}


def _stage_rates(cfg, bufs, ev_per_buf: int) -> dict:
    """Host-stage isolation: deframe-only and decode-only throughput on
    the same pre-generated buffers the feed loop eats. Emitted next to
    ``feed_path_events_per_sec`` so a future feed regression can be
    attributed to a stage (wire walk vs columnar packing vs fold)."""
    from gyeeta_tpu.ingest import decode, native, wire

    K = cfg.fold_k

    def rate(f, min_s: float = 0.5):
        f(0)                               # warm
        t0 = time.perf_counter()
        n = 0
        while time.perf_counter() - t0 < min_s:
            f(n % len(bufs))
            n += 1
        return n * ev_per_buf / (time.perf_counter() - t0)

    deframe = rate(lambda i: native.drain(bufs[i]))
    drained = [native.drain(b)[0] for b in bufs]
    recs = [(d.get(wire.NOTIFY_TCP_CONN), d.get(wire.NOTIFY_RESP_SAMPLE))
            for d in drained]

    def dec(i):
        conn, resp = recs[i]
        decode.conn_slab([] if conn is None else [conn], K,
                         cfg.conn_batch)
        decode.resp_slab([] if resp is None else [resp], K,
                         cfg.resp_batch)

    return {"deframe_ev_per_sec": round(deframe, 1),
            "decode_ev_per_sec": round(rate(dec), 1)}


def _bench_feed(cfg, sim, label: str, dep_pairs: int,
                dep_edges: int, journal: bool = False) -> dict:
    """Feed-path throughput: the PRODUCT ingest loop (bytes → native
    deframe → decode → staged K-slab fold), not just the device fold —
    VERDICT r4 #3 requires ≥0.8× of the fold at both geometries.
    Frames are pre-generated so the sim's RNG cost isn't billed to the
    server path. ``journal=True`` runs the same loop with the
    write-ahead journal appending every chunk (default knobs) — the WAL
    overhead contract is within 5% of journal-off on the toy feed, with
    journal append/fsync time visible as its own stage rows. Returns
    {rate, deframe_ev_per_sec, decode_ev_per_sec}."""
    import jax

    from gyeeta_tpu.runtime import Runtime
    from gyeeta_tpu.utils.config import RuntimeOpts

    K = cfg.fold_k
    wal_dir = None
    if journal:
        import tempfile
        wal_dir = tempfile.mkdtemp(prefix="gyt_bench_wal_")
    rt = Runtime(cfg, RuntimeOpts(dep_pair_capacity=dep_pairs,
                                  dep_edge_capacity=dep_edges,
                                  journal_dir=wal_dir))
    n_bufs = 4
    ev_per_buf = K * (cfg.conn_batch + cfg.resp_batch)
    bufs = [sim.conn_frames(K * cfg.conn_batch)
            + sim.resp_frames(K * cfg.resp_batch) for _ in range(n_bufs)]
    # warm EVERY jit the measured loop can touch (slab fold, partial
    # flush, pressure readback, single-batch flush path) + absorb
    # first-seen inserts — a stray in-loop compile once cost the toy
    # measurement 0.7s and read as a fake feed-path deficit
    for _ in range(3):
        for b in bufs:
            rt.feed(b)
    rt.td_drain(max_iters=1)
    rt.flush()
    jax.block_until_ready(rt.state)
    # calibrate from one timed feed call
    t0 = time.perf_counter()
    rt.feed(bufs[0])
    rt.flush()
    jax.block_until_ready(rt.state)
    per_call = max(time.perf_counter() - t0, 1e-6)
    feed_calls = max(2, min(100, int(1.5 / per_call)))
    c0 = dict(rt.stats.counters)
    t0 = time.perf_counter()
    for i in range(feed_calls):
        rt.feed(bufs[i % n_bufs])
    rt.flush()
    jax.block_until_ready(rt.state)
    feed_rate = feed_calls * ev_per_buf / (time.perf_counter() - t0)
    # device dispatches per feed batch over the measured loop: the
    # fold_all calls + digest partial flushes (contract ≤ 2)
    c1 = rt.stats.counters
    delta = lambda k: c1.get(k, 0) - c0.get(k, 0)   # noqa: E731
    disp = delta("fold_dispatches") + delta("td_partial_flushes")
    dispatches_per_batch = round(disp / max(feed_calls, 1), 4)
    # overlap win, measured directly: the same feed loop with a
    # block_until_ready barrier after every batch — the host can never
    # decode batch N+1 while the device folds batch N (async dispatch +
    # the double-buffered staging slabs disabled in effect). The ratio
    # async/synced is the wall-clock the overlap actually buys; ~1.0
    # means the host or the device fully dominates.
    sync_calls = max(2, feed_calls // 2)
    t0 = time.perf_counter()
    for i in range(sync_calls):
        rt.feed(bufs[i % n_bufs])
        jax.block_until_ready(rt.state)
    rt.flush()
    jax.block_until_ready(rt.state)
    synced_rate = sync_calls * ev_per_buf / (time.perf_counter() - t0)
    overlap_ratio = round(feed_rate / max(synced_rate, 1e-9), 4)
    stages = _stage_rates(cfg, bufs, ev_per_buf)
    print(f"bench[{label}]: feed path {feed_rate:,.0f} ev/s "
          f"(deframe {stages['deframe_ev_per_sec']:,.0f}, "
          f"decode {stages['decode_ev_per_sec']:,.0f}, "
          f"{dispatches_per_batch} dispatches/batch, "
          f"overlap {overlap_ratio}x)",
          file=sys.stderr, flush=True)
    # embed the run's own telemetry (obs tier): counters incl. the
    # native-vs-fallback decode path, per-stage latency histograms, and
    # the engine-health gauges from one batched readback — a perf
    # artifact that can't hide a silently-degraded decode path
    rt.engine_health()
    selfstats = {"counters": {k: v for k, v in
                              sorted(rt.stats.snapshot().items())},
                 "timings": rt.stats.timing_rows()}
    rt.close()
    if wal_dir is not None:
        import shutil
        shutil.rmtree(wal_dir, ignore_errors=True)
        # the stage breakdown rows the contract asks for: journal
        # append/fsync wall time, separated from deframe/decode/fold
        jrows = [r for r in selfstats["timings"]
                 if r["stage"].startswith("journal_")]
        c = selfstats["counters"]
        return {"rate": round(feed_rate, 1), **stages,
                "dispatches_per_batch": dispatches_per_batch,
                "overlap_ratio": overlap_ratio,
                "selfstats": selfstats, "journal_timings": jrows,
                # hot-loop honesty: the toy loop generates wire bytes
                # far past disk bandwidth, so the bounded WAL backlog
                # may shed (counted) — a real serving edge throttles
                # agents long before this (admission control)
                "wal_appended_chunks": c.get("wal_appended_chunks", 0),
                "wal_backlog_dropped": c.get("wal_backlog_dropped", 0)}
    return {"rate": round(feed_rate, 1), **stages,
            "dispatches_per_batch": dispatches_per_batch,
            "overlap_ratio": overlap_ratio,
            "selfstats": selfstats}


def _bench_topk_recover(cfg, sim, dep_pairs: int, dep_edges: int) -> dict:
    """Heavy-hitter recovery cost + accuracy (ISSUE 7): the per-tick
    invertible-sketch decode readback, measured three ways — wall ms
    per recovery, measured top-32 weighted error vs the exact offline
    reference (``sketch/exact.py:StreamTopK``, the same truth the fuzz
    test asserts ≤2% against), and the feed-path ev/s impact when a
    recovery runs after EVERY feed batch (worst-case cadence; the
    product runs one per 5s tick)."""
    import jax

    from gyeeta_tpu.ingest import decode, wire
    from gyeeta_tpu.runtime import Runtime
    from gyeeta_tpu.sketch import exact
    from gyeeta_tpu.utils.config import RuntimeOpts

    from gyeeta_tpu.sim.partha import ParthaSim

    rt = Runtime(cfg, RuntimeOpts(dep_pair_capacity=dep_pairs,
                                  dep_edge_capacity=dep_edges))
    K = cfg.fold_k
    truth = exact.StreamTopK()
    n_bufs = 6
    ev_per_buf = K * (cfg.conn_batch + cfg.resp_batch)
    bufs = []
    for i in range(n_bufs):
        # one flow universe per buffer (distinct sim seeds): the union
        # of heavy keys exceeds the exact tier's capacity, so the
        # invertible recovery actually contributes rows — the regime
        # the tier exists for, not the one the exact lanes already own
        s = ParthaSim(n_hosts=sim.n_hosts, n_svcs=sim.n_svcs,
                      n_clients=sim.n_clients, seed=1000 + i)
        conns = s.conn_records(K * cfg.conn_batch)
        truth.add_conn_batch(decode.conn_batch(conns, len(conns)))
        bufs.append(wire.encode_frames_chunked(wire.NOTIFY_TCP_CONN,
                                               conns)
                    + s.resp_frames(K * cfg.resp_batch))
    # accuracy leg: each buffer folds exactly ONCE (the engine and the
    # exact reference must see the same stream), then one recovery
    for b in bufs:
        rt.feed(b)
    rt.flush()
    rec = rt.heavy_recover()            # compiles the decode program
    by_id = {r[0]: r[1] for r in rec["flows"]}
    err = mass = 0.0
    for key_hex, exact_v in truth.topk_hex(32):
        err += abs(by_id.get(key_hex, 0.0) - exact_v)
        mass += exact_v
    top32_err = err / max(mass, 1e-9)

    # recovery wall time (cache-busted so every call decodes)
    iters = 20
    t0 = time.perf_counter()
    for _ in range(iters):
        rt._cols.bump()
        rt.heavy_recover()
    recover_ms = (time.perf_counter() - t0) / iters * 1e3

    # feed impact: same loop ± one recovery per feed batch
    def feed_rate(with_recovery: bool, calls: int = 12) -> float:
        t0 = time.perf_counter()
        for i in range(calls):
            rt.feed(bufs[i % n_bufs])
            if with_recovery:
                rt.heavy_recover()
        rt.flush()
        jax.block_until_ready(rt.state)
        return calls * ev_per_buf / (time.perf_counter() - t0)

    feed_rate(False, 4)                 # warm both loop shapes
    r0 = feed_rate(False)
    r1 = feed_rate(True)
    out = {
        "recover_ms_per_tick": round(recover_ms, 3),
        "recovered_keys": rec["recovered_keys"],
        "evicted_mass": rec["evicted"],
        "top32_weighted_err": round(top32_err, 5),
        "err_bound_met": top32_err <= 0.02,
        "feed_ev_per_sec": round(r0, 1),
        "feed_ev_per_sec_with_recovery": round(r1, 1),
        "recover_feed_impact_ratio": round(r1 / max(r0, 1e-9), 4),
        "tick_budget_frac": round(recover_ms / 5000.0, 5),
    }
    print(f"bench[topk_recover]: {recover_ms:.2f} ms/recovery, "
          f"{rec['recovered_keys']} keys, top32 err "
          f"{top32_err:.4f}, feed impact x{out['recover_feed_impact_ratio']}",
          file=sys.stderr, flush=True)
    rt.close()
    return out


def _bench_compact(cfg, sim, dep_pairs: int, dep_edges: int) -> dict:
    """History-tier bulk replay (ISSUE 8): feed a journaled runtime at
    full rate, then compact the sealed WAL into columnar snapshot
    shards and measure the REPLAY ev/s (the compactor re-folds through
    the same fused fold_all path — a second, full-rate consumer of the
    megakernel with no wire interleave) plus the shard footprint per
    window. The producer run warms every compiled fold; the replay
    runtime shares them via the process-wide jit memo, so the measured
    loop is steady-state."""
    import shutil
    import tempfile

    from gyeeta_tpu.history.compactor import Compactor
    from gyeeta_tpu.runtime import Runtime
    from gyeeta_tpu.utils.config import RuntimeOpts
    from gyeeta_tpu.utils.selfstats import Stats

    tmp = tempfile.mkdtemp(prefix="gyt_bench_hist_")
    opts = RuntimeOpts(dep_pair_capacity=dep_pairs,
                       dep_edge_capacity=dep_edges,
                       journal_dir=os.path.join(tmp, "wal"),
                       hist_shard_dir=os.path.join(tmp, "shards"),
                       hist_window_ticks=4, journal_segment_mb=256,
                       # the synthetic producer drives the wire ~60x a
                       # real fleet; the backlog bound must not shed
                       # chunks or the replay would measure less work
                       # than was produced
                       journal_backlog_mb=1024)
    rt = Runtime(cfg, opts)
    K = cfg.fold_k
    n_bufs = 4
    ev_per_buf = K * (cfg.conn_batch + cfg.resp_batch)
    bufs = [sim.conn_frames(K * cfg.conn_batch)
            + sim.resp_frames(K * cfg.resp_batch)
            for _ in range(n_bufs)]
    # 16 slab batches (~1.6M events) per window tick: the sweet spot
    # for the toy sim's 8-service universe — denser ticking amortizes
    # worse (nothing to amortize), sparser ticking drives the per-svc
    # digest stages into permanent overflow-flush pressure (8 svcs
    # absorbing >3M samples/tick is not a production shape; production
    # spreads a 5s tick across 65k services)
    feeds_per_tick = 16

    def produce(nticks):
        for t in range(nticks):
            for i in range(feeds_per_tick):
                rt.feed(bufs[(t * feeds_per_tick + i) % n_bufs])
            rt.run_tick()
        return nticks * feeds_per_tick * ev_per_buf

    comp = Compactor(cfg, opts, journal=rt.journal, stats=Stats())
    # pass 1 (unmeasured): compiles the replay/emit programs the
    # producer never touched — the daemon's steady state is warm
    produce(4)
    comp.compact_once(seal=True, upto_tick=rt._tick_no)
    # pass 2 (measured): same compactor instance, fresh WAL window
    produced = produce(8)
    final_tick = rt._tick_no
    rep = comp.compact_once(seal=True, upto_tick=final_tick)
    raws = comp.store.shards()
    shard_bytes = sum(e["bytes"] for e in raws)
    c = rt.stats.counters
    out = {
        "replay_ev_per_sec": rep["ev_per_sec"],
        "replay_records": rep["records"],
        "replay_chunks": rep["chunks"],
        "replay_secs": rep["secs"],
        "windows": rep["windows"],
        "shards": len(raws),
        "shard_bytes_per_window": round(shard_bytes
                                        / max(len(raws), 1)),
        "produced_events": produced,
        # honesty: chunks the 60x-realtime producer shed before disk
        # (a real serving edge throttles agents long before this)
        "wal_backlog_dropped": c.get("wal_backlog_dropped", 0),
    }
    print(f"bench[compact]: bulk replay {rep['ev_per_sec']:,.0f} ev/s "
          f"({rep['records']} records, {rep['windows']} windows, "
          f"{out['shard_bytes_per_window']:,} B/window)",
          file=sys.stderr, flush=True)
    comp.close()
    rt.close()
    shutil.rmtree(tmp, ignore_errors=True)
    return out


def _bench_compact_par(cfg, dep_pairs: int, dep_edges: int) -> dict:
    """Distributed compaction scaling (ISSUE 14): one 4-shard WAL
    (host-disjoint per-shard streams, two sealed halves per shard)
    replayed by the parallel compactor at 1 worker and at 4 workers.

    Methodology (the MULTICHIP_r08 records/worker-CPU-second shape —
    wall clock cannot scale on a 1-core box, per-worker CPU
    efficiency can): every worker process replays the FIRST half
    unmeasured (GYT_COMPACT_WARM_SEQ — fold compiles + cache loads
    land there), then the measured half's records/CPU-second comes
    from per-shard rusage deltas inside the worker. Aggregate
    capacity = Σ per-worker rate; scaling = capacity(4w) /
    capacity(1w). Gate (ISSUE 14): ≥ 2.5x."""
    import shutil
    import tempfile

    from gyeeta_tpu.history.compactproc import ParallelCompactor
    from gyeeta_tpu.sim.partha import ParthaSim
    from gyeeta_tpu.utils import journal as J
    from gyeeta_tpu.utils.config import RuntimeOpts
    from gyeeta_tpu.utils.selfstats import Stats

    nshards = 4
    # warm half = exactly one 4-tick window, SEALED into its own
    # segment (seal_active rotates): the warm pass replays only below
    # that bound, emits a durable resume shard, and the measured pass
    # replays ONLY the second half
    warm_ticks, meas_ticks = 4, 8
    chunks_per_tick = 16
    tmp = tempfile.mkdtemp(prefix="gyt_bench_cpar_")
    wal = os.path.join(tmp, "wal")
    hosts_per = max(4, cfg.n_hosts // nshards)
    warm_seq = None
    produced = 0
    for s in range(nshards):
        sub = os.path.join(wal, f"shard_{s:02d}")
        sim = ParthaSim(n_hosts=hosts_per, n_svcs=8, seed=70 + s,
                        host_base=s * hosts_per)
        j = J.Journal(sub, backlog_max_bytes=1 << 30)
        j.append(sim.name_frames(), hid=s * hosts_per, tick=0)
        for t in range(warm_ticks):
            for _ in range(chunks_per_tick):
                j.append(sim.conn_frames(cfg.conn_batch)
                         + sim.resp_frames(cfg.resp_batch),
                         hid=s * hosts_per, tick=t)
        bound = j.seal_active()
        warm_seq = bound if warm_seq is None else max(warm_seq, bound)
        for t in range(warm_ticks, warm_ticks + meas_ticks):
            for _ in range(chunks_per_tick):
                j.append(sim.conn_frames(cfg.conn_batch)
                         + sim.resp_frames(cfg.resp_batch),
                         hid=s * hosts_per, tick=t)
                produced += cfg.conn_batch + cfg.resp_batch
        j.close()

    total_ticks = warm_ticks + meas_ticks
    os.environ["GYT_COMPACT_WARM_SEQ"] = str(warm_seq)
    os.environ["GYT_COMPACT_WARM_TICK"] = str(warm_ticks)
    legs = {}
    try:
        for procs in (1, nshards):
            opts = RuntimeOpts(
                dep_pair_capacity=dep_pairs,
                dep_edge_capacity=dep_edges,
                hist_shard_dir=os.path.join(tmp, f"sh{procs}"),
                hist_window_ticks=4)
            pc = ParallelCompactor(cfg, opts, procs, journal_dir=wal,
                                   shard_dir=opts.hist_shard_dir,
                                   stats=Stats())
            rep = pc.compact_once(upto_tick=total_ticks)
            pc.close()
            legs[procs] = rep
    finally:
        os.environ.pop("GYT_COMPACT_WARM_SEQ", None)
        os.environ.pop("GYT_COMPACT_WARM_TICK", None)

    def capacity(rep, workers):
        # per-worker rate over the measured half; procs=1 runs every
        # shard in ONE worker (Σrec/Σcpu), procs=4 one shard each
        per = rep["per_shard"]
        if workers == 1:
            cpu = sum(v["cpu_s"] for v in per.values())
            rec = sum(v["records"] for v in per.values())
            return rec / max(cpu, 1e-9)
        return sum(v["records"] / max(v["cpu_s"], 1e-9)
                   for v in per.values())

    cap1 = capacity(legs[1], 1)
    cap4 = capacity(legs[nshards], nshards)
    out = {
        "scaling_1_to_4": round(cap4 / max(cap1, 1e-9), 3),
        "aggregate_ev_per_cpu_s_1w": round(cap1),
        "aggregate_ev_per_cpu_s_4w": round(cap4),
        "records_measured": legs[1]["records"],
        "produced_events": produced,
        "windows": legs[1]["windows"],
        "wall_serialized_1w_s": legs[1]["secs"],
        "wall_serialized_4w_s": legs[nshards]["secs"],
        "per_shard_4w": legs[nshards]["per_shard"],
        "note": ("records/worker-CPU-second methodology "
                 "(MULTICHIP_r08): 1-core host serializes workers, so "
                 "aggregate capacity is Σ per-worker rate, not wall "
                 "clock; warm half excluded via GYT_COMPACT_WARM_SEQ"),
    }
    print(f"bench[compact_par]: 1w {cap1:,.0f} ev/cpu-s → "
          f"{nshards}w Σ {cap4:,.0f} ev/cpu-s "
          f"(x{out['scaling_1_to_4']})", file=sys.stderr, flush=True)
    shutil.rmtree(tmp, ignore_errors=True)
    return out


def _bench_timeview_aggr() -> dict:
    """Windowed COLUMN aggregation, old vs new (ISSUE 9 satellite /
    ROADMAP history item (a)): the keyed python loop vs the np.unique
    + segment-sum vectorization, on a synthetic 100k-entity svcstate
    window (3 shard samples, ~30% per-sample churn). Parity is
    asserted here too — a fast wrong answer is no answer."""
    import numpy as np

    from gyeeta_tpu.history import timeview as TV

    rng = np.random.default_rng(17)
    n_ent, n_parts = 100_000, 3
    ids = np.array([f"{i:016x}" for i in range(n_ent)], object)
    names = np.array([f"svc-{i % 997}" for i in range(n_ent)], object)
    parts = []
    for _ in range(n_parts):
        cols = {
            "svcid": ids, "svcname": names,
            "qps5s": rng.uniform(0, 100, n_ent),
            "nqry5s": rng.uniform(0, 500, n_ent),
            "nconns": rng.integers(0, 50, n_ent).astype(np.float64),
            "sererr": rng.uniform(0, 5, n_ent),
            "state": rng.integers(0, 5, n_ent).astype(np.int32),
            "hostid": (np.arange(n_ent) % 1024).astype(np.float64),
        }
        parts.append((cols, rng.uniform(size=n_ent) > 0.3))

    t0 = time.perf_counter()
    ref, rmask = TV.aggregate_window_columns_ref("svcstate", parts)
    ref_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    got, gmask = TV.aggregate_window_columns("svcstate", parts)
    vec_s = time.perf_counter() - t0
    for c in ref:
        if ref[c].dtype == object:
            assert got[c].tolist() == ref[c].tolist(), c
        else:
            assert np.array_equal(got[c], ref[c]), c
    out = {
        "entities": int(len(rmask)),
        "rows_aggregated": int(sum(int(p[1].sum()) for p in parts)),
        "ref_loop_s": round(ref_s, 3),
        "vectorized_s": round(vec_s, 3),
        "speedup": round(ref_s / max(vec_s, 1e-9), 1),
    }
    print(f"bench[timeview_aggr]: {out['rows_aggregated']} rows → "
          f"{out['entities']} entities: loop {ref_s:.2f}s vs "
          f"vectorized {vec_s:.3f}s (x{out['speedup']})",
          file=sys.stderr, flush=True)
    return out


def _proc_usage() -> dict:
    """Per-phase resource row (ISSUE-12 satellite): peak RSS plus
    CPU-seconds split between THIS process (the fold side) and its
    CHILDREN (ingest workers / render-pool children) — without the
    split, per-process scaling numbers on a shared box are
    uninterpretable (a phase can look fast while its workers burned a
    core somewhere else)."""
    import resource
    self_ru = resource.getrusage(resource.RUSAGE_SELF)
    child_ru = resource.getrusage(resource.RUSAGE_CHILDREN)
    rss_mb = self_ru.ru_maxrss / 1024.0       # linux: KiB
    try:
        with open("/proc/self/status") as f:
            for ln in f:
                if ln.startswith("VmHWM:"):
                    rss_mb = int(ln.split()[1]) / 1024.0
                    break
    except OSError:                            # pragma: no cover
        pass
    return {
        "rss_peak_mb": round(rss_mb, 1),
        "cpu_user_s": round(self_ru.ru_utime, 2),
        "cpu_sys_s": round(self_ru.ru_stime, 2),
        "child_cpu_user_s": round(child_ru.ru_utime, 2),
        "child_cpu_sys_s": round(child_ru.ru_stime, 2),
    }


def _run_phase(phase: str) -> dict:
    """Leaf mode: run ONE phase in-process and return its fields."""
    import jax

    dev = jax.devices()[0]
    print(f"bench[{phase}]: device={dev.platform}:{dev.device_kind}",
          file=sys.stderr, flush=True)
    if dev.platform == "cpu" \
            and os.environ.get("GYT_BENCH_PLATFORM") != "cpu":
        print("bench: jax found no accelerator, and a measurement path "
              "does not fall back to the CPU (a CPU rehearsal is "
              "GYT_BENCH_PLATFORM=cpu, and says so in its output)",
              file=sys.stderr, flush=True)
        raise SystemExit(RC_NO_ACCELERATOR)
    if phase == "fold_ns":
        cfg, sim, dp, de = _geometry("ns")
        r = _bench_fold(cfg, sim, dev, "northstar", dp, de)
        return {"rate": round(r["rate"], 1),
                "ms_per_dispatch": round(r["ms_per_dispatch"], 3),
                "dispatches_per_batch": r.get("dispatches_per_batch")}
    if phase == "fold_toy":
        cfg, sim, dp, de = _geometry("toy")
        r = _bench_fold(cfg, sim, dev, "toy", dp, de)
        return {"rate": round(r["rate"], 1),
                "ms_per_dispatch": round(r["ms_per_dispatch"], 3),
                "dispatches_per_batch": r.get("dispatches_per_batch")}
    if phase == "feed_ns":
        cfg, sim, dp, de = _geometry("ns")
        return _bench_feed(cfg, sim, "northstar", dp, de)
    if phase == "feed_toy":
        cfg, sim, dp, de = _geometry("toy")
        return _bench_feed(cfg, sim, "toy", dp, de)
    if phase == "feed_toy_wal":
        cfg, sim, dp, de = _geometry("toy")
        return _bench_feed(cfg, sim, "toy+wal", dp, de, journal=True)
    if phase == "topk_recover":
        cfg, sim, dp, de = _geometry("toy")
        return _bench_topk_recover(cfg, sim, dp, de)
    if phase == "compact":
        cfg, sim, dp, de = _geometry("toy")
        return _bench_compact(cfg, sim, dp, de)
    if phase == "compact_par":
        cfg, _sim, dp, de = _geometry("toy")
        return _bench_compact_par(cfg, dp, de)
    if phase == "timeview_aggr":
        return _bench_timeview_aggr()
    raise SystemExit(f"unknown phase {phase!r}")


def _partial_path() -> str:
    return os.environ.get("GYT_BENCH_PARTIAL",
                          os.path.join(HERE, "bench_partial.jsonl"))


# primary metric per phase — the median-selection key of the repeat
# runs (the shared 1-core box shows ±15-50% run-to-run variance, PR
# 8/10 notes; a single-shot row reads as a trend where there is none)
_PHASE_METRIC = {"fold_toy": "rate", "fold_ns": "rate",
                 "feed_toy": "rate", "feed_ns": "rate",
                 "feed_toy_wal": "rate",
                 "topk_recover": "recover_ms_per_tick",
                 "compact": "replay_ev_per_sec",
                 "compact_par": "scaling_1_to_4",
                 "timeview_aggr": "speedup"}


def _phase_subproc(phase: str, platform: str | None):
    """One killable leaf run of ``phase`` → its dict, or a failure
    marker dict. A leaf that found no accelerator ends the run."""
    import subprocess

    env = dict(os.environ)
    env["GYT_BENCH_PHASE"] = phase
    if platform:
        # assigned, not defaulted: the leaf's backend is this one
        env["JAX_PLATFORMS"] = env["GYT_BENCH_PLATFORM"] = platform
    t0 = time.time()
    try:
        r = subprocess.run([sys.executable, __file__], env=env,
                           cwd=HERE, capture_output=True, text=True,
                           timeout=PHASE_TIMEOUT[phase])
    except subprocess.TimeoutExpired as e:
        print(f"bench: phase {phase} TIMED OUT after "
              f"{time.time() - t0:.0f}s; "
              f"stderr tail: {(e.stderr or b'')[-300:]!r}",
              file=sys.stderr, flush=True)
        return {"timeout": True}
    sys.stderr.write(r.stderr or "")
    if r.returncode == RC_NO_ACCELERATOR:
        raise SystemExit(RC_NO_ACCELERATOR)
    line = None
    for ln in (r.stdout or "").splitlines():
        if ln.strip().startswith("{"):
            line = ln.strip()
    if r.returncode != 0 or not line:
        print(f"bench: phase {phase} failed rc={r.returncode}",
              file=sys.stderr, flush=True)
        return {"failed": True, "rc": r.returncode}
    try:
        return json.loads(line)
    except ValueError:
        print(f"bench: phase {phase} emitted non-JSON: "
              f"{line[:200]!r}", file=sys.stderr, flush=True)
        return {"failed": True, "bad_json": True}


def _orchestrate(platform: str | None) -> None:
    """Run each phase as a killable subprocess; merge what completed.
    Exits non-zero when any phase failed or timed out.

    Measured phases repeat ``GYT_BENCH_RUNS`` times (default 3): the
    reported row is the MEDIAN run by the phase's primary metric, and
    every row records its per-run values + spread — single-shot rows
    on the shared box kept misleading trend reads (PR 8/10 notes)."""
    partial = _partial_path()
    # stale partials from a previous run must not leak into this one
    try:
        os.remove(partial)
    except OSError:
        pass
    runs_want = max(1, int(os.environ.get("GYT_BENCH_RUNS", "3")))
    phases: dict[str, dict] = {}
    failed = []                 # a phase any of whose runs failed
    for phase in PHASE_ORDER:
        metric = _PHASE_METRIC.get(phase)
        n_runs = runs_want if metric else 1
        attempts = []
        for i in range(n_runs):
            out = _phase_subproc(phase, platform)
            attempts.append(out)
            if metric is None or metric not in out:
                break           # a failed run ends the repeat
        good = [a for a in attempts if metric and metric in a]
        if metric and good:
            vals = sorted(float(a[metric]) for a in good)
            med = vals[len(vals) // 2]
            pick = min(good, key=lambda a: abs(float(a[metric]) - med))
            pick = dict(pick)
            pick["runs"] = [round(float(a[metric]), 4) for a in good]
            if med:
                pick["spread_pct"] = round(
                    100.0 * (vals[-1] - vals[0]) / abs(med), 1)
            phases[phase] = pick
        else:
            phases[phase] = attempts[-1]
        if any("failed" in a or "timeout" in a for a in attempts):
            failed.append(phase)
        if "failed" in phases[phase] or "timeout" in phases[phase]:
            continue
        with open(partial, "a") as f:
            f.write(json.dumps({"phase": phase, **phases[phase]}) + "\n")

    ns, toy = phases.get("fold_ns", {}), phases.get("fold_toy", {})
    fns, ftoy = phases.get("feed_ns", {}), phases.get("feed_toy", {})
    device = next((v["device"] for v in phases.values()
                   if "device" in v), None)
    on_cpu = device is None or device.startswith("cpu:")
    value = ns.get("rate") or toy.get("rate") or 0.0
    result = {
        # a CPU-backend figure never carries a per-chip name
        "metric": ("flow_events_per_sec_cpu_backend" if on_cpu
                   else "flow_events_per_sec_per_chip"),
        "value": value,
        "unit": "events/sec",
        **({} if on_cpu
           else {"vs_baseline": round(value / PER_CHIP_TARGET, 4)}),
        # constants of _geometry("ns") — NOT recomputed here: the
        # orchestrator imports neither jax nor the engine (the leaf of
        # the moment is the one process that may hold the chip)
        "geometry": {"svc_capacity": 131072,
                     "services": 512 * 128, "n_hosts": 50048},
        "device": device,
        **({"toy_events_per_sec": toy["rate"]} if "rate" in toy else {}),
        **({"northstar_vs_toy": round(ns["rate"] / toy["rate"], 3)}
           if "rate" in ns and "rate" in toy else {}),
    }
    # perf runs carry their own telemetry: the feed phase's selfstats
    # snapshot (counters + stage histograms + engine-health gauges)
    snap = fns.get("selfstats") or ftoy.get("selfstats")
    if snap:
        result["selfstats"] = snap
    if "rate" in fns:
        result["feed_path_events_per_sec"] = fns["rate"]
        if "rate" in ns:
            result["feed_vs_fold"] = round(fns["rate"] / ns["rate"], 3)
        # per-stage breakdown (ISSUE 1): attribute future feed-path
        # regressions to deframe / decode / fold instead of one blended
        # number
        for k in ("deframe_ev_per_sec", "decode_ev_per_sec",
                  "dispatches_per_batch", "overlap_ratio"):
            if k in fns:
                result[k] = fns[k]
        if "rate" in ns:
            result["fold_ev_per_sec"] = ns["rate"]
            result["fold_ms_per_dispatch"] = ns.get("ms_per_dispatch")
            result["fold_dispatches_per_batch"] = \
                ns.get("dispatches_per_batch")
    if "rate" in ftoy:
        result["toy_feed_path_events_per_sec"] = ftoy["rate"]
        if "rate" in toy:
            result["toy_feed_vs_fold"] = round(
                ftoy["rate"] / toy["rate"], 3)
        for k in ("deframe_ev_per_sec", "decode_ev_per_sec",
                  "dispatches_per_batch", "overlap_ratio"):
            if k in ftoy:
                result["toy_" + k] = ftoy[k]
        if "rate" in toy:
            result["toy_fold_ms_per_dispatch"] = \
                toy.get("ms_per_dispatch")
            result["toy_fold_dispatches_per_batch"] = \
                toy.get("dispatches_per_batch")
    fwal = phases.get("feed_toy_wal", {})
    if "rate" in fwal:
        # WAL overhead contract (ISSUE 5): journaling within 5% of
        # journal-off on the toy feed; append/fsync rows separated
        result["toy_feed_wal_events_per_sec"] = fwal["rate"]
        if "rate" in ftoy:
            result["wal_overhead_ratio"] = round(
                fwal["rate"] / ftoy["rate"], 4)
        if fwal.get("journal_timings"):
            result["journal_stage_timings"] = fwal["journal_timings"]
    hh = phases.get("topk_recover", {})
    if "recover_ms_per_tick" in hh:
        # heavy-hitter recovery row (ISSUE 7): per-tick decode cost,
        # measured accuracy vs the exact offline count, feed impact
        result["topk_recover"] = hh
    cp = phases.get("compact", {})
    if "replay_ev_per_sec" in cp:
        # history-tier bulk replay row (ISSUE 8): the WAL compactor's
        # re-fold rate (a second full-rate fused-fold consumer, no
        # wire/decode interleave) vs the live ns fold rate, plus the
        # columnar shard footprint per window
        result["compact"] = dict(cp)
        if "rate" in ns:
            result["compact"]["replay_vs_ns_fold"] = round(
                cp["replay_ev_per_sec"] / ns["rate"], 4)
    cpp = phases.get("compact_par", {})
    if "scaling_1_to_4" in cpp:
        # distributed compaction row (ISSUE 14): 1→4 replay worker
        # aggregate capacity ratio, records/worker-CPU-second
        # methodology (gate ≥ 2.5x)
        result["compact_par"] = dict(cpp)
    tv = phases.get("timeview_aggr", {})
    if "speedup" in tv:
        # windowed-aggregation vectorization row (ISSUE 9 satellite):
        # keyed python loop vs np.unique segment sums at 100k entities
        result["timeview_aggr"] = dict(tv)
    # snapshot-serving contract row (ISSUE 9): embed the concurrent
    # phase summary from the most recent _querylat.py artifact — the
    # orchestrator only READS the json (never imports the engine)
    for art in ("QUERYLAT_r06.json",):
        try:
            with open(os.path.join(HERE, art)) as f:
                conc = json.load(f).get("concurrent")
        except (OSError, ValueError):
            conc = None
        if conc:
            result["querylat_concurrent"] = {
                k: conc[k] for k in (
                    "qps", "p50_ms", "p99_ms", "cache_hit_rate",
                    "snapshot_age_p99_s", "feed_impact_ratio",
                    "queries_shed", "meets_target")
                if k in conc}
            result["querylat_concurrent"]["artifact"] = art
    if failed:
        result["phases_failed"] = failed
    print(json.dumps(result))
    if failed:
        print(f"bench: FAILED phases: {', '.join(failed)}",
              file=sys.stderr, flush=True)
        raise SystemExit(1)


def main() -> None:
    # compile cache placement (JAX_COMPILATION_CACHE_DIR if set, else
    # the checkout's .jax_cache) — before any leaf imports jax
    from gyeeta_tpu.utils import xlacache
    xlacache.configure()
    phase = os.environ.get("GYT_BENCH_PHASE")
    plat = os.environ.get("GYT_BENCH_PLATFORM")
    if phase:
        # leaf: one phase. JAX_PLATFORMS decides the backend (the
        # orchestrator assigned it from GYT_BENCH_PLATFORM; a leaf
        # started by hand gets the same)
        if plat:
            os.environ["JAX_PLATFORMS"] = plat
        out = _run_phase(phase)
        import jax
        dev = jax.devices()[0]
        out["device"] = f"{dev.platform}:{dev.device_kind}"
        out["device_count"] = len(jax.devices())
        # resource row AFTER the measured work: peak RSS + the fold-
        # vs-child CPU-seconds split (shared-box interpretability)
        out["usage"] = _proc_usage()
        print(json.dumps(out))
        return
    _orchestrate(plat)


if __name__ == "__main__":
    main()
