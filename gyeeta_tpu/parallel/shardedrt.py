"""ShardedRuntime: the full product loop on an n-device mesh.

The single-node :class:`~gyeeta_tpu.runtime.Runtime` is one madhava. This
is the whole tier: every mesh shard owns the engine state for its slice of
the host space (DP over ``HOST_AXIS``), and the subsystems that the
reference runs as madhava→shyama RPCs become collectives:

- **ingest**: host-side routing of decoded records by ``host_id % n``
  (shyama's ``assign_partha_madhava`` placement, stateless) + shard_map'd
  folds — zero collectives in the hot path;
- **tick**: per-shard classify (each madhava classifies its own
  listeners), per-shard window tick/ageing, dep-graph TTL;
- **pairing / dep graph**: ``all_to_all`` to flow owners
  (``parallel/depgraph.py``);
- **queries & alerts**: gather per-shard snapshot columns and run the
  SAME filter/sort/aggregation pipeline on the merged columns — the
  multi-madhava scatter the reference's Node webserver performs
  (``server/gy_mnodehandle.cc:203``), done once here so alertdefs, JSON
  queries and history writes all see a cluster-wide view.

Everything stacked ``(n_shards, ...)`` with a leading-axis sharding, so
the same program runs on one chip (n=1), a v5e-8 slice, or a multi-slice
DCN mesh.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from gyeeta_tpu.alerts import AlertManager
from gyeeta_tpu.engine.aggstate import EngineCfg, flat_widths
from gyeeta_tpu.hostingest import SECTION_COUNTERS, HostIngest, \
    section_builders
from gyeeta_tpu.ingest import decode, native, wire
from gyeeta_tpu.obs import health as obs_health
from gyeeta_tpu.obs import xlamon
from gyeeta_tpu.obs.spans import SpanTracer
from gyeeta_tpu.parallel import depgraph as dg
from gyeeta_tpu.parallel import pairing, rollup, sharded
from gyeeta_tpu.parallel.mesh import shard_of_host  # noqa: F401 — re-export
from gyeeta_tpu.query import api, fieldmaps, readback
from gyeeta_tpu.query.api import QueryOptions
from gyeeta_tpu.sketch import topk
from gyeeta_tpu.utils import dnsmap as _dnsmap
from gyeeta_tpu.utils.config import RuntimeOpts
from gyeeta_tpu.utils.intern import InternTable
from gyeeta_tpu.utils.selfstats import Stats


class ShardedRuntime(HostIngest):
    def __init__(self, cfg: Optional[EngineCfg] = None, mesh=None,
                 opts: Optional[RuntimeOpts] = None, clock=None):
        from gyeeta_tpu.parallel.mesh import make_mesh

        self.cfg = cfg or EngineCfg()
        self.mesh = mesh if mesh is not None else make_mesh()
        self.n = self.mesh.devices.size
        # the ONE shard-layout declaration (parallel/partition.py):
        # fold, roll-up, snapshot placement, the ingest-edge host hash
        # and the per-shard WAL subdirs all ask the layout instead of
        # re-deriving placement locally
        from gyeeta_tpu.parallel.partition import ShardLayout
        self.layout = ShardLayout(self.mesh)
        self.opts = opts or RuntimeOpts()
        self.stats = Stats()
        # the one stage timer: span ring + timing histograms + profiler
        # annotations on the leaves (obs/spans.py; same span names at
        # the same places as the single-node Runtime)
        self.spans = SpanTracer(stats=self.stats,
                                annotation=jax.profiler.TraceAnnotation)
        self._tick_p0 = None          # run_tick entry, until its publish
        from gyeeta_tpu.utils.colcache import ColumnCache
        self._cols = ColumnCache()    # version-keyed snapshot memo
        self.names = InternTable()
        from gyeeta_tpu.utils.svcreg import SvcInfoRegistry
        from gyeeta_tpu.utils.hostreg import CgroupRegistry, \
            HostInfoRegistry, MountRegistry, NetIfRegistry
        from gyeeta_tpu.utils.notifylog import NotifyLog
        from gyeeta_tpu.trace.defs import TraceDefs
        self.tracedefs = TraceDefs(clock=clock)
        from gyeeta_tpu.utils.natreg import NatClusterRegistry
        self.svcreg = SvcInfoRegistry()
        self.hostinfo = HostInfoRegistry()
        self.cgroups = CgroupRegistry()
        self.mounts = MountRegistry()
        self.netifs = NetIfRegistry()
        self.natclusters = NatClusterRegistry()
        from gyeeta_tpu.utils.traceconnreg import TraceConnRegistry
        self.traceconns = TraceConnRegistry()
        from gyeeta_tpu.utils.tagreg import TagRegistry
        self.tags = TagRegistry()
        from gyeeta_tpu.utils.dnsmap import DnsCache
        self.dns = DnsCache()
        self.notifylog = NotifyLog(clock=clock)
        self.alerts = AlertManager(self.cfg, clock=clock)
        self._clock = clock or time.time
        self._t_started = self._clock()
        self._tick_no = 0
        self._pending = b""
        # write-ahead event journal (utils/journal.py): the mesh tier
        # journals PER SHARD — chunks land in ``shard_NN/`` subdirs by
        # the layout's sticky hid→shard hash, so journaling, replay and
        # compaction all shard with the fold (a replayed chunk re-folds
        # into exactly the shard that folded it live; see the routing-
        # stability tests). A 1-device mesh keeps the flat layout.
        self.journal = None
        if self.opts.journal_dir:
            from gyeeta_tpu.utils.journal import Journal, ShardedJournal
            jkw = dict(
                segment_max_bytes=self.opts.journal_segment_mb << 20,
                fsync_bytes=self.opts.journal_fsync_kb << 10,
                fsync_ms=self.opts.journal_fsync_ms,
                backlog_max_bytes=self.opts.journal_backlog_mb << 20,
                stats=self.stats, clock=clock)
            if self.n > 1:
                self.journal = ShardedJournal(
                    self.opts.journal_dir, self.n,
                    subdir_fmt=self.layout.WAL_SUBDIR_FMT, **jkw)
            else:
                self.journal = Journal(self.opts.journal_dir, **jkw)
        self._journal_replaying = False
        # time-travel query tier (history/timeview.py): shard-
        # materialized snapshots re-enter the stacked pytree shape and
        # are served by the SAME merged-columns pipeline (see
        # _merged_columns_state), so the mesh tier gets at=/window=
        # queries on every edge with zero edge-specific code
        self.timeview = None
        if self.opts.hist_shard_dir:
            from gyeeta_tpu.history.shards import open_shard_store
            from gyeeta_tpu.history.timeview import TimeView
            store = open_shard_store(self.opts.hist_shard_dir,
                                     stats=self.stats)
            self.timeview = TimeView(self, store, clock=clock)
            if self.journal is not None:
                pos = store.position()
                if pos:
                    from gyeeta_tpu.utils.journal import floors_of
                    fl = floors_of(pos)
                    if isinstance(fl, list) \
                            and not hasattr(self.journal, "shards"):
                        fl = min(fl) if fl else 0
                    self.journal.set_truncate_floor(fl)
                else:
                    self.journal.set_truncate_floor(0)
        # per-host sweep-seq high-water marks (the WAL dedup state)
        self._sweep_last_seq: dict = {}
        # conn/resp slab staging, PER SHARD: the ingest edge hashes
        # each record's host to its shard ONCE at staging time
        # (``_stage_raw``), so a dispatch builds every shard's lanes
        # from its own bucket — lane width is the actual slab width,
        # not the worst-case routing skew, and the per-record routing
        # cost leaves the dispatch path. ``_n_conn_raw``/``_n_resp_raw``
        # stay the TOTALS (the admission controller reads them).
        self._conn_raw: list = [[] for _ in range(self.n)]
        self._resp_raw: list = [[] for _ in range(self.n)]
        self._conn_staged = [0] * self.n
        self._resp_staged = [0] * self.n
        self._n_conn_raw = 0
        self._n_resp_raw = 0
        # per-shard folded-event counters → gyt_shard_fold_ev_per_sec
        # gauges at tick cadence (host-side ints, no readback)
        self._shard_events = np.zeros(self.n, np.int64)
        self._shard_rate_mark = np.zeros(self.n, np.int64)
        self._shard_rate_t: float = self._clock()
        # last tick each host sent a native RESP_SAMPLE (trace→resp
        # bridge precedence, see Runtime)
        self._host_resp_tick = np.full(self.cfg.n_hosts, -(10 ** 9),
                                       np.int64)

        self.state = sharded.init_sharded(self.cfg, self.mesh)
        self.dep = self.layout.put(
            jax.tree.map(
                lambda x: np.broadcast_to(
                    np.asarray(x)[None], (self.n,) + np.asarray(x).shape),
                dg.init(self.opts.dep_pair_capacity,
                        self.opts.dep_edge_capacity)))

        self._td_flush = sharded.td_flush_sharded(self.cfg, self.mesh)
        self._td_pressure = sharded.td_pressure_sharded(self.mesh)
        # the slab dispatch: engine fold + dep fold + pressure scalar
        # in ONE shard_map'd jit
        self._fold_dep_slab = sharded.fold_step_dep_sharded(
            self.cfg, self.mesh,
            cap_per_dest=self.cfg.conn_batch * self.cfg.fold_k)
        self._fold_dep_chunk = sharded.fold_step_dep_sharded(
            self.cfg, self.mesh, cap_per_dest=self.cfg.conn_batch)
        self._td_dirty = False
        self._pressure = None         # device scalar from last dispatch
        # sweep subsystems: one stacked dispatch each (ROADMAP D1):
        # kind → (sharded fold, lanes per shard); the delta fold takes
        # and returns dep too (edge pre-aggregation, both donated)
        cfg, mesh = self.cfg, self.mesh
        self._sect_builders = section_builders(cfg)
        self._sect_folds = {
            "listener": (sharded.ingest_listener_sharded(cfg, mesh),
                         cfg.listener_batch),
            "host": (sharded.ingest_host_sharded(cfg, mesh),
                     wire.MAX_HOSTS_PER_BATCH),
            "task": (sharded.ingest_task_sharded(cfg, mesh),
                     wire.MAX_TASKS_PER_BATCH),
            "ping": (sharded.ping_tasks_sharded(cfg, mesh),
                     wire.MAX_PINGS_PER_BATCH),
            "cpumem": (sharded.ingest_cpumem_sharded(cfg, mesh),
                       wire.MAX_CPUMEM_PER_BATCH),
            "trace": (sharded.ingest_trace_sharded(cfg, mesh),
                      wire.MAX_TRACE_PER_BATCH),
            "delta": (sharded.ingest_delta_sharded(cfg, mesh),
                      decode.DELTA_LANES_DEFAULT),
        }
        self._classify = sharded.classify_sharded(self.cfg, self.mesh)
        self._tick = sharded.tick_5s_sharded(self.cfg, self.mesh)
        self._age_tasks = sharded.age_tasks_sharded(
            self.cfg, self.mesh, self.opts.task_max_age_ticks)
        self._age_apis = sharded.age_apis_sharded(
            self.cfg, self.mesh, self.opts.api_max_age_ticks)
        self._rollup = rollup.rollup_fn(self.cfg, self.mesh)
        self._edge_roll = dg.edge_rollup_fn(
            self.mesh, out_capacity=self.opts.dep_edge_capacity)
        # the once-per-tick fleet-view collective: cluster rollup +
        # merged dep edges + health vector in ONE shard_map program
        # (the in-device madhava→shyama push cycle). run_tick seeds the
        # snapshot/live column caches from its outputs, so dashboard
        # queries and alertdefs reuse the tick's collective instead of
        # re-dispatching their own.
        self._fleet_roll = rollup.fleet_rollup_fn(
            self.cfg, self.mesh, self.opts.dep_edge_capacity)

        from functools import partial
        from jax.sharding import PartitionSpec as P

        from gyeeta_tpu.parallel.mesh import axes_of
        pttl, ettl = (self.opts.dep_pair_ttl_ticks,
                      self.opts.dep_edge_ttl_ticks)
        _axes = axes_of(self.mesh)
        mkey = sharded.mesh_key(self.mesh)

        def _make_dep_age():
            @partial(jax.shard_map, mesh=self.mesh,
                     in_specs=(P(_axes), P()), out_specs=P(_axes),
                     check_vma=False)
            def _dep_age(dep, tick):
                local = jax.tree.map(lambda x: x[0], dep)
                return jax.tree.map(lambda x: x[None],
                                    dg.age(local, tick, pttl, ettl))

            return jax.jit(_dep_age, donate_argnums=(0,))

        # instance-local jits route through the process memo too
        self._dep_age = sharded.memo_sharded(
            ("dep_age", mkey, pttl, ettl), _make_dep_age)
        self._mesh_clusters = sharded.memo_sharded(
            ("mesh_clusters",),
            lambda: jax.jit(dg.mesh_clusters, static_argnums=(1,)))
        # device-health readback: sums over stacked shard leaves (max
        # for stage pressure) → ONE replicated vector, one small
        # transfer per report cadence (no donation — read-only)
        from gyeeta_tpu.engine import step as _step
        self._engine_health = sharded.memo_sharded(
            ("engine_health", self.cfg, mkey),
            lambda: jax.jit(
                lambda s, d: _step.engine_health_vec(self.cfg, s, d)))

        # recovered-hot key set from the previous recovery (promotion
        # edge detection — see Runtime.heavy_recover)
        self._hh_prev_hot: set = set()

        # snapshot publication (query/snapshot.py): one non-donating
        # jitted copy of the stacked (state, dep) per publish — output
        # shardings follow the inputs, so collectives (rollup, edge
        # rollup) run on the frozen copy unchanged. See Runtime.
        self._snap_copy = sharded.memo_sharded(
            ("snap_copy",),
            lambda: jax.jit(lambda t: jax.tree.map(jnp.copy, t)))
        self.snapshot = None
        self._snap_version = 0
        # registry renders on query worker threads vs updates on the
        # serving loop (see Runtime._reg_lock)
        self._reg_lock = threading.RLock()

        from gyeeta_tpu.alerts import columns as AC
        self._aux = {
            "topk": self._topk_columns,
            "hostinfo": lambda: self.hostinfo.columns(self.names),
            "cgroupstate": lambda: self.cgroups.columns(self.names),
            "mountstate": lambda: self.mounts.columns(self.names),
            "netif": lambda: self.netifs.columns(self.names),
            "alerts": lambda: AC.alerts_columns(self.alerts),
            "alertdef": lambda: AC.alertdef_columns(self.alerts),
            "silences": lambda: AC.silences_columns(self.alerts),
            "inhibits": lambda: AC.inhibits_columns(self.alerts),
            "actions": lambda: AC.actions_columns(self.alerts),
            "notifymsg": lambda: self.notifylog.columns(self.names),
            "serverstatus": self._serverstatus_columns,
            "hostlist": self._hostlist_columns,
            "shardlist": self._shardlist_columns,
            "svcipclust": lambda: _dnsmap.annotate_vip_cols(
                self.natclusters.columns(self.names), self.dns),
            "tags": lambda: self.tags.columns(),
            "tracedef": lambda: self.tracedefs.columns(),
            "tracestatus": lambda: self.tracedefs.columns(),
            "traceuniq": self._traceuniq_columns,
            "traceconn": lambda: self.traceconns.columns(
                self.names, svc_task_ids=self._svc_task_ids()),
            "extactiveconn": lambda: self._ext_join("activeconn"),
            "extclientconn": lambda: self._ext_join("clientconn",
                                                    idcol="cliid"),
            "exttracereq": lambda: self._ext_join("tracereq"),
        }

    # ------------------------------------------------------------- ingest
    def _stack(self, builder, recs, lanes):
        return sharded.put_sharded(self.mesh, sharded.shard_batches(
            self.cfg, self.mesh, (builder, lanes), recs, recs["host_id"]))

    def ingest_records(self, recs: dict, shard=None) -> int:
        """Fold a drained ``{subtype: record array}`` dict — the
        post-deframe half of :meth:`feed`. The multi-process ingest
        supervisor (``net/ingestproc.py``) drains shared-memory ring
        slots through here with ``shard=`` set: the worker already
        routed the records by the layout's host hash, so conn/resp
        arrays go STRAIGHT into that shard's staging bucket (no
        re-hash, no argsort — the pre-routed fast path the per-shard
        rings exist for)."""
        self._cols.bump()
        n = self._ingest_sweep_marks(recs.pop(wire.NOTIFY_SWEEP_SEQ, None))
        # conn/resp hot path: hash each record's host to its shard ONCE
        # and stage into per-shard buckets; a shard whose bucket fills a
        # slab (fold_k microbatches' worth) triggers ONE stacked
        # dispatch where every shard's lanes come from its own bucket
        conn = recs.pop(wire.NOTIFY_TCP_CONN, None)
        if conn is not None and len(conn):
            with self._reg_lock:
                self.natclusters.observe_conns(conn)
            if shard is None:
                self._stage_raw(self._conn_raw, self._conn_staged, conn)
            else:
                self._conn_raw[shard].append(conn)
                self._conn_staged[shard] += len(conn)
            self._n_conn_raw += len(conn)
            self.stats.bump("conn_events", len(conn))
            n += len(conn)
        resp = recs.pop(wire.NOTIFY_RESP_SAMPLE, None)
        if resp is not None and len(resp):
            self._note_native_resp(resp)
            if shard is None:
                self._stage_raw(self._resp_raw, self._resp_staged, resp)
            else:
                self._resp_raw[shard].append(resp)
                self._resp_staged[shard] += len(resp)
            self._n_resp_raw += len(resp)
            self.stats.bump("resp_events", len(resp))
            n += len(resp)
        slab_c = self.cfg.fold_k * self.cfg.conn_batch
        slab_r = self.cfg.fold_k * self.cfg.resp_batch
        while (max(self._conn_staged) >= slab_c
               or max(self._resp_staged) >= slab_r):
            self._dispatch_slab(slab_c, slab_r)
        for kind, chunk in decode.drain_chunks(
                recs, self.cfg.conn_batch, self.cfg.resp_batch,
                self.cfg.listener_batch):
            if kind in SECTION_COUNTERS:
                n += self._fold_section(kind, chunk)
            else:
                n += self._ingest_host_kind(kind, chunk)
        return n

    def _fold_section(self, kind: str, recs) -> int:
        """One sweep-subsystem chunk: routed by host, stacked, folded in
        a dispatch of its own."""
        if kind == "trace":
            self._observe_trace(recs)
        fold, lanes = self._sect_folds[kind]
        builder = self._sect_builders[kind]
        batch = self._stack(lambda r, sz: builder(r, sz, self.stats),
                            recs, lanes)
        if kind == "delta":
            self.state, self.dep = fold(self.state, self.dep, batch,
                                        np.int32(self._tick_no))
        else:
            self.state = fold(self.state, batch)
        self.stats.bump(SECTION_COUNTERS[kind], len(recs))
        return len(recs)

    def _stage_bridged_resp(self, rs) -> None:
        """Trace→resp bridge samples join their shards' resp buckets."""
        self._stage_raw(self._resp_raw, self._resp_staged, rs)
        self._n_resp_raw += len(rs)

    def _stage_raw(self, buckets: list, counts: list, recs) -> None:
        """Hash each record's host to its shard (the layout's stable
        ingest-edge rule) and append the per-shard slices — one stable
        argsort per record array, so within-shard arrival order is
        exactly what the pre-routed fold sees (bit-parity with the
        route-at-dispatch path)."""
        if self.n == 1:
            buckets[0].append(recs)
            counts[0] += len(recs)
            return
        dest = np.asarray(
            self.layout.shard_of_host(recs["host_id"].astype(np.int64)))
        order = np.argsort(dest, kind="stable")
        recs = recs[order]
        bounds = np.searchsorted(dest[order], np.arange(self.n + 1))
        for s in range(self.n):
            a, b = int(bounds[s]), int(bounds[s + 1])
            if b > a:
                buckets[s].append(recs[a:b])
                counts[s] += b - a

    def _take_shard_raw(self, buckets: list, counts: list, lanes: int,
                        dtype) -> list:
        """Pop up to ``lanes`` records off EVERY shard's bucket."""
        out = []
        for s in range(self.n):
            got = decode.take_raw(buckets[s], lanes, dtype)
            counts[s] -= len(got)
            out.append(got)
        return out

    def _dispatch_slab(self, lanes_c: int, lanes_r: int) -> None:
        """Decode + fold up to a slab of staged raw records PER SHARD
        in one stacked dispatch. Records were routed at staging time,
        so each shard's lanes build straight from its own bucket."""
        crecs = self._take_shard_raw(self._conn_raw, self._conn_staged,
                                     lanes_c, wire.TCP_CONN_DT)
        rrecs = self._take_shard_raw(self._resp_raw, self._resp_staged,
                                     lanes_r, wire.RESP_SAMPLE_DT)
        nc = sum(len(x) for x in crecs)
        nr = sum(len(x) for x in rrecs)
        self._n_conn_raw -= nc
        self._n_resp_raw -= nr
        for s in range(self.n):
            self._shard_events[s] += len(crecs[s]) + len(rrecs[s])
        span = self.spans.span
        with span("fold_dispatch", nrec=nc + nr,
                  path=native.decode_path()):
            with span("slab_decode", nrec=nc + nr,
                      path=native.decode_path(), annotate=True):
                b = lambda r, sz: decode.conn_batch_fast(  # noqa: E731
                    r, sz, stats=self.stats)
                cbs = sharded.stack_prerouted((b, lanes_c), crecs)
                b = lambda r, sz: decode.resp_batch_fast(  # noqa: E731
                    r, sz, stats=self.stats)
                rbs = sharded.stack_prerouted((b, lanes_r), rrecs)
            with span("fold_h2d", nrec=nc + nr, annotate=True):
                cbs, rbs = self.layout.put(cbs), self.layout.put(rbs)
            # previous dispatch's pressure scalar is ready by now:
            # flush the fullest per-shard stages before folding if
            # headroom is low
            if self._pressure is not None:
                with span("td_flush", annotate=True):
                    if int(self._pressure) > self.cfg.td_stage_cap // 2:
                        self.state = self._td_flush(self.state)
                        self.stats.bump("td_partial_flushes")
            with span("fold_enqueue", nrec=nc + nr, annotate=True):
                # ONE dispatch: fold + dep (a2a pairing) + pressure
                # output — no observation dispatch
                fn = self._fold_dep_slab \
                    if lanes_c > self.cfg.conn_batch \
                    else self._fold_dep_chunk
                self.state, self.dep, self._pressure = fn(
                    self.state, self.dep, cbs, rbs,
                    np.int32(self._tick_no))
                self.stats.bump("fold_dispatches")
        self._td_dirty = True

    def flush(self) -> int:
        """Fold staged raw leftovers (chunk-width dispatches) — state
        is fully query-ready afterwards. Called at every tick/query
        boundary."""
        folded = self._n_conn_raw + self._n_resp_raw
        if folded:
            # evict BEFORE the donating dispatches: cached zero-copy
            # shard views must never alias a donated buffer. (The
            # single-node twin bumps AFTER its folds — safe there
            # because its closures hold jax arrays that error loudly
            # if ever read post-donation, and the single thread has no
            # read window mid-flush; numpy views would read reused
            # memory SILENTLY, so this path evicts up front.)
            self._cols.bump()
        while self._n_conn_raw or self._n_resp_raw:
            self._dispatch_slab(self.cfg.conn_batch,
                                self.cfg.resp_batch)
        return folded

    # ---------------------------------------------------- merged columns
    @staticmethod
    def _shard_leaf(x, s: int):
        """Leaf slice for shard s, read from its addressable buffer
        directly — no cross-device XLA gather, no host transfer."""
        if hasattr(x, "addressable_shards"):
            for sh in x.addressable_shards:
                idx = sh.index[0] if sh.index else None
                if (isinstance(idx, slice) and idx.start is not None
                        and idx.stop is not None
                        and idx.start <= s < idx.stop):
                    if sh.data.platform() == "cpu":
                        # zero-copy host view (see _shard_state for
                        # the lifetime discipline)
                        return np.asarray(sh.data)[s - idx.start]
                    # accelerator: slice stays on-device
                    return sh.data[s - idx.start]
        return np.asarray(x)[s]

    def _shard_state(self, s: int, state=None, cache=None):
        """Shard s's full state slice for the per-shard column
        providers (``state``/``cache`` default to the LIVE state and
        column memo; the time-travel tier passes a shard-materialized
        state and its snapshot-scoped cache).

        On the CPU platform the slice is a zero-copy NUMPY VIEW of the
        shard's buffer (measured: eager jnp slicing costs ~26-430 ms
        PER LEAF in dispatch overhead — ~10 s per merge at the 51k
        geometry, the r5 post-tick cold-query profile; the view is
        0.01 ms). Views alias device buffers, so they must never
        outlive a donating fold: ColumnCache holds them (here and in
        the providers' LazyCols closures) and ``feed`` bumps/evicts at
        entry, BEFORE any donating dispatch — queries and feeds share
        one thread, so no view survives into a fold. On accelerators
        the device-side slice path keeps data on-chip."""
        state = self.state if state is None else state
        cache = self._cols if cache is None else cache
        return cache.get(
            f"__shard_state_{s}",
            lambda: jax.tree.map(lambda x: self._shard_leaf(x, s),
                                 state))

    def _hosts_ever_reported(self, s: int) -> np.ndarray:
        """Shard s's ``host_last_tick`` as a host array — the single
        definition of "has ever reported" (last tick >= 0), shared by
        hostlist and serverstatus so the two can't diverge."""
        return np.asarray(self._shard_leaf(self.state.host_last_tick, s))

    def _merged_columns(self, subsys: str):
        """Cluster-wide (cols, mask), version-cached: the per-shard
        snapshot gather recomputes only after state actually changed
        (feed/tick/td-flush bump the cache version) — between ticks
        queries serve from the cached merge (query freshness, VERDICT
        r3 weak #4). Registry/CRUD-backed aux views are never cached
        (they mutate without a version bump)."""
        if "@" in subsys:
            # subsys@window: an alertdef with a window field evaluates
            # against the time-travel tier's windowed aggregate
            base, _, win = subsys.partition("@")
            if self.timeview is None:
                raise ValueError(
                    "windowed alertdef needs history shards "
                    "(hist_shard_dir)")
            return self.timeview.window_columns_for(base, win)
        if subsys in self._aux:
            return self._aux[subsys]()
        out = self._cols.get(
            subsys, lambda: self._merged_columns_uncached(subsys))
        if subsys == fieldmaps.SUBSYS_PROCINFO:
            # joined OUTSIDE the cache: tags mutate via CRUD without a
            # state version bump
            out = self.tags.with_tags(out)
        return out

    def _merged_columns_uncached(self, subsys: str):
        return self._merged_columns_state(subsys, self.state, self.dep,
                                          self._cols, live=True)

    def _merged_columns_state(self, subsys: str, state, dep, cache,
                              live: bool = False, reg: bool = False):
        """Per-shard provider outputs concatenated, or collective-
        rollup-backed for global subsystems — parameterized on
        (state, dep, cache) so the SAME pipeline serves the live mesh
        AND shard-materialized historical snapshots
        (``history/timeview.py``) AND the per-tick published snapshot
        (``query/snapshot.py``). ``live`` routes recursive lookups
        through the top-level cached path and keeps registry-backed
        joins (which have no historical source) available; ``reg``
        keeps the registry joins available over a NON-live state (the
        published snapshot: engine columns frozen, registries live)."""
        if live:
            def get(s):
                return self._merged_columns(s)
        else:
            def get(s):
                return cache.get(
                    s, lambda: self._merged_columns_state(
                        s, state, dep, cache, reg=reg))
        if subsys == fieldmaps.SUBSYS_SVCINFO:
            if not (live or reg):
                raise ValueError(
                    "svcinfo is registry-backed — not available "
                    "historically")
            return self.svcreg.columns(self.names)
        if subsys == fieldmaps.SUBSYS_SVCSUMM:
            # group AFTER merging: one host's services span shards
            cols, live_m = get(fieldmaps.SUBSYS_SVCSTATE)
            return api.svcsumm_from_svc(cols, live_m, self.names)
        if subsys == fieldmaps.SUBSYS_EXTSVCSTATE:
            if not (live or reg):
                raise ValueError(
                    "extsvcstate joins the live registry — not "
                    "available historically")
            cols, live_m = get(fieldmaps.SUBSYS_SVCSTATE)
            info_cols, _ = self.svcreg.columns(self.names)
            return api.extsvc_join(cols, live_m, info_cols)
        if subsys == fieldmaps.SUBSYS_SVCPROCMAP:
            if not (live or reg):
                raise ValueError(
                    "svcprocmap joins the live registry — not "
                    "available historically")
            tcols, tlive = get(fieldmaps.SUBSYS_TASKSTATE)
            info_cols, _ = self.svcreg.columns(self.names)
            return api.svcprocmap_join(tcols, tlive, info_cols)
        if subsys in (fieldmaps.SUBSYS_SVCDEP, fieldmaps.SUBSYS_SVCMESH,
                      fieldmaps.SUBSYS_ACTIVECONN,
                      fieldmaps.SUBSYS_CLIENTCONN):
            # run_tick seeds __edgeset from the fleet-rollup collective;
            # a miss (between-tick mutation, historical state) pays the
            # standalone edge-rollup dispatch
            es = cache.get("__edgeset", lambda: self._edge_roll(dep))
            return self._dep_cols_from_edgeset(subsys, es,
                                               state=state, cache=cache)
        if subsys == fieldmaps.SUBSYS_FLOWSTATE:
            ru = cache.get("__rollup", lambda: self._rollup(state))
            k = min(128, int(ru.flow_topk.counts.shape[0]))
            f_hi, f_lo, f_bytes = topk.query(ru.flow_topk, k)
            f_hi, f_lo = np.asarray(f_hi), np.asarray(f_lo)
            f_bytes = np.asarray(f_bytes)
            cols = {
                "flowid": api._hex_id(f_hi, f_lo),
                "bytes": f_bytes,
                "evictedbytes": np.full(len(f_bytes),
                                        float(ru.flow_topk.evicted)),
            }
            return cols, f_bytes > 0
        if subsys == fieldmaps.SUBSYS_CLUSTERSTATE:
            from gyeeta_tpu.semantic import hoststate as HS
            hcols, reported = get(fieldmaps.SUBSYS_HOSTSTATE)
            c = HS.cluster_state(np.asarray(hcols["state"]),
                                 valid=reported)
            return ({k: np.array([float(v)]) for k, v in c.items()},
                    np.ones(1, bool))
        provider = api._COLUMNS_OF[subsys]
        parts = [provider(self.cfg,
                          self._shard_state(s, state, cache),
                          names=self.names)
                 for s in range(self.n)]
        from gyeeta_tpu.query.lazycols import LazyCols, merge_lazy
        if all(isinstance(p[0], LazyCols) for p in parts):
            # lazy groups concatenate on first reference — a sharded
            # query reads only the groups its filter/sort names
            cols = merge_lazy([p[0] for p in parts],
                              widths=[len(p[1]) for p in parts])
        else:
            cols = {k: np.concatenate([p[0][k] for p in parts])
                    for k in parts[0][0]}
        mask = np.concatenate([p[1] for p in parts])
        return cols, mask

    def _gathered_task_names(self, hi, lo, state=None, cache=None):
        """Resolve task-group callers via the gathered task slabs."""
        keys, comms, lives = [], [], []
        for s in range(self.n):
            k, c, lv = api._task_slab_arrays(
                self._shard_state(s, state, cache))
            keys.append(k)
            comms.append(c)
            lives.append(lv)
        return api.task_comm_names_from(
            self.names, np.concatenate(keys), np.concatenate(comms),
            np.concatenate(lives), hi, lo)

    def _dep_cols_from_edgeset(self, subsys: str, es, state=None,
                               cache=None):
        from gyeeta_tpu.engine import table

        if subsys in (fieldmaps.SUBSYS_ACTIVECONN,
                      fieldmaps.SUBSYS_CLIENTCONN):
            snap = api.dep_edges_view(None, self, merged=es)
            if subsys == fieldmaps.SUBSYS_CLIENTCONN:
                return api.clientconn_from_edges(
                    snap, self.names,
                    lambda hi, lo: self._gathered_task_names(
                        hi, lo, state, cache))
            return api.activeconn_from_edges(snap, self.names)
        if subsys == fieldmaps.SUBSYS_SVCMESH:
            cap = 2 * es.nconn.shape[0]
            ntbl, labels, sizes = self._mesh_clusters(es, cap)
            n_hi, n_lo = np.asarray(ntbl.key_hi), np.asarray(ntbl.key_lo)
            cols = {
                "svcid": api._hex_id(n_hi, n_lo),
                "svcname": api._names_of(self.names, wire.NAME_KIND_SVC,
                                         n_hi, n_lo),
                "clusterid": np.asarray(labels),
                "clustersize": np.asarray(sizes),
            }
            return cols, np.asarray(table.live_mask(ntbl))
        return api.dep_cols_from_edges(
            api.dep_edges_view(None, self, merged=es), self.names,
            lambda hi, lo: self._gathered_task_names(hi, lo, state,
                                                     cache), obs=self)

    # -------------------------------------------------- heavy hitters
    def heavy_recover(self) -> dict:
        """Cluster-wide heavy-hitter recovery: the rollup collective
        decodes every shard's invertible buckets, gathers the
        candidates across shards (`all_gather`, the madhava→shyama
        candidate pull) and estimates each against the globally-merged
        CMS; the host merges with the merged exact top-K lanes. One
        collective dispatch + one small readback per tick."""
        from gyeeta_tpu.sketch import invertible

        self.flush()
        with self.spans.span("topk_recover"):
            ru = self._cols.get("__rollup",
                                lambda: self._rollup(self.state))
            rec = {
                "topk_hi": np.asarray(ru.flow_topk.key_hi),
                "topk_lo": np.asarray(ru.flow_topk.key_lo),
                "topk_counts": np.asarray(ru.flow_topk.counts),
                "topk_est": np.asarray(ru.hh_topk_est),
                "hh_hi": np.asarray(ru.hh_hi),
                "hh_lo": np.asarray(ru.hh_lo),
                "hh_ok": np.asarray(ru.hh_ok),
                "hh_est": np.asarray(ru.hh_est),
            }
            evicted = float(np.asarray(ru.flow_topk.evicted))
            total = float(np.asarray(ru.hh_total_mass))
        self.stats.bump("topk_recover_readbacks")
        err_term = invertible.cms_error_term(total, self.cfg.cms_width)
        hot_thresh = (self.cfg.hh_hot_frac * total
                      if self.cfg.hh_hot_frac > 0 else 0.0)
        flows, recovered, hot = invertible.merge_recovered_np(
            rec, err_term, hot_thresh)
        new_hot = hot - self._hh_prev_hot
        if new_hot:
            self.stats.bump("topk_hot_promotions", len(new_hot))
        self._hh_prev_hot = hot
        self.stats.gauge("topk_recovered_keys", float(len(recovered)))
        self.stats.gauge("topk_evicted_mass", evicted)
        return {"flows": flows, "recovered_keys": len(recovered),
                "evicted": evicted, "err_term": err_term,
                "total_mass": total, "new_hot": len(new_hot)}

    def _topk_columns(self):
        """topk subsystem over the mesh: cluster-wide heavy flows
        (rollup recovery) + dense rankings over the MERGED svc/api
        columns — the same union builder as the single-node runtime."""
        rec = self._cols.get("__hh_recover", self.heavy_recover)
        return api.heavy_topk_columns(
            rec["flows"],
            svc=self._merged_columns(fieldmaps.SUBSYS_SVCSTATE),
            trace=self._merged_columns(fieldmaps.SUBSYS_TRACEREQ))

    def _hostlist_columns(self):
        """hostlist over the mesh: each shard's host panel holds only
        its routed hosts (global ids), so concatenating the seen rows
        of every shard yields the cluster host list."""
        parts_id, parts_age = [], []
        for s in range(self.n):
            last = self._hosts_ever_reported(s)
            seen = np.nonzero(last >= 0)[0]
            parts_id.append(seen)
            parts_age.append(self._tick_no - last[seen])
        ids = np.concatenate(parts_id)
        age = np.concatenate(parts_age)
        order = np.argsort(ids, kind="stable")
        ids, age = ids[order], age[order]
        from gyeeta_tpu.ingest import wire as W
        names = self.names.resolve_array(W.NAME_KIND_HOST,
                                         ids.astype(np.uint64))
        cols = {
            "hostid": ids.astype(np.float64),
            "hostname": names,
            "up": age <= api.DOWN_AFTER_TICKS,
            "lastseen": age.astype(np.float64),
        }
        return cols, np.ones(len(ids), bool)

    def _ext_join(self, base_subsys: str, idcol: str = "svcid"):
        cols, live = self._merged_columns(base_subsys)
        info_cols, _ = self.svcreg.columns(self.names)
        return api.info_join(cols, live, info_cols, idcol=idcol)

    def _svc_task_ids(self):
        """Hex process-group ids serving a listener (traceconn csvc)."""
        cols, live = self._merged_columns(fieldmaps.SUBSYS_TASKSTATE)
        zero = "0" * 16
        return {t for t, r, ok in zip(cols["taskid"], cols["relsvcid"],
                                      live) if ok and r != zero}

    def _traceuniq_columns(self):
        tcols, tlive = self._merged_columns(fieldmaps.SUBSYS_TRACEREQ)
        return api.traceuniq_from_trace(tcols, tlive)

    def trace_control_diff(self, hosts=None):
        """Mesh analogue of Runtime.trace_control_diff: evaluate
        tracedefs against the (registry-backed) svcinfo inventory."""
        targets = self.tracedefs.target_svcids(self._merged_columns)
        return self.tracedefs.diff_for_hosts(targets, hosts=hosts)

    def _shardlist_columns(self):
        """One row per mesh shard (the madhavalist analogue): live
        rows, hosts, fold counters, and drop diagnostics per shard."""
        rows = []
        for sidx in range(self.n):
            st = self._shard_state(sidx)
            rows.append({
                "shard": float(sidx),
                "nsvc": float(np.asarray(st.tbl.n_live)),
                "nhosts": float((np.asarray(st.host_last_tick) >= 0)
                                .sum()),
                "nconn": float(np.asarray(st.n_conn)),
                "nresp": float(np.asarray(st.n_resp)),
                "ntaskrows": float(np.asarray(st.task_tbl.n_live)),
                "ndropped": float(np.asarray(st.tbl.n_drop)
                                  + np.asarray(st.task_tbl.n_drop)),
            })
        cols = {k: np.array([r[k] for r in rows], np.float64)
                for k in rows[0]}
        return cols, np.ones(self.n, bool)

    def _serverstatus_columns(self):
        ru = self._cols.get("__rollup",
                            lambda: self._rollup(self.state))
        # "hosts that have EVER reported" (same quantity the single-node
        # runtime reports) — each shard's host panel holds only its own
        # routed hosts, so the per-shard counts are disjoint and sum
        nhosts = sum(int((self._hosts_ever_reported(s) >= 0).sum())
                     for s in range(self.n))
        return api.serverstatus_columns(self, self._tick_no, nhosts,
                                        float(ru.n_svc_live))

    # ----------------------------------------------------- snapshot tier
    def publish_snapshot(self):
        """Freeze the stacked mesh state into an immutable
        :class:`~gyeeta_tpu.query.snapshot.EngineSnapshot` (see
        ``Runtime.publish_snapshot`` — same double-buffer contract; the
        copied leaves keep their shardings, so the merged-columns
        pipeline and the rollup collectives serve the frozen view
        unchanged)."""
        from gyeeta_tpu.query.snapshot import EngineSnapshot
        with self.spans.span("snapshot_publish", annotate=True):
            state, dep = self._snap_copy((self.state, self.dep))
        self._snap_version += 1
        snap = EngineSnapshot(
            self, state, dep, tick=self._tick_no,
            published_at=self._clock(), version=self._snap_version,
            result_cache_max=int(os.environ.get(
                "GYT_QUERY_CACHE_MAX", "1024")))
        self.snapshot = snap
        if self._tick_p0 is not None:     # see Runtime.publish_snapshot
            self.spans.interval("tick_visible", self._tick_p0,
                                nrec=self._tick_no)
            self._tick_p0 = None
        self.stats.bump("snapshots_published")
        self.stats.gauge("snapshot_tick", float(self._tick_no))
        self.stats.gauge("snapshot_age_seconds", 0.0)
        return snap

    # ------------------------------------------------------------ cadence
    def td_drain(self, max_iters: int | None = None) -> int:
        """Drain per-shard digest stages with O(m) partial flushes
        against the global pressure scalar — same host-trigger design
        as the single-chip runtime (no in-graph cond; see
        ``Runtime.td_drain``). Unbounded by default; ``run_tick``
        bounds it to amortize a fully-active slab across ticks. No
        query subsystem reads the digest, so this is off the <1s
        query path."""
        self.flush()
        # the flushes below DONATE state: cached zero-copy shard views
        # (and LazyCols closures) from the current version must be
        # evicted BEFORE the first donating dispatch, or a later
        # cache-hit query would read reused buffers
        self._cols.bump()
        i = 0
        while max_iters is None or i < max_iters:
            if int(self._td_pressure(self.state)) <= 0:
                self._td_dirty = False
                self._pressure = None
                break
            self.state = self._td_flush(self.state)
            self.stats.bump("td_partial_flushes")
            i += 1
        return i

    def _shard_rate_gauges(self) -> None:
        """Per-shard fold rates + staged-slab occupancy at tick cadence
        (host-side counters only — no device readback). Rendered as
        ``gyt_shard_fold_ev_per_sec{shard=...}`` and
        ``gyt_shard_stage_occupancy{shard=...}``."""
        now = self._clock()
        dt = max(now - self._shard_rate_t, 1e-9)
        delta = self._shard_events - self._shard_rate_mark
        for s in range(self.n):
            self.stats.gauge(f"shard_fold_ev_per_sec|shard={s}",
                             round(float(delta[s]) / dt, 1))
        cap = max(1, self.cfg.fold_k
                  * (self.cfg.conn_batch + self.cfg.resp_batch))
        for s in range(self.n):
            occ = (self._conn_staged[s] + self._resp_staged[s]) / cap
            self.stats.gauge(f"shard_stage_occupancy|shard={s}",
                             round(occ, 4))
        self._shard_rate_t = now
        self._shard_rate_mark = self._shard_events.copy()

    def engine_health(self, vec=None) -> dict:
        """Cluster-wide device-health gauges (sums over every shard's
        slabs; max stage pressure) — the sharded twin of
        ``Runtime.engine_health``, folded into the same ``Stats`` gauge
        names so /metrics parity holds across runtimes. ``run_tick``
        passes the fleet-rollup collective's health vector; standalone
        callers (scrapes between ticks) pay one batched readback."""
        if vec is None:
            vec = np.asarray(self._engine_health(self.state, self.dep))
        gauges = obs_health.gauges_from_vec(
            vec, obs_health.capacities(self.cfg, self.opts,
                                       n_shards=self.n))
        gauges["native_decode_available"] = \
            1.0 if native.available() else 0.0
        # what each device holds now and at its high-water mark (none
        # on the CPU backend, which reports no memory_stats)
        gauges.update(xlamon.device_gauges())
        if self.journal is not None:
            gauges.update(self.journal.gauges())
        for k, v in gauges.items():
            self.stats.gauge(k, v)
        return gauges

    def run_tick(self) -> dict:
        self._tick_p0 = time.perf_counter()
        try:
            with self.spans.span("tick", nrec=self._tick_no):
                return self._run_tick()
        finally:
            self._tick_p0 = None      # a tick that failed before its swap

    def _run_tick(self) -> dict:
        """Sharded 5s pass: classify → alerts on merged columns → window
        tick → ageing. Every step runs inside a leaf span, as in
        ``Runtime._run_tick`` (plus ``rollup``; no history sweep)."""
        report = {}
        span = self.spans.span
        with span("tick.flush", annotate=True):
            self.flush()
        if self._td_dirty:    # tick-cadence digest compression (bounded)
            with span("tick.td_drain", annotate=True):
                self.td_drain(max_iters=self.opts.td_drain_iters_per_tick)
        with span("tick.classify", annotate=True):
            self.state = self._classify(self.state)
            self._cols.bump()
        # publish the post-classify view and route alert evaluation
        # through it — tick-time work pre-warms the snapshot's merged
        # columns for the dashboards (see Runtime._run_tick)
        snap = self.publish_snapshot()
        # ---- the once-per-tick cross-shard roll-up: cluster rollup +
        # merged dep edges + health vector in ONE collective program
        # over the FROZEN snapshot leaves. Both the snapshot's and the
        # live column cache are seeded from its outputs, so svcdep/
        # flowstate/serverstatus/topk queries and alertdefs this window
        # reuse the tick's collective instead of re-dispatching.
        t_ru = self._clock()
        with span("rollup", annotate=True):
            fv = self._fleet_roll(snap.state, snap.dep)
            health_vec = np.asarray(fv.health)
        self.stats.gauge("rollup_seconds",
                         round(self._clock() - t_ru, 6))
        for cache in (snap._cols, self._cols):
            cache.get("__rollup", lambda: fv.rollup)
            cache.get("__edgeset", lambda: fv.edges)
        # per-tick heavy-hitter recovery (memoized — an alertdef on
        # `topk` and queries until the next feed reuse the readback)
        ev = self.opts.hh_recover_every_ticks
        if ev and self.cfg.hh_width > 0 \
                and (self._tick_no + 1) % ev == 0:
            with span("tick.hh_recover", annotate=True):
                report["topk_recovered"] = self._cols.get(
                    "__hh_recover", self.heavy_recover)["recovered_keys"]
        # alert eval short-circuits BEFORE any column render when no
        # realtime def is enabled (counted; pending group-wait batches
        # still flush on schedule)
        with span("tick.alerts", annotate=True):
            if self.alerts.wants_realtime():
                fired = self.alerts.check(None, columns_fn=snap.columns)
            else:
                self.stats.bump("alert_eval_skipped")
                fired = self.alerts.flush_groups()
        report["alerts_fired"] = len(fired)
        for a in fired:
            self.notifylog.add_alert(a)
        self._tick_no += 1
        report["tick"] = self._tick_no
        self.stats.gauge("tick", self._tick_no)
        # device health from the SAME collective (no extra readback);
        # the drop-pressure signal (VERDICT r4 #10) feeds off the vector
        from gyeeta_tpu.utils import droppressure
        with span("tick.health", annotate=True):
            health = self.engine_health(vec=health_vec)
            self._shard_rate_gauges()
            self._last_drops = droppressure.check(
                obs_health.drops_for_pressure(health),
                {"svc": self.cfg.svc_capacity,
                 "task": self.cfg.task_capacity,
                 "api": self.cfg.api_capacity,
                 "dep": self.opts.dep_pair_capacity},
                getattr(self, "_last_drops", {}),
                self.notifylog, self.stats)
        with span("tick.roll", annotate=True):
            self.state = self._tick(self.state)
            if self._tick_no % self.opts.task_age_every_ticks == 0:
                self.state = self._age_tasks(self.state)
                self.state = self._age_apis(self.state)
            self.dep = self._dep_age(self.dep, np.int32(self._tick_no))
            with self._reg_lock:      # ageing structurally mutates the
                self.cgroups.age()    # registries snapshot aux renders
                self.mounts.age()     # iterate on worker threads
                self.netifs.age()
                self.natclusters.age()
                self.traceconns.age()
        with span("tick.close", annotate=True):
            self._tick_close(report)
        return report

    def _tick_close(self, report: dict) -> None:
        """The journal's fsync backstop and the checkpoint-with-WAL-
        position (same durability contract as the single-node Runtime:
        the checkpoint records the fsynced journal position and
        supersedes older segments)."""
        if self.journal is not None:
            self.journal.poll()
        if (self.opts.checkpoint_dir
                and self._tick_no % self.opts.checkpoint_every_ticks
                == 0):
            from gyeeta_tpu.utils import checkpoint as ckpt
            from gyeeta_tpu.utils import journal as J
            extra = J.checkpoint_extra(self, self._tick_no)
            path = ckpt.save(
                f"{self.opts.checkpoint_dir}/"
                f"gyt_ckpt_{self._tick_no:08d}.npz",
                self.cfg, self.state, extra=extra)
            J.post_checkpoint_truncate(self, extra)
            report["checkpoint"] = str(path)
            self.stats.bump("checkpoints")
        # the window tick / ageing above changed every view
        self._cols.bump()

    # -------------------------------------------------------------- query
    def crud(self, req: dict) -> dict:
        from gyeeta_tpu.query import crud as CR
        with self._reg_lock:
            out = CR.crud(self, req)
        snap = self.snapshot          # CRUD invalidates aux views
        if snap is not None:
            snap.on_mutation()
        return out

    def query(self, req: dict) -> dict:
        if req.get("op"):
            return self.crud(req)
        if "multiquery" in req:
            from gyeeta_tpu.query import crud as CR
            return CR.multiquery(self.query, req)
        if req.get("consistency") == "snapshot":
            return self.query_snapshot(req)
        if "consistency" in req:
            req = dict(req)
            if req.pop("consistency") != "strong":
                raise ValueError(
                    "consistency must be 'snapshot' or 'strong'")
        # process-local subsystems (selfstats + metrics exposition) —
        # shared routing with the single-node Runtime (api.py)
        out = api.local_response(self, req)
        if out is not None:
            return out
        # time-travel tier: at=/window=/tstart/tend materialize
        # compaction shards (the mesh has no relational store, so every
        # historical request routes here)
        from gyeeta_tpu.history.timeview import route_historical
        out = route_historical(self, req)
        if out is not None:
            return out
        self.stats.bump("queries")
        self.flush()          # live queries see all staged records
        with self.spans.span("query", annotate=True):
            return api.execute(self.cfg, None, QueryOptions.from_json(req),
                               names=self.names,
                               columns_fn=self._merged_columns)

    def query_snapshot(self, req: dict) -> dict:
        """Serve a live query from the last published snapshot (no
        flush, no fold-path dispatch; safe from worker threads) — the
        mesh twin of ``Runtime.query_snapshot``."""
        req = {k: v for k, v in req.items() if k != "consistency"}
        snap = self.snapshot
        if snap is None:
            snap = self.publish_snapshot()
        if req.get("subsys") in api.LOCAL_SUBSYS:
            return api.local_response(self, req, snapshot=snap)
        from gyeeta_tpu.history.timeview import route_historical
        out = route_historical(self, req)
        if out is not None:
            return out
        self.stats.bump("queries")
        with self.spans.span("query", annotate=True):
            return snap.query(req)

    def close(self) -> None:
        """Release background workers (alert delivery, DNS resolver).
        Idempotent — mirrors Runtime.close()."""
        self.alerts.close()
        self.dns.close()
        if self.journal is not None:
            self.journal.close()      # fsync + close (idempotent)

    # -------------------------------------------------- restore/recovery
    def restore(self, path) -> dict:
        """Restore a checkpoint saved by a SAME-GEOMETRY mesh run (the
        stacked ``(n_shards, …)`` leaves re-shard onto this mesh).
        Mirrors ``Runtime.restore``: staged records and partial-frame
        bytes from before the restore are dropped (folding them into
        checkpointed state would double-count)."""
        from gyeeta_tpu.utils import checkpoint as ckpt

        self._conn_raw = [[] for _ in range(self.n)]
        self._resp_raw = [[] for _ in range(self.n)]
        self._conn_staged = [0] * self.n
        self._resp_staged = [0] * self.n
        self._n_conn_raw = self._n_resp_raw = 0
        self._pending = b""
        self._cols.bump()
        self._cols.clear()
        self._td_dirty = True
        self._pressure = None
        state_np, extra = ckpt.restore(
            path, self.cfg, self.state,
            flat_widths=flat_widths(self.cfg))
        # re-shard every leaf with its live counterpart's sharding (the
        # checkpoint stores gathered host arrays; shapes were already
        # validated against this mesh's stacked geometry)
        self.state = jax.tree_util.tree_map(
            lambda a, ref: jax.device_put(a, ref.sharding),
            state_np, self.state)
        # the dep graph is not checkpointed: reset (edges rebuild from
        # live traffic), placed per the layout like __init__
        self.dep = self.layout.put(
            jax.tree.map(
                lambda x: np.broadcast_to(
                    np.asarray(x)[None], (self.n,) + np.asarray(x).shape),
                dg.init(self.opts.dep_pair_capacity,
                        self.opts.dep_edge_capacity)))
        self._tick_no = int(extra.get("tick", 0))
        self._sweep_last_seq = {
            int(k): int(v)
            for k, v in extra.get("sweep_seq", {}).items()}
        # republish over the restored view (see Runtime.restore)
        if self.snapshot is not None:
            self.publish_snapshot()
        return extra

    def replay_journal(self, pos=None) -> dict:
        """Re-fold WAL chunks from ``pos`` through the normal
        decode/fold path (chunks journal once at the mesh's single
        ingest edge; ``feed`` routes records per-shard by host_id, so
        replay is per-shard by construction)."""
        from gyeeta_tpu.utils import journal as J
        return J.replay_journal(self, pos)

    def rollup_stats(self) -> dict:
        """Replicated cluster totals (the MS_CLUSTER_STATE analogue)."""
        self.flush()          # staged slab records must count
        ru = self._rollup(self.state)
        return {
            "n_conn": float(ru.n_conn), "n_resp": float(ru.n_resp),
            "n_svc_live": float(ru.n_svc_live),
            "n_hosts_up": float(ru.n_hosts_up),
        }
