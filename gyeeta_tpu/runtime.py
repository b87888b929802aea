"""Runtime: the aggregation-server process loop (the ``madhava`` analogue).

Owns the engine state and composes every tier: byte streams in (native
deframe), columnar folds onto the device, the 5s cadence (window tick +
semantic classify + alert check), history snapshots, checkpointing, and
table compaction — the role of madhava's L1/L2 thread architecture and
scheduler domains (``server/gy_mconnhdlr.h:53-75``,
``common/gy_scheduler.h:220``), but single-controller and event-driven:
``feed()`` ingests bytes; ``run_tick()`` closes a 5s window. No thread
pool — the device pipeline is the concurrency.
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
from gyeeta_tpu.engine import aggstate, compact, step
from gyeeta_tpu.engine.aggstate import EngineCfg
from gyeeta_tpu.history import open_store
from gyeeta_tpu.hostingest import SECTION_COUNTERS, HostIngest, \
    section_builders
from gyeeta_tpu.obs import health as obs_health
from gyeeta_tpu.obs import xlamon
from gyeeta_tpu.obs.spans import SpanTracer
from gyeeta_tpu.parallel import depgraph as dg
from gyeeta_tpu.ingest import decode, native, pack, wire
from gyeeta_tpu.query import api
from gyeeta_tpu.semantic import derive
from gyeeta_tpu.utils import checkpoint as ckpt
from gyeeta_tpu.utils import dnsmap as _dnsmap
from gyeeta_tpu.utils.config import RuntimeOpts
from gyeeta_tpu.utils.intern import InternTable
from gyeeta_tpu.utils.selfstats import Stats


_JIT_MEMO: dict = {}


def _memo_jit(key: tuple, make):
    """Process-wide compiled-function memo. Every Runtime used to
    build its own ``jax.jit`` wrappers (fresh lambdas → zero cache
    reuse), so each construction re-traced AND re-compiled the whole
    fold family — seconds per instance, minutes across a test suite
    that builds dozens of runtimes with identical geometry. The
    compiled functions are pure (donation included — they never hold
    instance state), so instances with the same key share them. Every
    value the jitted closure captures MUST be part of the key (the
    EngineCfg tuple, relevant RuntimeOpts fields, section-presence
    names)."""
    fn = _JIT_MEMO.get(key)
    if fn is None:
        fn = make()
        _JIT_MEMO[key] = fn
    return fn


# Per-subsystem staging-slab lane capacities of the fold slab (fixed →
# one compiled shape per presence combination). Sized at 1-2 wire-max
# batches per section: sweep subsystems arrive at 5s cadence, so a
# deeper slab only adds padding cost to the dispatch.
_SLAB_LANES = {
    "listener": 2 * wire.MAX_LISTENERS_PER_BATCH,
    "host": wire.MAX_HOSTS_PER_BATCH,
    "task": 2 * wire.MAX_TASKS_PER_BATCH,
    "cpumem": wire.MAX_CPUMEM_PER_BATCH,
    "trace": wire.MAX_TRACE_PER_BATCH,
    "ping": wire.MAX_PINGS_PER_BATCH,
    # SKETCH_DELTA records per dispatch (each expands into its
    # per-family payload lanes host-side): the drain_chunks chunk size
    "delta": decode.DELTA_LANES_DEFAULT,
}


# wire subtype per device-fold kind (the raw-backlog concat dtype);
# hostingest holds their selfstats counters and columnar builders
_SECTION_SUBTYPES = {
    "listener": wire.NOTIFY_LISTENER_STATE, "host": wire.NOTIFY_HOST_STATE,
    "task": wire.NOTIFY_AGGR_TASK_STATE, "ping": wire.NOTIFY_TASK_PING,
    "cpumem": wire.NOTIFY_CPU_MEM_STATE, "trace": wire.NOTIFY_REQ_TRACE,
    "delta": wire.NOTIFY_SKETCH_DELTA,
}


def fold_all_name(names: tuple) -> str:
    """Function (hence compiled-module) name of one ``fold_all``
    variant: ``fn_connresp[_<other sections>]`` when it folds the
    conn/resp slab, ``fn_sections_<sections>`` otherwise."""
    rest = [k for k in names if k != "connresp"]
    head = "fn_connresp" if "connresp" in names else "fn_sections"
    return "_".join([head] + rest)


class Runtime(HostIngest):
    def __init__(self, cfg: Optional[EngineCfg] = None,
                 opts: Optional[RuntimeOpts] = None,
                 clock=None):
        self.cfg = cfg or EngineCfg()
        self.opts = opts or RuntimeOpts()
        self.state = aggstate.init(self.cfg)
        self.stats = Stats()
        # the one stage timer: span ring + timing histograms + profiler
        # annotations on the leaves (obs/spans.py)
        self.spans = SpanTracer(stats=self.stats,
                                annotation=jax.profiler.TraceAnnotation)
        self._tick_p0 = None          # run_tick entry, until its publish
        self.alerts = AlertManager(self.cfg, clock=clock)
        self.history = (open_store(self.opts.history_db)
                        if self.opts.history_db else None)
        # batched single-writer thread: run_tick renders snapshot rows
        # (device readbacks stay on the fold thread) and ENQUEUES; a
        # slow sqlite/pg write can no longer stall the tick loop.
        # Read paths that need read-your-writes (db-mode alertdefs,
        # historical SQL queries) call barrier() first.
        self._histwriter = None
        if self.history is not None:
            from gyeeta_tpu.history.histwriter import HistoryWriter
            self._histwriter = HistoryWriter(
                self.history, stats=self.stats,
                max_queue=self.opts.history_queue_max)
        self._clock = clock or time.time
        # write-ahead event journal (utils/journal.py): every accepted
        # event-stream chunk appends post-validation/pre-fold; recovery
        # re-folds from the checkpoint's recorded position (bounds data
        # loss to the last group fsync, not the last checkpoint)
        self.journal = None
        if self.opts.journal_dir:
            from gyeeta_tpu.utils.journal import Journal
            self.journal = Journal(
                self.opts.journal_dir,
                segment_max_bytes=self.opts.journal_segment_mb << 20,
                fsync_bytes=self.opts.journal_fsync_kb << 10,
                fsync_ms=self.opts.journal_fsync_ms,
                backlog_max_bytes=self.opts.journal_backlog_mb << 20,
                stats=self.stats, clock=clock)
        self._journal_replaying = False
        # time-travel query tier (history/timeview.py): at=/window=
        # requests materialize compaction shards into transient engine
        # snapshots served through the unchanged query path. The
        # journal truncate floor starts at the compactor's durable
        # position so checkpoints never delete unconsumed segments.
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
                        # per-shard floors against a flat journal
                        # (layout drift): hold back at the lowest
                        fl = min(fl) if fl else 0
                    self.journal.set_truncate_floor(fl)
                else:
                    self.journal.set_truncate_floor(0)
        # per-host sweep-seq high-water marks (NOTIFY_SWEEP_SEQ): the
        # WAL dedup state — checkpointed, rebuilt by replay, echoed to
        # reconnecting agents so resend + replay never double-counts
        self._sweep_last_seq: dict[int, int] = {}
        self._tick_no = 0             # host-side mirror of the window tick
        self._pending = b""           # partial-frame resume buffer
        # conn/resp hot path stages RAW record arrays; decode happens
        # once per K-slab (one native columnar pass, free reshape into
        # the stacked layout) instead of per chunk + np.stack
        self._conn_raw: list = []
        self._resp_raw: list = []
        self._n_conn_raw = 0
        self._n_resp_raw = 0
        # last tick each host sent a native RESP_SAMPLE (the trace→resp
        # bridge's precedence rule, HostIngest._note_native_resp)
        self._host_resp_tick = np.full(self.cfg.n_hosts, -(10 ** 9),
                                       np.int64)
        self._td_dirty = False        # digest stage may be non-empty
        from gyeeta_tpu.utils.colcache import ColumnCache
        self._cols = ColumnCache()    # version-keyed snapshot memo
        # every state→state jit donates its input: without donation XLA
        # copies the whole AggState per call — 3 GiB ≈ 2 s/dispatch at
        # north-star geometry (the r4 listener-sweep cost was exactly
        # this). self.state is always rebound to the result, so the
        # donated buffers are never read again.
        cfg = self.cfg
        mj = lambda tag, make, *extra: _memo_jit(  # noqa: E731
            (tag, cfg, *extra), make)
        _api_age = self.opts.api_max_age_ticks
        self._age_apis = mj("age_apis", lambda: jax.jit(
            lambda s: step.age_apis(cfg, s, _api_age),
            donate_argnums=(0,)), _api_age)
        _task_age = self.opts.task_max_age_ticks
        self._age_tasks = mj("age_tasks", lambda: jax.jit(
            lambda s: step.age_tasks(cfg, s, _task_age),
            donate_argnums=(0,)), _task_age)
        self._compact_tasks = mj("compact_tasks", lambda: jax.jit(
            lambda s: step.compact_tasks(cfg, s),
            donate_argnums=(0,)))
        self._tick = mj("tick", lambda: jax.jit(
            lambda s: step.tick_5s(cfg, s), donate_argnums=(0,)))
        # device-health readback: every health scalar packed into ONE
        # small vector (no donation — it only reads), transferred once
        # per report cadence (tick / metrics scrape), never per event
        self._engine_health = mj("health", lambda: jax.jit(
            lambda s, d: step.engine_health_vec(cfg, s, d)))
        # digest flush: host-side pressure trigger + O(m) partial flush.
        # An in-graph lax.cond flush cost 110 ms/dispatch UNTAKEN at 65k
        # capacity (whole-stage copies at the cond boundary); the full
        # O(capacity) flush cost 6.2 s there. The pressure scalar from
        # dispatch N is checked (already materialized) before dispatch
        # N+1 — no pipeline sync on the hot path.
        self._td_flush_partial = mj("td_flush_partial", lambda: jax.jit(
            lambda s: step.td_flush_partial(cfg, s),
            donate_argnums=(0,)))
        self._stage_pressure = mj("stage_pressure", lambda: jax.jit(
            step.stage_pressure))
        # heavy-hitter recovery: decode the invertible buckets + exact
        # top-K lanes in ONE read-only dispatch (no donation — the
        # readback must not invalidate live state); memoized like every
        # other compiled program
        self._hh_recover = mj("hh_recover", lambda: jax.jit(
            lambda s: step.heavy_recover(cfg, s)))
        # snapshot publication (query/snapshot.py): ONE non-donating
        # jitted copy of (state, dep) per publish — jit outputs never
        # alias non-donated inputs, so the snapshot's buffers survive
        # every later donating fold (the double buffer: queries read
        # snapshot N on worker threads while the fold builds N+1)
        self._snap_copy = mj("snap_copy", lambda: jax.jit(
            lambda t: jax.tree.map(jnp.copy, t)))
        self.snapshot = None          # last published EngineSnapshot
        self._snap_version = 0
        # host-side registry renders (snapshot aux views) run on query
        # worker threads; registry UPDATES stay on the serving loop —
        # this lock keeps dict/deque iteration away from concurrent
        # structural mutation (cheap: uncontended except at render)
        self._reg_lock = threading.RLock()
        # recovered-hot key set from the previous recovery: promotions
        # count keys NEWLY recovered at/above the hot threshold, so the
        # counter tracks churn into the top view, not steady residency
        self._hh_prev_hot: set = set()
        from collections import deque
        # pressure scalars from recent dispatches: checked at lag 2 so
        # the int() readback never blocks on an in-flight fold (lag 1
        # would serialize dispatch N+1's launch on N's completion)
        self._pressures: deque = deque()
        # dependency graph (single-shard slice; the sharded tier keeps its
        # own stacked DepGraph — see parallel/depgraph.py)
        self.dep = dg.init(self.opts.dep_pair_capacity,
                           self.opts.dep_edge_capacity)
        _pttl = self.opts.dep_pair_ttl_ticks
        _ettl = self.opts.dep_edge_ttl_ticks
        self._dep_age = mj("dep_age", lambda: jax.jit(
            lambda d, t: dg.age(d, t, _pttl, _ettl),
            donate_argnums=(0,)), _pttl, _ettl)
        # ---- the fold path: every staged section and the conn/resp
        # slab ride ONE fold_all dispatch ----
        self._slab_lanes_cfg = _SLAB_LANES
        self._sect_builders = section_builders(cfg)
        # per-subsystem staging sections: raw record-array backlogs that
        # ride the NEXT fold_all dispatch (drained at the end of every
        # ingest_records call, so they never outlive a feed batch)
        self._stage_recs = {k: [] for k in self._slab_lanes_cfg}
        self._stage_n = {k: 0 for k in self._slab_lanes_cfg}
        # double-buffered conn/resp decode slabs: the idle buffer is
        # decoded into while the in-flight fold still owns (device
        # copies of) the other — host decode of batch N+1 overlaps
        # device fold of batch N (async dispatch + buffer flip). Each
        # buffer's columns are views of ONE word block, and the block
        # is what crosses to the device
        K = self.cfg.fold_k
        self._slab_bufs = []
        for _ in range(2):
            block, conn, resp = decode.alloc_slab_cols(
                K * self.cfg.conn_batch, K * self.cfg.resp_batch)
            self._slab_bufs.append(
                {"block": block, "conn": conn, "resp": resp,
                 "hw_conn": 0, "hw_resp": 0, "consumer": None})
        self._slab_active = 0
        # fold_all jit cache: one compiled variant per section-presence
        # combination (hot path = connresp-only; a 5s sweep batch adds
        # one "everything" variant)
        self._fold_all_jits: dict = {}
        self.names = InternTable()
        from gyeeta_tpu.utils.svcreg import SvcInfoRegistry
        from gyeeta_tpu.utils.hostreg import CgroupRegistry, \
            HostInfoRegistry, MountRegistry, NetIfRegistry
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
        from gyeeta_tpu.alerts import columns as AC
        from gyeeta_tpu.trace.defs import TraceDefs
        from gyeeta_tpu.utils.notifylog import NotifyLog
        self.notifylog = NotifyLog(clock=clock)
        self.tracedefs = TraceDefs(clock=clock)
        self._t_started = self._clock()
        self._aux = {
            "topk": self._topk_columns,
            "tracedef": lambda: self.tracedefs.columns(),
            "tracestatus": lambda: self.tracedefs.columns(),
            "traceuniq": self._traceuniq_columns,
            "traceconn": lambda: self.traceconns.columns(
                self.names, svc_task_ids=self._svc_task_ids()),
            "extactiveconn": lambda: self._ext_join("activeconn"),
            "extclientconn": lambda: self._ext_join("clientconn",
                                                    idcol="cliid"),
            "exttracereq": lambda: self._ext_join("tracereq"),
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
            "hostlist": self._hostlist_columns,
            "serverstatus": self._serverstatus_columns,
            "svcipclust": lambda: _dnsmap.annotate_vip_cols(
                self.natclusters.columns(self.names), self.dns),
            "tags": lambda: self.tags.columns(),
        }
        self._classify = derive.jit_classify_pass(self.cfg)

    # ------------------------------------------------------------- ingest
    def ingest_records(self, recs: dict) -> int:
        """Fold a drained {subtype: record array} dict (the post-
        deframe half of :meth:`feed` — the feed pipeline's decode
        worker hands these over, ``ingest/pipeline.py``)."""
        n = self._ingest_sweep_marks(recs.pop(wire.NOTIFY_SWEEP_SEQ, None))
        # conn/resp hot path: stage the raw record arrays as-is — the
        # per-slab decode in _dispatch_fused is the only decode they get
        conn = recs.pop(wire.NOTIFY_TCP_CONN, None)
        if conn is not None and len(conn):
            with self._reg_lock:
                self.natclusters.observe_conns(conn)
            self._conn_raw.append(conn)
            self._n_conn_raw += len(conn)
            self.stats.bump("conn_events", len(conn))
            n += len(conn)
        resp = recs.pop(wire.NOTIFY_RESP_SAMPLE, None)
        if resp is not None and len(resp):
            self._note_native_resp(resp)
            self._resp_raw.append(resp)
            self._n_resp_raw += len(resp)
            self.stats.bump("resp_events", len(resp))
            n += len(resp)
        for kind, chunk in decode.drain_chunks(
                recs, self.cfg.conn_batch, self.cfg.resp_batch,
                self.cfg.listener_batch):
            if kind in SECTION_COUNTERS:
                n += self._stage_section(kind, chunk)
            else:
                n += self._ingest_host_kind(kind, chunk)
        self._dispatch_fused_pending()
        if n:
            self._cols.bump()
        return n

    def _stage_bridged_resp(self, rs) -> None:
        """Trace→resp bridge samples join the native resp backlog."""
        self._resp_raw.append(rs)
        self._n_resp_raw += len(rs)

    # ------------------------------------------------------ fold path
    def _stage_section(self, kind: str, recs) -> int:
        """Stage one drained subsystem chunk into its slab section;
        dispatches the pending slab first when the section would
        overflow its fixed lane capacity."""
        if kind == "trace":
            self._observe_trace(recs)
        if self._stage_n[kind] + len(recs) > self._slab_lanes_cfg[kind]:
            self._dispatch_fused()
        self._stage_recs[kind].append(recs)
        self._stage_n[kind] += len(recs)
        self.stats.bump(SECTION_COUNTERS[kind], len(recs))
        return len(recs)

    def _dispatch_fused_pending(self) -> None:
        """End-of-ingest fold boundary: one fused dispatch folds every
        staged subsystem section plus (when full) the conn/resp K-slab;
        extra full K-slabs drain in follow-up connresp-only dispatches."""
        K = self.cfg.fold_k
        nc, nr = K * self.cfg.conn_batch, K * self.cfg.resp_batch
        while (any(self._stage_n.values())
               or self._n_conn_raw >= nc or self._n_resp_raw >= nr):
            self._dispatch_fused(
                connresp="slab" if (self._n_conn_raw >= nc
                                    or self._n_resp_raw >= nr)
                else None)

    def _get_fold_all(self, names: tuple):
        """Compiled fold_all variant for one section-presence tuple
        (process-wide memo — every Runtime with the same geometry
        shares the compiled variants)."""
        jitted = self._fold_all_jits.get(names)
        if jitted is None:
            cfg = self.cfg

            def make():
                # the sections arrive as one packed word block
                # (ingest/pack.py); ``layout`` = (treedef, leaf dtypes
                # and shapes) is static, and the unpack is the fold's
                # first few ops
                def fn(st, dep, tick, block, layout, _names=names):
                    treedef, leaves = layout
                    secs = jax.tree.unflatten(
                        treedef, pack.unpack(block, leaves))
                    return step.fold_all(cfg, st, dep, tick,
                                         **dict(zip(_names, secs)))
                # names the compiled module: a device trace tells the
                # slab fold from the section-only folds
                fn.__name__ = fold_all_name(names)
                return jax.jit(fn, donate_argnums=(0, 1),
                               static_argnums=(4,))

            jitted = _memo_jit(("fold_all", cfg, names), make)
            self._fold_all_jits[names] = jitted
        return jitted

    def _dispatch_fused(self, connresp=None) -> None:
        """ONE fused device dispatch: staged subsystem sections (folded
        in ``step.FOLD_ALL_ORDER``) + optionally the conn/resp slab + the dep
        fold + the digest-stage pressure scalar, with full state
        donation. ``connresp``: None (sections only), "slab" (a (K, B)
        double-buffered slab take) or "single" (one (1, B) microbatch —
        the flush/boundary shape).

        The per-batch device dispatch count of the hot path is exactly
        ONE (plus the occasional ``td_flush_partial``): the pressure
        scalar rides the fold's own outputs, so no second dispatch ever
        runs just to observe it."""
        lanes_c = lanes_r = 0
        buf = None
        if connresp == "slab":
            K = self.cfg.fold_k
            lanes_c, lanes_r = K * self.cfg.conn_batch, K * self.cfg.resp_batch
            buf = self._slab_bufs[self._slab_active]
            self._slab_active ^= 1          # flip: next decode goes to
            self.stats.bump("stage_slab_flips")  # the idle buffer
            # device_put reads host memory asynchronously (and may
            # alias it outright on the CPU backend), so a staging
            # buffer is rewritten only once the fold that consumed its
            # last contents has finished — an output of that fold is
            # ready. The fold in between keeps the device busy
            # meanwhile; a device two dispatches behind the host used
            # to fold half-rewritten lanes, silently.
            with self.spans.span("slab_wait", annotate=True):
                jax.block_until_ready(buf["consumer"])
        elif connresp == "single":
            K = 1
            lanes_c, lanes_r = self.cfg.conn_batch, self.cfg.resp_batch
        nc = min(self._n_conn_raw, lanes_c)
        nr = min(self._n_resp_raw, lanes_r)
        nrec = nc + nr
        sections = {}
        with self.spans.span("slab_decode", nrec=nrec,
                             path=native.decode_path(), annotate=True):
            for kind in self._slab_lanes_cfg:
                if self._stage_n[kind]:
                    recs = decode._concat_chunks(
                        self._stage_recs[kind],
                        wire.DTYPE_OF_SUBTYPE[_SECTION_SUBTYPES[kind]])
                    sections[kind] = self._sect_builders[kind](
                        recs, self._slab_lanes_cfg[kind], self.stats)
                    self._stage_recs[kind] = []
                    self._stage_n[kind] = 0
            if connresp:
                crecs, _ = decode.take_raw_chunks(self._conn_raw, lanes_c)
                rrecs, _ = decode.take_raw_chunks(self._resp_raw, lanes_r)
                self._n_conn_raw -= nc
                self._n_resp_raw -= nr
                # a slab decodes into its double buffer (clearing the
                # lanes its last contents reached); the flush/boundary
                # microbatch into fresh columns
                into_c = dict(out=buf["conn"], clear_to=buf["hw_conn"]) \
                    if buf else {}
                into_r = dict(out=buf["resp"], clear_to=buf["hw_resp"]) \
                    if buf else {}
                cbs = decode.conn_slab(crecs, K, self.cfg.conn_batch,
                                       stats=self.stats, **into_c)
                rbs = decode.resp_slab(rrecs, K, self.cfg.resp_batch,
                                       stats=self.stats, **into_r)
                sections["connresp"] = (cbs, rbs)
        if buf is not None:
            # host-side staging gauges (no device readback): slab fill
            # at dispatch + the buffer flip counter; the engine_ prefix
            # rides the `health {...}` cadence line and /metrics
            self.stats.gauge("engine_stage_slab_conn_occupancy",
                             round(nc / lanes_c, 4))
            self.stats.gauge("engine_stage_slab_resp_occupancy",
                             round(nr / lanes_r, 4))
            buf["hw_conn"], buf["hw_resp"] = nc, nr
            self.stats.bump("slab_dispatches")
        if not sections:
            return
        self._td_flush_on_pressure()
        names = tuple(k for k in step.FOLD_ALL_ORDER if k in sections)
        with self.spans.span("fold_dispatch", nrec=nrec,
                             path=native.decode_path()):
            # the staged (idle-buffer) block transfers while the
            # previous fold may still be in flight; the jit call below
            # never blocks on it (async dispatch). ONE array whatever
            # sections ride: a slab decoded in place IS its block, the
            # rest is one small host copy into a fresh one
            with self.spans.span("fold_h2d", nrec=nrec, annotate=True):
                leaves, treedef = jax.tree.flatten(
                    tuple(sections[k] for k in names))
                block = pack.pack(
                    leaves, into=buf["block"] if buf else None)
                self.stats.bump("h2d_arrays")
                self.stats.bump("h2d_bytes", block.nbytes)
                block = jax.device_put(block)
            with self.spans.span("fold_enqueue", nrec=nrec, annotate=True):
                self.state, self.dep, pressure = self._get_fold_all(names)(
                    self.state, self.dep, np.int32(self._tick_no), block,
                    (treedef, pack.layout_of(leaves)))
        self._pressures.append(pressure)
        if buf is not None:
            buf["consumer"] = pressure
        if "connresp" in sections:
            self._td_dirty = True
        self.stats.bump("fold_dispatches")

    def _td_flush_on_pressure(self) -> None:
        """Flush the fullest digest stages BEFORE the next fold when the
        lag-2 pressure scalar (an output of the fold two dispatches
        back) says headroom is low. The ``int()`` blocks until that
        fold has finished: with a device that sets the pace it is where
        the loop waits (the ``td_flush`` span; PERF.md §5)."""
        if len(self._pressures) < 2:
            return
        with self.spans.span("td_flush", annotate=True):
            if int(self._pressures.popleft()) > self.cfg.td_stage_cap // 2:
                self.state = self._td_flush_partial(self.state)
                self.stats.bump("td_partial_flushes")

    def flush(self) -> int:
        """Fold all staged raw records (single-microbatch path when they
        fit one, padded partial slab otherwise). Called at every
        cadence/query boundary — after it, every QUERY view is current
        (no query subsystem reads the all-time digest; its stage drains
        on tick cadence / ``td_drain``, off the <1s query path).
        Returns records folded."""
        n = self._n_conn_raw + self._n_resp_raw
        while (self._n_conn_raw or self._n_resp_raw
               or any(self._stage_n.values())):
            if (self._n_conn_raw <= self.cfg.conn_batch
                    and self._n_resp_raw <= self.cfg.resp_batch):
                # boundary leftovers: one (1, B) microbatch dispatch
                self._dispatch_fused(
                    connresp="single"
                    if (self._n_conn_raw or self._n_resp_raw) else None)
            else:
                self._dispatch_fused(connresp="slab")
        if n:
            self._cols.bump()
        return n

    def td_drain(self, max_iters: int | None = None) -> int:
        """Drain the digest stage with O(m) partial flushes.

        Iteration count scales with the number of ACTIVE stages (entities
        holding samples), not capacity — the toy/test case drains in one
        pass. Unbounded by default (direct ``svc_snapshot`` consumers
        want exact digests); ``run_tick`` passes a bound to amortize the
        north-star worst case (every entity active) across ticks —
        overflowing stages drop + count, and the loghist remains the
        lossless estimator, mirroring the reference's ~50% response
        sampling (``common/gy_ebpf.h:29``). Returns flushes run."""
        self.flush()
        # the flushes below donate state: evict cached column closures
        # capturing the current state object (a cache hit after the
        # donation would dereference deleted device buffers)
        self._cols.bump()
        i = 0
        while max_iters is None or i < max_iters:
            if int(self._stage_pressure(self.state)) <= 0:
                self._td_dirty = False
                self._pressures.clear()
                break
            self.state = self._td_flush_partial(self.state)
            self.stats.bump("td_partial_flushes")
            i += 1
        return i

    # ------------------------------------------------------------ health
    def engine_health(self) -> dict:
        """Device-state health gauges from ONE batched readback
        (``engine/step.py:engine_health_vec``): slab occupancy %,
        probe-failure and eviction counters, dep-graph pair/edge fill,
        digest-stage pressure. Folded into ``self.stats`` gauges so
        the same numbers ride selfstats, /metrics and the cadence
        log."""
        vec = np.asarray(self._engine_health(self.state, self.dep))
        gauges = obs_health.gauges_from_vec(
            vec, obs_health.capacities(self.cfg, self.opts))
        # decode-path state gauge: a degraded native extension is a
        # scrape-level signal, not just a growing fallback counter
        gauges["native_decode_available"] = \
            1.0 if native.available() else 0.0
        # what each device holds now and at its high-water mark (none
        # on the CPU backend, which reports no memory_stats)
        gauges.update(xlamon.device_gauges())
        # WAL health rides the same one-readback report path: fsync lag
        # (the RPO bound), pending bytes, segment footprint
        if self.journal is not None:
            gauges.update(self.journal.gauges())
        for k, v in gauges.items():
            self.stats.gauge(k, v)
        return gauges

    # -------------------------------------------------- heavy hitters
    def heavy_recover(self) -> dict:
        """Per-tick heavy-hitter key recovery: ONE read-only device
        dispatch decodes the invertible buckets (fingerprint + bucket-
        position verification), point-queries the CMS for every
        candidate and reads the exact top-K lanes alongside; the host
        merges them into the bound-annotated heavy-flow view the
        ``topk`` subsystem serves. Counted in /metrics
        (``gyt_topk_recover_readbacks_total``) — the fold path itself
        never pays an op for recovery."""
        from gyeeta_tpu.sketch import invertible

        self.flush()
        with self.spans.span("topk_recover"):
            out = {k: np.asarray(v) for k, v in
                   self._hh_recover(self.state).items()}
        self.stats.bump("topk_recover_readbacks")
        evicted = float(out["evicted"])
        total = float(out["total_mass"])
        err_term = invertible.cms_error_term(total, self.cfg.cms_width)
        hot_thresh = (self.cfg.hh_hot_frac * total
                      if self.cfg.hh_hot_frac > 0 else 0.0)
        flows, recovered, hot = invertible.merge_recovered_np(
            out, err_term, hot_thresh)
        # promotions: recovered-hot keys that were NOT hot at the
        # previous recovery — the "new flow entered the top view" edge
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
        """topk subsystem columns: heavy flows (exact ∪ recovered) +
        dense svc/api rankings. Recovery memoizes per state version —
        between folds every query (and the alert check) reuses one
        readback."""
        rec = self._cols.get("__hh_recover", self.heavy_recover)
        return api.heavy_topk_columns(
            rec["flows"], svc=self._cached_columns("svcstate"),
            trace=self._cached_columns("tracereq"))

    # ----------------------------------------------------- snapshot tier
    def publish_snapshot(self):
        """Freeze the current engine view into an immutable
        :class:`~gyeeta_tpu.query.snapshot.EngineSnapshot` and swap it
        in (plain attribute store — atomic under the GIL). One
        non-donating device copy of (state, dep) per publish; queries
        on worker threads keep reading the PREVIOUS snapshot until the
        swap, and the old snapshot's buffers free when its last reader
        drops it. Called once per tick (post-classify, pre-window-roll)
        and on restore; ``run_tick`` routes alert evaluation and the
        history sweep through the fresh snapshot so tick-time work
        PRE-WARMS the columns dashboards then reuse."""
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
        if self._tick_p0 is not None:
            # the part of a tick that delays visibility: run_tick entry
            # → this swap (what follows only holds the loop)
            self.spans.interval("tick_visible", self._tick_p0,
                                nrec=self._tick_no)
            self._tick_p0 = None
        self.stats.bump("snapshots_published")
        self.stats.gauge("snapshot_tick", float(self._tick_no))
        self.stats.gauge("snapshot_age_seconds", 0.0)
        return snap

    # ------------------------------------------------------------ cadence
    def run_tick(self) -> dict:
        self._tick_p0 = time.perf_counter()
        try:
            with self.spans.span("tick", nrec=self._tick_no):
                return self._run_tick()
        finally:
            self._tick_p0 = None      # a tick that failed before its swap

    def _run_tick(self) -> dict:
        """Close one 5s window: classify → alerts → windows tick →
        maintenance cadences. Returns a tick report.

        Every step runs inside a leaf span (``tick.*``,
        ``snapshot_publish``), in program order. JAX dispatch is
        asynchronous: a step that only enqueues reads near zero, and
        the first step that reads a value back (``tick.td_drain``'s
        pressure scalar, else ``tick.roll``'s window-tick readback)
        absorbs the device time queued before it."""
        span = self.spans.span
        with span("tick.flush", annotate=True):
            self.flush()
        if self._td_dirty:    # tick-cadence digest compression (bounded)
            with span("tick.td_drain", annotate=True):
                self.td_drain(max_iters=self.opts.td_drain_iters_per_tick)
        report = {}
        with span("tick.classify", annotate=True):
            self.state = self._classify(self.state)
            self._cols.bump()         # classify + tick mutate views
        # publish the post-classify view: the snapshot dashboards read
        # for the next 5s window. Everything below that reads columns
        # (alert eval, the history sweep) goes THROUGH it — tick-time
        # work pre-warms the snapshot's column cache.
        snap = self.publish_snapshot()
        # per-tick heavy-hitter recovery (one read-only readback,
        # memoized per state version — an alertdef on `topk` and every
        # query until the next fold reuse it). 0 disables the cadence;
        # queries still recover on demand.
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
                fired = self.alerts.check(self.state,
                                          columns_fn=snap.columns)
            else:
                self.stats.bump("alert_eval_skipped")
                fired = self.alerts.flush_groups()
        # history snapshots BEFORE the window tick: the closing 5s slab is
        # still readable (tick zeroes it)
        with span("tick.roll", annotate=True):
            tick = int(np.asarray(self.state.resp_win.tick)) + 1
            report["tick"] = tick
            self._tick_no = tick
            self.stats.gauge("tick", tick)
            self.dep = self._dep_age(self.dep, tick)
            with self._reg_lock:      # ageing structurally mutates the
                self.cgroups.age()    # registries snapshot aux renders
                self.mounts.age()     # iterate on worker threads
                self.netifs.age()
                self.natclusters.age()
                self.traceconns.age()

        with span("tick.history", annotate=True):
            fired += self._tick_history(snap, tick, report)
        report["alerts_fired"] = len(fired)
        for a in fired:
            self.notifylog.add_alert(a)

        # device-health readback (obs tier): slab occupancy, probe
        # failures, dep fill, stage pressure — ONE batched transfer,
        # folded into the stats gauges for /metrics + the cadence log.
        # The drop-pressure signal (VERDICT r4 #10) feeds off the same
        # vector (growing drops → notifymsg entries + gauges).
        from gyeeta_tpu.utils import droppressure
        with span("tick.health", annotate=True):
            health = self.engine_health()
            self._last_drops = droppressure.check(
                obs_health.drops_for_pressure(health),
                {"svc": self.cfg.svc_capacity,
                 "task": self.cfg.task_capacity,
                 "api": self.cfg.api_capacity,
                 "dep": self.opts.dep_pair_capacity},
                getattr(self, "_last_drops", {}),
                self.notifylog, self.stats)

        with span("tick.close", annotate=True):
            self._tick_close(tick, report)
        return report

    def _tick_history(self, snap, tick: int, report: dict) -> list:
        """The tick's history sweep and the db-mode alertdefs that read
        it. Returns the alerts those fired."""
        if self.history and tick % self.opts.history_every_ticks == 0:
            now = self._clock()
            # render on the fold thread from the JUST-published
            # snapshot (pre-warming its column cache for dashboards),
            # WRITE on the history writer thread (bounded queue,
            # drop-oldest counted) — a slow sqlite/pg write can no
            # longer stall run_tick (it used to be synchronous SQL in
            # this loop)
            out = api.execute(self.cfg, None, api.QueryOptions(
                subsys="svcstate", maxrecs=self.cfg.svc_capacity),
                names=self.names, columns_fn=snap.columns)
            hout = api.execute(self.cfg, None, api.QueryOptions(
                subsys="hoststate", maxrecs=self.cfg.n_hosts),
                names=self.names, columns_fn=snap.columns)
            cout = api.execute(self.cfg, None, api.QueryOptions(
                subsys="clusterstate"), columns_fn=snap.columns)
            tout = api.execute(self.cfg, None, api.QueryOptions(
                subsys="taskstate", maxrecs=self.cfg.task_capacity),
                names=self.names, columns_fn=snap.columns)
            mout = api.execute(self.cfg, None, api.QueryOptions(
                subsys="cpumem", maxrecs=self.cfg.n_hosts),
                names=self.names, columns_fn=snap.columns)
            trout = api.execute(self.cfg, None, api.QueryOptions(
                subsys="tracereq", maxrecs=self.cfg.api_capacity),
                names=self.names, columns_fn=snap.columns)
            sweep = [("svcstate", now, out["recs"]),
                     ("hoststate", now, hout["recs"]),
                     ("clusterstate", now, cout["recs"]),
                     ("taskstate", now, tout["recs"]),
                     ("cpumem", now, mout["recs"]),
                     ("tracereq", now, trout["recs"])]
            ncg = 0
            if len(self.cgroups):
                cgout = api.execute(self.cfg, None, api.QueryOptions(
                    subsys="cgroupstate", maxrecs=100_000),
                    names=self.names, columns_fn=snap.columns)
                sweep.append(("cgroupstate", now, cgout["recs"]))
                ncg = cgout["nrecs"]
            self._histwriter.write_sweep(sweep)
            report["history_rows"] = (
                out["nrecs"] + hout["nrecs"] + tout["nrecs"]
                + mout["nrecs"] + trout["nrecs"] + ncg + 1)

        # db-mode alertdefs run AFTER the history write so a due def sees
        # the snapshot from this very tick (ref: MDB alerts query the DB
        # the madhava just wrote, server/gy_malerts.cc). Only defs that
        # actually read the store pay the writer-queue barrier.
        if self.history and self.alerts.wants_db():
            self._histwriter.barrier()
            return self.alerts.check_db(self.history)
        return []

    def _tick_close(self, tick: int, report: dict) -> None:
        """Roll the 5 s window and run the maintenance cadences: task
        and API ageing, tombstone compaction, the journal's fsync
        backstop, the checkpoint."""
        self.state = self._tick(self.state)
        if tick % self.opts.task_age_every_ticks == 0:
            self.state = self._age_tasks(self.state)
            self.state = self._age_apis(self.state)
        n_tomb = int(np.asarray(self.state.tbl.n_tomb))
        if n_tomb > self.cfg.svc_capacity * self.opts.compact_tomb_frac:
            self.state = compact.compact_state(self.cfg, self.state)
            self.stats.bump("compactions")
            report["compacted"] = True
        nt_tomb = int(np.asarray(self.state.task_tbl.n_tomb))
        if nt_tomb > self.cfg.task_capacity * self.opts.compact_tomb_frac:
            self.state = self._compact_tasks(self.state)
            self.stats.bump("task_compactions")
            report["task_compacted"] = True

        # journal fsync cadence backstop: appends check the ms budget
        # themselves, but a quiet wire must not hold bytes unsynced
        # past a tick
        if self.journal is not None:
            self.journal.poll()
        if (self.opts.checkpoint_dir
                and tick % self.opts.checkpoint_every_ticks == 0):
            from gyeeta_tpu.utils import journal as J
            extra = J.checkpoint_extra(self, tick)
            path = ckpt.save(
                f"{self.opts.checkpoint_dir}/gyt_ckpt_{tick:08d}.npz",
                self.cfg, self.state, extra=extra)
            # the checkpoint supersedes older WAL segments: drop them
            # (bounds journal disk to ~one checkpoint interval)
            J.post_checkpoint_truncate(self, extra)
            report["checkpoint"] = str(path)
            self.stats.bump("checkpoints")
        # the window tick / aging / compaction above changed every view
        self._cols.bump()

    def _hostlist_columns(self):
        """hostlist subsystem (ref parthalist): hosts that have ever
        reported, with liveness from the last-report tick."""
        last = np.asarray(self.state.host_last_tick)
        seen = np.nonzero(last >= 0)[0]
        age = self._tick_no - last[seen]
        hostids, hostnames = api._host_name_cols(self.cfg.n_hosts,
                                                 self.names)
        cols = {
            "hostid": seen.astype(np.float64),
            "hostname": np.asarray(hostnames, object)[seen],
            "up": age <= api.DOWN_AFTER_TICKS,
            "lastseen": age.astype(np.float64),
        }
        return cols, np.ones(len(seen), bool)

    def _serverstatus_columns(self):
        return api.serverstatus_columns(
            self, self._tick_no,
            int((np.asarray(self.state.host_last_tick) >= 0).sum()),
            float(np.asarray(self.state.tbl.n_live)))

    def _alert_columns(self, subsys: str):
        """Column source for realtime alertdef evaluation — the same
        dispatch as api.execute so defs can target ANY live subsystem
        (device slabs, dep graph, or host-side registries). Routed
        through the snapshot cache: alert evaluation at tick time
        PRE-WARMS the columns queries then reuse. A ``subsys@window``
        name (an alertdef with a ``window`` field) evaluates against
        the time-travel tier's windowed aggregate instead of the live
        snapshot."""
        if "@" in subsys:
            base, _, win = subsys.partition("@")
            if self.timeview is None:
                raise ValueError(
                    "windowed alertdef needs history shards "
                    "(hist_shard_dir)")
            return self.timeview.window_columns_for(base, win)
        return self._cached_columns(subsys)

    def _cached_columns(self, subsys: str):
        """Version-keyed snapshot cache (query freshness, VERDICT r3
        weak #4): device readbacks recompute only after state actually
        changed (feed/tick/flush/restore bump the cache version);
        between ticks every query serves from the cached columns — the
        reference likewise queries incrementally-maintained in-memory
        tables, not per-request recomputation. Registry/CRUD-backed aux
        views are NEVER cached (they mutate without a version bump)."""
        if subsys in self._aux:
            return self._aux[subsys]()
        def compute():
            try:
                return api.columns_for(self.cfg, self.state, subsys,
                                       names=self.names, dep=self.dep,
                                       svcreg=self.svcreg,
                                       aux=self._aux, obs=self)
            except KeyError:
                # a subsystem with fields but no single-node provider
                # (e.g. shardlist) must fail like execute() without a
                # columns_fn would — clean error, not a bare KeyError
                raise ValueError(
                    f"unknown subsystem {subsys!r}") from None
        out = self._cols.get(subsys, compute)
        if subsys == "procinfo":
            # joined OUTSIDE the cache: tags mutate via CRUD without a
            # state version bump
            out = self.tags.with_tags(out)
        return out

    def _ext_join(self, base_subsys: str, idcol: str = "svcid"):
        """ext* subsystems: base columns ⋈ svcinfo metadata."""
        cols, live = self._alert_columns(base_subsys)
        info_cols, _ = self.svcreg.columns(self.names)
        return api.info_join(cols, live, info_cols, idcol=idcol)

    def _svc_task_ids(self):
        """Hex process-group ids that serve a listener (taskstate rows
        with a nonzero relsvcid) — the traceconn ``csvc`` source."""
        cols, live = self._cached_columns("taskstate")
        zero = "0" * 16
        return {t for t, r, ok in zip(cols["taskid"], cols["relsvcid"],
                                      live) if ok and r != zero}

    def _traceuniq_columns(self):
        """traceuniq: distinct API signatures per service, derived by
        grouping the per-(svc, api) slab (ref traceuniqtbl)."""
        tcols, tlive = api.trace_columns(self.cfg, self.state,
                                         names=self.names)
        return api.traceuniq_from_trace(tcols, tlive)

    # ------------------------------------------------------- trace control
    def trace_control_diff(self, hosts=None):
        """Evaluate tracedefs against live svcinfo → per-host
        enable/disable diffs for the network edge to push (the
        REQ_TRACE_SET distribution step). ``hosts`` restricts to
        reachable agents so unreachable diffs aren't consumed."""
        targets = self.tracedefs.target_svcids(self._alert_columns)
        return self.tracedefs.diff_for_hosts(targets, hosts=hosts)

    # ---------------------------------------------------------------- CRUD
    def crud(self, req: dict) -> dict:
        from gyeeta_tpu.query import crud as CR
        with self._reg_lock:
            out = CR.crud(self, req)
        # CRUD mutates aux views mid-snapshot: invalidate the published
        # snapshot's result + column caches so the next query re-renders
        snap = self.snapshot
        if snap is not None:
            snap.on_mutation()
        return out

    # -------------------------------------------------------------- query
    def query(self, req: dict) -> dict:
        """Point-in-time (live) or historical (time-ranged) JSON query;
        requests with an "op" field route to the CRUD channel; a
        "multiquery" list runs several queries in one round trip (the
        reference's multiquery batches, ``gy_query_common.h:24``).

        ``consistency`` selects the live-query path: ``"strong"`` (the
        default for direct callers — flush staged events, read the live
        engine) or ``"snapshot"`` (read the last published per-tick
        :class:`~gyeeta_tpu.query.snapshot.EngineSnapshot`; never
        touches the fold — the serving edges default to this)."""
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
        # process-local subsystems (selfstats readback + Prometheus
        # metrics exposition) — shared routing with ShardedRuntime
        out = api.local_response(self, req)
        if out is not None:
            return out
        with self.spans.span("query", annotate=True):
            return self._query(req)

    def query_snapshot(self, req: dict) -> dict:
        """Serve a live query from the last published snapshot — no
        ``flush()``, no fold-path device dispatch, safe from worker
        threads (the off-loop executor's path, ``net/qexec.py``).
        Historical ``at=``/``window=`` requests route to the shard tier
        (file-backed — also fold-free); relational ``tstart/tend`` SQL
        runs against the live history handle and must use
        ``consistency=strong`` (the serving edge routes it inline)."""
        req = {k: v for k, v in req.items() if k != "consistency"}
        snap = self.snapshot
        if snap is None:
            # bootstrap publish (single-threaded callers); the serving
            # edge publishes at start() so worker threads always find
            # a snapshot here
            snap = self.publish_snapshot()
        if req.get("subsys") in api.LOCAL_SUBSYS:
            return api.local_response(self, req, snapshot=snap)
        if ("tstart" in req or "tend" in req) and "at" not in req \
                and "window" not in req and self.history:
            raise ValueError(
                "relational history queries need consistency=strong")
        from gyeeta_tpu.history.timeview import route_historical
        out = route_historical(self, req)
        if out is not None:
            return out
        self.stats.bump("queries")
        with self.spans.span("query", annotate=True):
            return snap.query(req)

    def _query(self, req: dict) -> dict:
        # time-travel tier: at=/window= materialize snapshot shards
        # (tstart/tend also route there when no relational store is
        # configured) — shared three-edge routing, so GYT binary, REST
        # and stock NM requests land on identical code paths
        from gyeeta_tpu.history.timeview import route_historical
        out = route_historical(self, req)
        if out is not None:
            return out
        if "tstart" in req or "tend" in req:
            if not self.history:
                raise ValueError("no history store configured")
            if self._histwriter is not None:
                self._histwriter.barrier()   # read-your-writes
            now = self._clock()
            if req.get("aggr"):
                recs = self.history.aggr_query(
                    req["subsys"], float(req.get("tstart", 0)),
                    float(req.get("tend", now)), req["aggr"],
                    groupby=req.get("groupby"), filter=req.get("filter"),
                    step=float(req["step"]) if req.get("step") else None,
                    maxrecs=int(req.get("maxrecs", 10000)))
                return {"recs": recs, "nrecs": len(recs)}
            return {"recs": self.history.query(
                req["subsys"], float(req.get("tstart", 0)),
                float(req.get("tend", now)), req.get("filter"),
                int(req.get("maxrecs", 10000)))}
        self.flush()                  # live queries see all staged events
        self.stats.bump("queries")
        return api.execute(self.cfg, self.state,
                           api.QueryOptions.from_json(req),
                           names=self.names,
                           columns_fn=self._cached_columns)

    def close(self) -> None:
        """Release background resources (alert delivery worker, DNS
        resolver, history db handle). Idempotent; the server calls it
        on stop."""
        self.alerts.close()
        self.dns.close()
        if self.journal is not None:
            self.journal.close()      # fsync + close (idempotent)
        if self._histwriter is not None:
            self._histwriter.close()  # drain queued sweeps first
        if self.history is not None:
            try:
                self.history.db.close()
            except Exception:  # noqa: BLE001 — already closed is fine
                pass

    def restore(self, path) -> dict:
        # drop staged records and partial-frame bytes from before the
        # restore: folding them into checkpointed state would double-count
        self._conn_raw, self._resp_raw = [], []
        self._n_conn_raw = self._n_resp_raw = 0
        self._stage_recs = {k: [] for k in self._slab_lanes_cfg}
        self._stage_n = {k: 0 for k in self._slab_lanes_cfg}
        self._pending = b""
        self._cols.bump()
        self._cols.clear()
        # the checkpoint may carry a non-empty digest stage (per-tick
        # drains are bounded): mark dirty so the tick cadence drains it
        self._td_dirty = True
        self._pressures.clear()
        self.state, extra = ckpt.restore(path, self.cfg, self.state)
        # the dep graph is not checkpointed: reset it (edges rebuild from
        # live traffic) and realign the host tick mirror so TTL deltas
        # never go negative
        self.dep = dg.init(self.opts.dep_pair_capacity,
                           self.opts.dep_edge_capacity)
        self._tick_no = int(extra.get("tick", 0))
        # sweep-seq high-water marks through checkpoint time; WAL
        # replay advances them for the post-checkpoint window
        self._sweep_last_seq = {
            int(k): int(v)
            for k, v in extra.get("sweep_seq", {}).items()}
        # snapshot serving must not keep answering from pre-restore
        # state: republish over the restored view (only when a snapshot
        # was ever published — bare runtimes pay nothing)
        if self.snapshot is not None:
            self.publish_snapshot()
        return extra

    def replay_journal(self, pos=None) -> dict:
        """Re-fold WAL chunks from ``pos`` (a checkpoint's recorded
        position; None = journal start) through the normal decode/fold
        path — the recovery phase of ``--restore-latest``."""
        from gyeeta_tpu.utils import journal as J
        return J.replay_journal(self, pos)
