"""Snapshot-isolated query serving: per-tick immutable engine views.

Every query edge used to walk the LIVE runtime under the fold loop —
each live query called ``flush()`` (a device dispatch), and a dashboard
fleet therefore stalled the fold while the fold stalled query p99. The
reference serves queries from incrementally-maintained in-memory tables
decoupled from ingest (``server/gy_mnodehandle.cc`` web queries walk
existing maps); sPIN makes the same argument from the streaming side —
the ingest path must never absorb request-processing stalls.

:class:`EngineSnapshot` is the decoupling point: each tick publishes a
frozen view of the folded engine — the state pytree and dep graph
COPIED out of the fold's donation domain (one non-donating device
dispatch per publish; every ``state -> state`` fold donates its input,
so a snapshot that merely aliased the live buffers would dereference
deleted memory after the next dispatch), plus a snapshot-scoped
:class:`~gyeeta_tpu.utils.colcache.ColumnCache` and a result cache
keyed by the normalized request. The runtime swaps ``rt.snapshot`` —
a plain attribute store, atomic under the GIL — so queries on worker
threads keep reading snapshot N while the fold builds N+1: the classic
double buffer, paid once per tick instead of once per query.

Thread model: snapshot state/dep are immutable after publish, so device
readbacks from any number of query threads are safe (jax dispatch is
thread-safe; the buffers are never donated). Host-side registries stay
live-shared — their renders run under the runtime's registry lock and
are memoized per snapshot, so a tick's worth of dashboard traffic pays
each render once. Result-cache invalidation is by replacement: a new
tick publishes a new snapshot (fresh caches); CRUD and restore clear or
replace the current one (``on_mutation`` / re-publish).
"""

from __future__ import annotations

import collections
import contextlib
import threading

import numpy as np

from gyeeta_tpu.query import api

# registry-backed renders race host-side mutators that the registry
# lock does not cover (notifylog appends from the tick loop, alert
# bookkeeping during check): a concurrent structural mutation raises
# RuntimeError("... changed size/mutated during iteration") — rare at
# per-snapshot-memo frequency, so a short retry is the right tool
_AUX_RETRIES = 3

# aux views served straight from host-side registries (no device state
# anywhere in their providers) — safe to delegate to the runtime's live
# aux table under the registry lock
_REGISTRY_AUX = frozenset((
    "hostinfo", "cgroupstate", "mountstate", "netif", "alerts",
    "alertdef", "silences", "inhibits", "actions", "notifymsg",
    "svcipclust", "tags", "tracedef", "tracestatus",
))


def request_key(req: dict) -> str:
    """Normalized request hash key — the ONE shared definition in
    ``query/normalize.py``: the gateway tier's distributed edge cache
    keys with the same function, so a result rendered here serves the
    whole fleet (and a gateway-side hit proves a replica-side hit
    would have happened too)."""
    from gyeeta_tpu.query.normalize import request_key as _rk
    return _rk(req)


class EngineSnapshot:
    """One immutable published engine view (the ``columns_fn``
    contract of :func:`gyeeta_tpu.query.api.execute`, plus a
    per-snapshot result cache).

    ``state``/``dep`` are fold-domain COPIES — see the module
    docstring. ``version`` increases monotonically per publish;
    ``tick`` is the window tick the view was frozen at."""

    def __init__(self, rt, state, dep, tick: int, published_at: float,
                 version: int, result_cache_max: int = 1024):
        self.rt = rt
        self.state = state
        self.dep = dep
        self.tick = int(tick)
        self.published_at = float(published_at)
        self.version = int(version)
        from gyeeta_tpu.utils.colcache import ColumnCache
        self._cols = ColumnCache()
        self._results: collections.OrderedDict = collections.OrderedDict()
        self._results_max = int(result_cache_max)
        self._again: dict = {}      # key → request, noted on a cache hit
        self._lock = threading.Lock()
        # single-flight: per-request and per-subsystem compute locks so
        # a dashboard stampede onto a FRESH snapshot collapses N
        # identical misses into ONE render (the N-1 waiters re-check
        # the cache after the holder publishes). Keyed locks form a
        # DAG (topk→svcstate/tracereq, svcsumm→svcstate, ext*→base) —
        # no cycles, no deadlock.
        self._flight: dict = {}

    def _flight_lock(self, key) -> threading.Lock:
        with self._lock:
            lk = self._flight.get(key)
            if lk is None:
                lk = self._flight[key] = threading.Lock()
            return lk

    # ------------------------------------------------------ result cache
    def query(self, req: dict) -> dict:
        """Serve one live query from this snapshot, collapsing repeated
        identical requests to one render (per-snapshot result cache:
        hits/misses land on ``gyt_query_cache_{hits,misses}_total``);
        CONCURRENT identical requests single-flight — one render, the
        rest wait for it and hit."""
        stats = self.rt.stats
        key = request_key(req)
        if self._results_max <= 0:
            stats.bump("query_cache_misses")
            return self._render(req)
        hit = self._cached(key, req)
        if hit is not None:
            stats.bump("query_cache_hits")
            return hit
        with self._flight_lock(("r", key)):
            hit = self._cached(key, req)   # the holder may have stored
            if hit is not None:
                stats.bump("query_cache_hits")
                return hit
            stats.bump("query_cache_misses")
            return self._fill(key, req)

    def _cached(self, key, req: dict):
        """The cached answer, or None. A hit also notes the request as
        REPEATED: asked again within one snapshot's life, so worth
        rendering ahead on the next (:meth:`repeated`)."""
        with self._lock:
            hit = self._results.get(key)
            if hit is not None:
                self._again.setdefault(key, req)
        return hit

    def _fill(self, key, req: dict) -> dict:
        out = self._render(req)
        with self._lock:
            self._results[key] = out
            while len(self._results) > self._results_max:
                self._results.popitem(last=False)
        return out

    # a dashboard fleet repeats a handful of requests; the bound keeps
    # one tick's render-ahead work small whatever else hit the cache
    PREWARM_MAX = 16

    def repeated(self) -> list:
        """The requests this snapshot answered from its cache at least
        once, soonest-repeated first, at most ``PREWARM_MAX``. A request
        asked once (a one-off, however slow) is never among them."""
        with self._lock:
            return list(self._again.values())[:self.PREWARM_MAX]

    def warm(self, req: dict) -> None:
        """Render ``req`` into the result cache ahead of its first ask
        (the serving edge hands a fresh snapshot its predecessor's
        :meth:`repeated` requests, ``net/qexec.py:prewarm``): a
        dashboard's first refresh after a tick is then a hit instead of
        a render. Single-flights with live asks of the same request;
        counted apart from misses (``query_cache_prewarms``) and never
        noted as a repeat, so a request nobody asks any more is rendered
        ahead once and then dropped."""
        key = request_key(req)
        with self._flight_lock(("r", key)):
            with self._lock:
                if key in self._results:
                    return
            self.rt.stats.bump("query_cache_prewarms")
            self._fill(key, req)

    def _render(self, req: dict) -> dict:
        """A result-cache miss: column compute, device→host readback
        and row assembly (the first reader of a fresh snapshot also
        waits here for the device to finish the publish copy)."""
        spans = self.rt.spans
        opts = api.QueryOptions.from_json(req)
        dep = opts.subsys == "svcdependency"
        with spans.span("query_render", annotate=True):
            if dep:
                # the snapshot's one view build (span ``dep_view``)
                # falls to whoever asks first, outside ``dep_render``
                self.columns(opts.subsys)
            with spans.span("dep_render") if dep \
                    else contextlib.nullcontext():
                out = api.execute(self.rt.cfg, None, opts,
                                  names=self.rt.names,
                                  columns_fn=self.columns)
        out["snaptick"] = self.tick
        return out

    def on_mutation(self) -> None:
        """CRUD invalidation hook: a registry/alert/tracedef mutation
        changes aux views mid-snapshot, so drop BOTH caches (device-
        backed column entries recompute from the frozen state — CRUD is
        rare enough that re-rendering beats tracking which subsystems a
        mutation touched)."""
        with self._lock:
            self._results.clear()
        self._cols.bump()

    def result_cache_len(self) -> int:
        with self._lock:
            return len(self._results)

    # ---------------------------------------------------------- columns
    def columns(self, subsys: str):
        """(cols, mask) for ``subsys`` over the frozen view — memoized
        per snapshot, so identical dashboard queries differing only in
        filter/sort/projection share one readback."""
        if "@" in subsys:
            # subsys@window (windowed alertdefs): the time-travel tier
            # reads shard FILES, not live state — safe from any thread
            base, _, win = subsys.partition("@")
            tv = getattr(self.rt, "timeview", None)
            if tv is None:
                raise ValueError("windowed alertdef needs history "
                                 "shards (hist_shard_dir)")
            return tv.window_columns_for(base, win)
        got = self._cols.peek(subsys)
        if got is not None:
            return got
        with self._flight_lock(("c", subsys)):
            return self._cols.get(subsys, lambda: self._columns(subsys))

    def _columns(self, subsys: str):
        rt = self.rt
        if subsys in _REGISTRY_AUX:
            return self._registry_columns(subsys)
        if subsys == "topk":
            return self._topk_columns()
        if subsys == "hostlist":
            return self._hostlist_columns()
        if subsys == "serverstatus":
            return self._serverstatus_columns()
        if subsys == "traceuniq":
            tcols, tlive = self.columns("tracereq")
            return api.traceuniq_from_trace(tcols, tlive)
        if subsys == "traceconn":
            return self._retry_aux(lambda: rt.traceconns.columns(
                rt.names, svc_task_ids=self._svc_task_ids()))
        if subsys in ("extactiveconn", "extclientconn", "exttracereq"):
            base = {"extactiveconn": "activeconn",
                    "extclientconn": "clientconn",
                    "exttracereq": "tracereq"}[subsys]
            idcol = "cliid" if subsys == "extclientconn" else "svcid"
            cols, live = self.columns(base)
            info_cols, _ = self._retry_aux(
                lambda: rt.svcreg.columns(rt.names))
            return api.info_join(cols, live, info_cols, idcol=idcol)
        if hasattr(rt, "_merged_columns_state"):     # ShardedRuntime
            if subsys == "shardlist":
                return self._shardlist_columns()
            return rt._merged_columns_state(subsys, self.state,
                                            self.dep, self._cols,
                                            reg=True)
        try:
            out = api.columns_for(rt.cfg, self.state, subsys,
                                  names=rt.names, dep=self.dep,
                                  svcreg=rt.svcreg, obs=rt)
        except KeyError:
            # a subsystem with fields but no single-node provider
            # (e.g. shardlist) fails like the live path: clean error
            raise ValueError(f"unknown subsystem {subsys!r}") from None
        if subsys == "procinfo":
            # tags mutate via CRUD; CRUD clears this snapshot's caches,
            # so joining INSIDE the memo stays consistent
            out = rt.tags.with_tags(out)
        return out

    def _registry_columns(self, subsys: str):
        return self._retry_aux(self.rt._aux[subsys])

    def _retry_aux(self, fn):
        """Run a host-side registry render under the registry lock,
        retrying the rare iteration-vs-mutation race (see module
        docstring)."""
        lock = getattr(self.rt, "_reg_lock", None)
        for attempt in range(_AUX_RETRIES):
            try:
                if lock is not None:
                    with lock:
                        return fn()
                return fn()
            except RuntimeError as e:
                if attempt + 1 == _AUX_RETRIES or (
                        "changed size" not in str(e)
                        and "mutated" not in str(e)):
                    raise
        raise AssertionError("unreachable")

    # ------------------------------------------- state-backed aux views
    def _topk_columns(self):
        """Heavy-hitter recovery over the FROZEN state (read-only
        dispatch — the shared decode+merge of ``timeview.hist_recover``
        works for both runtimes and never touches live buffers)."""
        from gyeeta_tpu.history.timeview import hist_recover
        rec = self._cols.get(
            "__hh_recover", lambda: hist_recover(self.rt, self.state))
        return api.heavy_topk_columns(
            rec["flows"], svc=self.columns("svcstate"),
            trace=self.columns("tracereq"))

    def _host_last_ticks(self) -> np.ndarray:
        rt = self.rt
        if hasattr(rt, "_shard_leaf"):               # ShardedRuntime
            return np.concatenate([
                np.asarray(rt._shard_leaf(self.state.host_last_tick, s))
                for s in range(rt.n)])
        return np.asarray(self.state.host_last_tick)

    def _hostlist_columns(self):
        last = self._host_last_ticks()
        seen = np.nonzero(last >= 0)[0]
        age = self.tick - last[seen]
        hostids, hostnames = api._host_name_cols(len(last), self.rt.names)
        cols = {
            "hostid": seen.astype(np.float64),
            "hostname": np.asarray(hostnames, object)[seen],
            "up": age <= api.DOWN_AFTER_TICKS,
            "lastseen": age.astype(np.float64),
        }
        return cols, np.ones(len(seen), bool)

    def _serverstatus_columns(self):
        rt = self.rt
        if hasattr(rt, "_rollup"):                   # ShardedRuntime
            nsvc = float(np.asarray(rt._rollup(self.state).n_svc_live))
        else:
            nsvc = float(np.asarray(self.state.tbl.n_live))
        return api.serverstatus_columns(
            rt, self.tick, int((self._host_last_ticks() >= 0).sum()),
            nsvc)

    def _svc_task_ids(self):
        cols, live = self.columns("taskstate")
        zero = "0" * 16
        from gyeeta_tpu.query.lazycols import rows_of
        idx = np.nonzero(np.asarray(live, bool))[0]
        got = rows_of(cols, ["taskid", "relsvcid"], idx)
        return {t for t, r in zip(got["taskid"], got["relsvcid"])
                if r != zero}

    def _shardlist_columns(self):
        rt = self.rt
        rows = []
        for sidx in range(rt.n):
            st = rt._shard_state(sidx, self.state, self._cols)
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
        return cols, np.ones(rt.n, bool)
