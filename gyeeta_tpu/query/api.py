"""Query API: QUERY_OPTIONS-style requests over live engine state.

The point-in-time path of the reference's web query engine
(``common/gy_query_common.h:24`` QUERY_OPTIONS parse →
``server/gy_mnodehandle.cc:203`` web_query_route_qtype → per-subsystem
``web_curr_*`` walks): here a request is one device readback + one columnar
criteria mask + host-side JSON row assembly. Freshness = one snapshot
latency (<1s north star); the historical path is ``gyeeta_tpu.history``.

Request shape (JSON-compatible dict, matching the Node webserver's query
envelope semantics)::

    {"subsys": "svcstate", "filter": "{ svcstate.state in 'Bad','Severe' }",
     "columns": ["svcid", "p95resp5s", "state"],    # optional projection
     "sortcol": "p95resp5s", "sortdesc": true,      # optional sort
     "maxrecs": 100}
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np

from gyeeta_tpu.engine.aggstate import AggState, EngineCfg
from gyeeta_tpu.ingest import decode as D
from gyeeta_tpu.query import criteria, fieldmaps, readback
from gyeeta_tpu.semantic import hoststate


class QueryOptions(NamedTuple):
    subsys: str
    filter: Optional[str] = None
    columns: Optional[tuple] = None
    sortcol: Optional[str] = None
    sortdesc: bool = True
    maxrecs: int = 1000
    aggr: Optional[tuple] = None       # e.g. ("avg(qps5s)", "count(*)")
    groupby: Optional[tuple] = None    # e.g. ("hostid",)

    @classmethod
    def from_json(cls, req: dict) -> "QueryOptions":
        known = {"subsys", "filter", "columns", "sortcol", "sortdesc",
                 "maxrecs", "aggr", "groupby"}
        unknown = set(req) - known
        if unknown:
            raise ValueError(f"unknown query options: {sorted(unknown)}")
        if "subsys" not in req:
            raise ValueError("query needs 'subsys'")
        cols = req.get("columns")
        ag = req.get("aggr")
        gb = req.get("groupby")
        if isinstance(ag, str):
            ag = [ag]
        if isinstance(gb, str):
            gb = [gb]
        return cls(
            subsys=req["subsys"], filter=req.get("filter"),
            columns=tuple(cols) if cols else None,
            sortcol=req.get("sortcol"),
            sortdesc=bool(req.get("sortdesc", True)),
            maxrecs=int(req.get("maxrecs", 1000)),
            aggr=tuple(ag) if ag else None,
            groupby=tuple(gb) if gb else None,
        )


def _hex_id(hi, lo):
    gid = (hi.astype(np.uint64) << np.uint64(32)) | lo.astype(np.uint64)
    return np.array([format(int(g), "016x") for g in gid], object)


def _names_of(names, kind, hi, lo):
    """Resolve interned 64-bit ids to names (hex-id fallback)."""
    if names is None:
        return _hex_id(hi, lo)
    ids = (hi.astype(np.uint64) << np.uint64(32)) | lo.astype(np.uint64)
    return names.resolve_array(kind, ids)


def _pad_idx(idx: np.ndarray, cap: int):
    """Row indices → (padded device array, true length). Padding to the
    next power of two bounds the jit-recompile count for the row-sliced
    readbacks at log2(capacity) shapes."""
    import jax.numpy as jnp

    n = len(idx)
    p = 8
    while p < n:
        p <<= 1
    p = min(p, cap)
    out = np.zeros(p, np.int32)
    out[:n] = idx
    return jnp.asarray(out), n


_QCOLS_OF_LEVEL = {
    -1: (("resp5s", "resp5s_us"), ("p95resp5s", "p95resp5s_us"),
         ("p99resp5s", "p99resp5s_us")),
    0: (("p95resp5m", "p95resp5m_us"),),
    1: (("p50resp5d", "p50resp5d_us"), ("p95resp5d", "p95resp5d_us")),
}


def svc_columns(cfg: EngineCfg, st: AggState, names=None):
    """svcstate subsystem columns (reference JSON names' units: msec).

    Returns a :class:`~gyeeta_tpu.query.lazycols.LazyCols`: the cheap
    gauge panel is eager; the per-window latency quantiles, volume/HLL
    sweeps and string columns materialize group-at-a-time only when a
    filter/sort references them, with O(result) row-sliced loaders for
    projection (VERDICT r4 #6 — a typical query no longer reads every
    (S, B) window or formats S hex ids)."""
    from gyeeta_tpu.ingest import wire
    from gyeeta_tpu.query.lazycols import LazyCols

    base = {k: np.asarray(v)
            for k, v in readback.svcstate_base(cfg, st).items()}
    g = base["stats"]
    hi, lo = base["glob_id_hi"], base["glob_id_lo"]
    eager = {
        "nconns": g[:, D.STAT_NCONNS],
        "nactive": g[:, D.STAT_NCONNS_ACTIVE],
        "nprocs": g[:, D.STAT_NTASKS],
        "kbin15s": g[:, D.STAT_KB_IN],
        "kbout15s": g[:, D.STAT_KB_OUT],
        "sererr": g[:, D.STAT_SER_ERRORS],
        "clierr": g[:, D.STAT_CLI_ERRORS],
        "delayus": g[:, D.STAT_TASKS_DELAY_US],
        "cpudelus": g[:, D.STAT_TASKS_CPUDELAY_US],
        "iodelus": g[:, D.STAT_TASKS_BLKIODELAY_US],
        "usercpu": g[:, D.STAT_USER_CPU],
        "syscpu": g[:, D.STAT_SYS_CPU],
        "rssmb": g[:, D.STAT_RSS_MB],
        "nissue": g[:, D.STAT_NTASKS_ISSUE],
        "state": base["state"],
        "issue": base["issue"],
        "hostid": base["hostid"],
    }

    def _qcols(level, d):
        d = {k: np.asarray(v) for k, v in d.items()}
        return {col: d[src] / 1e3 for col, src in _QCOLS_OF_LEVEL[level]}

    def _qload(level):
        return lambda: _qcols(level,
                              readback.svcstate_qlevel(cfg, st, level))

    def _qrows(level):
        def load(idx):
            pidx, n = _pad_idx(idx, cfg.svc_capacity)
            d = readback.svcstate_qlevel_rows(cfg, st, pidx, level)
            return {k: v[:n] for k, v in _qcols(level, d).items()}
        return load

    def _vol_rows(idx):
        pidx, n = _pad_idx(idx, cfg.svc_capacity)
        d = readback.svcstate_vol_rows(cfg, st, pidx)
        return {k: np.asarray(v)[:n] for k, v in d.items()}

    def _cli_rows(idx):
        pidx, n = _pad_idx(idx, cfg.svc_capacity)
        d = readback.svcstate_cli_rows(cfg, st, pidx)
        return {k: np.asarray(v)[:n] for k, v in d.items()}

    group_of = {"svcid": "sid", "svcname": "sname",
                "nqry5s": "vol", "qps5s": "vol", "nclients": "cli"}
    load = {
        "sid": lambda: {"svcid": _hex_id(hi, lo)},
        "sname": lambda: {"svcname": _names_of(
            names, wire.NAME_KIND_SVC, hi, lo)},
        "vol": lambda: {k: np.asarray(v) for k, v in
                        readback.svcstate_vol(cfg, st).items()},
        "cli": lambda: {k: np.asarray(v) for k, v in
                        readback.svcstate_cli(cfg, st).items()},
    }
    load_rows = {
        "sid": lambda idx: {"svcid": _hex_id(hi[idx], lo[idx])},
        "sname": lambda idx: {"svcname": _names_of(
            names, wire.NAME_KIND_SVC, hi[idx], lo[idx])},
        "vol": _vol_rows,
        "cli": _cli_rows,
    }
    for level, pairs in _QCOLS_OF_LEVEL.items():
        key = f"q{level}"
        for col, _src in pairs:
            group_of[col] = key
        load[key] = _qload(level)
        load_rows[key] = _qrows(level)
    return LazyCols(eager, group_of, load, load_rows), base["live"]


# a host is Down after this many base ticks without a report (6 x 5s = 30s;
# ref: server marks parthas inactive on missed status pings,
# gy_comm_proto.h:974 PARTHA_STATUS + conn timeouts gy_mconnhdlr.h:79)
DOWN_AFTER_TICKS = 6


def host_columns(cfg: EngineCfg, st: AggState, names=None) -> dict:
    panel = np.asarray(st.host_panel)
    last = np.asarray(st.host_last_tick)
    now = int(np.asarray(st.resp_win.tick))
    reported = last >= 0
    down = reported & (now - last > DOWN_AFTER_TICKS)
    states = hoststate.classify_hosts(
        ntask_issue=panel[:, D.HOST_NTASKS_ISSUE],
        ntask_severe=panel[:, D.HOST_NTASKS_SEVERE],
        nlisten_issue=panel[:, D.HOST_NLISTEN_ISSUE],
        nlisten_severe=panel[:, D.HOST_NLISTEN_SEVERE],
        cpu_issue=panel[:, D.HOST_CPU_ISSUE] > 0,
        mem_issue=panel[:, D.HOST_MEM_ISSUE] > 0,
        severe_cpu=panel[:, D.HOST_SEVERE_CPU] > 0,
        severe_mem=panel[:, D.HOST_SEVERE_MEM] > 0)
    from gyeeta_tpu.semantic.states import STATE_DOWN
    states = np.where(down, STATE_DOWN, states)
    hostids, hostnames = _host_name_cols(panel.shape[0], names)
    cols = {
        "hostid": hostids,
        "hostname": hostnames,
        "nprocissue": panel[:, D.HOST_NTASKS_ISSUE],
        "nprocsevere": panel[:, D.HOST_NTASKS_SEVERE],
        "nproc": panel[:, D.HOST_NTASKS],
        "nlistissue": panel[:, D.HOST_NLISTEN_ISSUE],
        "nlistsevere": panel[:, D.HOST_NLISTEN_SEVERE],
        "nlisten": panel[:, D.HOST_NLISTEN],
        "state": states,
        "cpuissue": panel[:, D.HOST_CPU_ISSUE],
        "memissue": panel[:, D.HOST_MEM_ISSUE],
        "severecpu": panel[:, D.HOST_SEVERE_CPU],
        "severemem": panel[:, D.HOST_SEVERE_MEM],
    }
    return cols, reported


def task_columns(cfg: EngineCfg, st: AggState, names=None) -> dict:
    """taskstate subsystem columns (ref MAGGR_TASK / aggrtaskstate)."""
    snap = {k: np.asarray(v)
            for k, v in readback.task_snapshot(cfg, st).items()}
    g = snap["stats"]
    cols = _task_identity_cols(snap, names)
    cols |= {
        "tcpkb": g[:, D.TASK_TCP_KB],
        "tcpconns": g[:, D.TASK_TCP_CONNS],
        "cpu": g[:, D.TASK_CPU_PCT],
        "cpup95": snap["cpu_p95"],
        "rssmb": g[:, D.TASK_RSS_MB],
        "cpudelms": g[:, D.TASK_CPU_DELAY_MS],
        "vmdelms": g[:, D.TASK_VM_DELAY_MS],
        "iodelms": g[:, D.TASK_BLKIO_DELAY_MS],
        "ntasks": g[:, D.TASK_NTASKS],
        "nissue": g[:, D.TASK_NTASKS_ISSUE],
        "forks": g[:, D.TASK_FORKS_SEC],
        "state": snap["state"],
        "issue": snap["issue"],
        "hostid": snap["hostid"],
    }
    return cols, snap["live"]


def flow_columns(cfg: EngineCfg, st: AggState, k: int = 128,
                 names=None) -> dict:
    snap = {kk: np.asarray(v)
            for kk, v in readback.flow_snapshot(cfg, st, k).items()}
    valid = snap["flow_bytes"] > 0
    cols = {
        "flowid": _hex_id(snap["flow_hi"], snap["flow_lo"]),
        "bytes": snap["flow_bytes"],
        "evictedbytes": np.full(len(valid), float(snap["evicted_bytes"])),
    }
    return cols, valid


# rows emitted per topk metric before maxrecs/filters apply — the
# union view stays bounded no matter the slab geometry (the reference
# caps its TOP_N walks the same way, gy_comm_proto.h:1415)
TOPK_PER_METRIC = 64


def heavy_topk_columns(flow_rows, svc=None, trace=None,
                       per_metric: int = TOPK_PER_METRIC):
    """The ``topk`` subsystem's union columns — shared by Runtime and
    ShardedRuntime so the three query edges render identical rows.

    ``flow_rows``: pre-merged heavy flows as ``(id_hex, value,
    errbound, source)`` tuples sorted heaviest-first (exact top-K lanes
    ∪ invertible-sketch recoveries — see ``Runtime.heavy_recover``).
    ``svc``/``trace``: the subsystem's (cols, live) snapshots for the
    dense rankings (top services by conns / error rate, top APIs by
    p99). Every row carries its error bound: exact lanes undercount by
    ≤ errbound, recovered rows are upper bounds overcounting by ≤
    errbound, dense rows are exact (0).
    """
    metric, rank, ids, names_, value, errb, source = \
        [], [], [], [], [], [], []

    def emit(m, rows):
        for i, (rid, rname, val, eb, src) in enumerate(
                rows[:per_metric]):
            metric.append(m)
            rank.append(float(i + 1))
            ids.append(rid)
            names_.append(rname)
            value.append(float(val))
            errb.append(float(eb))
            source.append(src)

    emit("bytes", [(rid, "", val, eb, src)
                   for rid, val, eb, src in flow_rows])

    def dense(cols, live, valcol, idcol, namecol, valfn=None):
        from gyeeta_tpu.query.lazycols import rows_of

        idx = np.nonzero(np.asarray(live, bool))[0]
        if len(idx) == 0:
            return []
        vals = (valfn(cols, idx) if valfn is not None
                else np.asarray(cols[valcol], np.float64)[idx])
        order = np.argsort(vals, kind="stable")[::-1]
        keep = order[: per_metric]
        keep = keep[vals[keep] > 0]
        # id/name projection over just the kept rows (LazyCols row
        # path — the string groups never format at slab width here)
        got = rows_of(cols, [idcol, namecol], idx[keep])
        rows = [(got[idcol][j], got[namecol][j], vals[keep[j]], 0.0,
                 "dense") for j in range(len(keep))]
        # deterministic rank on TIED values: value desc, id asc — the
        # kept window renders bit-identically whether the rows came
        # from one slab or a mesh's concatenated shard slabs (lane
        # order differs; the ranking must not)
        rows.sort(key=lambda r: (-r[2], r[0]))
        return rows

    if svc is not None:
        scols, slive = svc
        emit("conns", dense(scols, slive, "nconns", "svcid", "svcname"))

        def errrate(cols, idx):
            err = np.asarray(cols["sererr"], np.float64)[idx]
            nq = np.asarray(cols["nqry5s"], np.float64)[idx]
            return err / np.maximum(nq, 1.0)

        emit("errrate", dense(scols, slive, None, "svcid", "svcname",
                              valfn=errrate))
    if trace is not None:
        from gyeeta_tpu.query.lazycols import rows_of

        tcols, tlive = trace
        idx = np.nonzero(np.asarray(tlive, bool))[0]
        rows = []
        if len(idx):
            p99 = np.asarray(tcols["p99resp"], np.float64)[idx]
            keep = np.argsort(p99, kind="stable")[::-1][: per_metric]
            keep = keep[p99[keep] > 0]
            got = rows_of(tcols, ["svcid", "svcname", "api"], idx[keep])
            rows = [(got["svcid"][j],
                     f"{got['svcname'][j]}:{got['api'][j]}",
                     p99[keep[j]], 0.0, "dense")
                    for j in range(len(keep))]
            rows.sort(key=lambda r: (-r[2], r[0], r[1]))
        emit("p99resp", rows)

    n = len(metric)
    obj = lambda vals: _obj_col(vals)  # noqa: E731
    cols = {
        "metric": obj(metric), "rank": np.asarray(rank, np.float64),
        "id": obj(ids), "name": obj(names_),
        "value": np.asarray(value, np.float64),
        "errbound": np.asarray(errb, np.float64),
        "source": obj(source),
    }
    return cols, np.ones(n, bool)


def _obj_col(vals) -> np.ndarray:
    out = np.empty(len(vals), object)
    out[:] = [str(v) for v in vals]
    return out


def _host_name_cols(n: int, names):
    """(hostids, hostnames) shared by every host-axis subsystem."""
    from gyeeta_tpu.ingest import wire

    hostids = np.arange(n)
    if names is None:
        hostnames = np.array([str(h) for h in hostids], object)
    else:
        hostnames = np.array(
            [names.lookup(wire.NAME_KIND_HOST, h) or str(h)
             for h in hostids], object)
    return hostids, hostnames


def cpumem_columns(cfg: EngineCfg, st: AggState, names=None) -> dict:
    """cpumem subsystem: raw 2s gauges + server-side classification."""
    vals = np.asarray(st.host_cm)
    last = np.asarray(st.cm_last_tick)
    reported = last >= 0
    hostids, hostnames = _host_name_cols(vals.shape[0], names)
    cols = {
        "hostid": hostids,
        "hostname": hostnames,
        "cpu": vals[:, D.CM_CPU_PCT],
        "usercpu": vals[:, D.CM_USERCPU_PCT],
        "syscpu": vals[:, D.CM_SYSCPU_PCT],
        "iowait": vals[:, D.CM_IOWAIT_PCT],
        "corecpu": vals[:, D.CM_MAX_CORE_CPU_PCT],
        "cs": vals[:, D.CM_CS_SEC],
        "forks": vals[:, D.CM_FORKS_SEC],
        "runq": vals[:, D.CM_PROCS_RUNNING],
        "rsspct": vals[:, D.CM_RSS_PCT],
        "commitpct": vals[:, D.CM_COMMIT_PCT],
        "swapfreepct": vals[:, D.CM_SWAP_FREE_PCT],
        "pginout": vals[:, D.CM_PG_INOUT_SEC],
        "swapinout": vals[:, D.CM_SWAP_INOUT_SEC],
        "allocstall": vals[:, D.CM_ALLOCSTALL_SEC],
        "oom": vals[:, D.CM_OOM_KILLS],
        "cpustate": np.asarray(st.cm_cpu_state),
        "cpuissue": np.asarray(st.cm_cpu_issue),
        "memstate": np.asarray(st.cm_mem_state),
        "memissue": np.asarray(st.cm_mem_issue),
    }
    return cols, reported


def trace_columns(cfg: EngineCfg, st: AggState, names=None) -> dict:
    """tracereq subsystem: per-(service, API) latency aggregates."""
    from gyeeta_tpu.engine import step as S
    from gyeeta_tpu.ingest import wire

    snap = {k: np.asarray(v)
            for k, v in readback.trace_snapshot(cfg, st).items()}
    ctr = snap["ctr"]
    cols = {
        "svcid": _hex_id(snap["svc_hi"], snap["svc_lo"]),
        "svcname": _names_of(names, wire.NAME_KIND_SVC,
                             snap["svc_hi"], snap["svc_lo"]),
        "api": _names_of(names, wire.NAME_KIND_API,
                         snap["api_hi"], snap["api_lo"]),
        "proto": snap["proto"],
        "nreq": ctr[:, S.APIC_NREQ],
        "nerr": ctr[:, S.APIC_NERR],
        "bytesin": ctr[:, S.APIC_BYTES_IN],
        "bytesout": ctr[:, S.APIC_BYTES_OUT],
        "p50resp": snap["p50_us"] / 1e3,
        "p95resp": snap["p95_us"] / 1e3,
        "p99resp": snap["p99_us"] / 1e3,
        "hostid": snap["hostid"],
    }
    return cols, snap["live"]


def cluster_columns(cfg: EngineCfg, st: AggState, names=None) -> dict:
    hcols, reported = host_columns(cfg, st)
    c = hoststate.cluster_state(np.asarray(hcols["state"]), valid=reported)
    cols = {k: np.array([float(v)]) for k, v in c.items()}
    return cols, np.ones(1, bool)


def _sorted_task_comms(key, comm, live):
    """Live task-slab rows as (sorted keys, their comm ids)."""
    k, c = key[live], comm[live]
    order = np.argsort(k, kind="stable")
    return k[order], c[order]


def _comm_names(names, skey, scomm, task_hi, task_lo):
    """Process-group ids → comm names over sorted task-slab arrays (one
    binary search a row); an unknown group renders as its hex id."""
    from gyeeta_tpu.ingest import wire

    fallback = _hex_id(task_hi, task_lo)
    if names is None or not len(skey):
        return fallback
    want = ((task_hi.astype(np.uint64) << np.uint64(32))
            | task_lo.astype(np.uint64))
    pos = np.minimum(np.searchsorted(skey, want), len(skey) - 1)
    comm_ids = np.where(skey[pos] == want, scomm[pos], np.uint64(0))
    resolved = names.resolve_array(wire.NAME_KIND_COMM, comm_ids)
    return np.where(comm_ids != 0, resolved, fallback)


def task_comm_names_from(names, key, comm, live, task_hi, task_lo):
    """Resolve process-group ids → comm names given task-slab arrays
    (key/comm as u64, live mask) — shared by the single-node provider and
    the sharded runtime's gathered slabs."""
    return _comm_names(names, *_sorted_task_comms(key, comm, live),
                       task_hi, task_lo)


def _task_slab_arrays(st: AggState):
    hi, lo = np.asarray(st.task_tbl.key_hi), np.asarray(st.task_tbl.key_lo)
    key = (hi.astype(np.uint64) << np.uint64(32)) | lo
    comm = (np.asarray(st.task_comm_hi).astype(np.uint64)
            << np.uint64(32)) | np.asarray(st.task_comm_lo)
    live = (hi != np.uint32(0xFFFFFFFF)) | (lo != np.uint32(0xFFFFFFFF))
    return key, comm, live


def dep_edges_view(dep, obs=None, merged=None) -> dict:
    """One build of the dependency views' numeric edge columns on the
    host: one shard's device program over its edge slab
    (``readback.dep_edges_snapshot``) — or, for the mesh, its ``merged``
    EdgeSet — and the readback, every leaf copied out together. ``obs``
    (a runtime: its ``spans`` and ``stats``) times it as span
    ``dep_view`` and counts it (``dep_view_builds``, ``dep_view_edges``,
    ``dep_merge_dropped``: what a merge left out, 0 for one shard, which
    merges nothing). Callers memoize the columns they derive per state
    version, so a snapshot builds once whatever its dashboards ask."""
    import contextlib

    import jax

    if merged is None and dep is None:
        raise ValueError("the dependency views need a dependency graph "
                         "(runtime not configured with one)")
    with obs.spans.span("dep_view") if obs is not None \
            else contextlib.nullcontext():
        snap = jax.device_get(
            readback.dep_edges_snapshot(dep) if merged is None
            else readback.edge_cols(merged))
    dropped = snap.pop("e_dropped")
    if obs is not None:
        obs.stats.bump("dep_view_builds")
        obs.stats.gauge("dep_view_edges", float(snap["e_live"].sum()))
        obs.stats.gauge("dep_merge_dropped", float(dropped))
    return snap


def dep_cols_from_edges(snap: dict, names=None, task_names_fn=None,
                        obs=None):
    """svcdependency columns over a dep-edge column snapshot (one
    shard's slab or the mesh's merged set): a
    :class:`~gyeeta_tpu.query.lazycols.LazyCols` over the LIVE edges, in
    slab order. The numeric columns (and the four key words) are eager;
    the hex ids and the names are rendered for the rows a query returns,
    or at the live width when a filter or sort names one. A ``groupby``
    on an id groups on its key words (``keys_of``).

    ``task_names_fn(hi, lo) -> names`` resolves task-group callers
    (single-node: the local task slab; sharded: gathered slabs); it is
    asked once per projection, for the task rows only."""
    from gyeeta_tpu.ingest import wire
    from gyeeta_tpu.query.lazycols import LazyCols

    live = np.nonzero(snap["e_live"])[0]
    cli_hi, cli_lo = snap["e_cli_hi"][live], snap["e_cli_lo"][live]
    ser_hi, ser_lo = snap["e_ser_hi"][live], snap["e_ser_lo"][live]
    cli_svc = snap["e_cli_svc"][live]
    eager = {
        "clisvc": cli_svc,
        "nconn": snap["e_nconn"][live],
        "bytes": snap["e_bytes"][live],
        "cli_hi": cli_hi, "cli_lo": cli_lo,
        "ser_hi": ser_hi, "ser_lo": ser_lo,
    }

    def ids(idx=slice(None)):
        return {"cliid": _hex_id(cli_hi[idx], cli_lo[idx]),
                "serid": _hex_id(ser_hi[idx], ser_lo[idx])}

    def cli_names(idx=slice(None)):
        # caller name: listener name for svc→svc edges, comm (via the
        # task slab) for task→svc edges
        hi, lo, is_svc = cli_hi[idx], cli_lo[idx], cli_svc[idx]
        out = np.empty(len(hi), object)
        out[is_svc] = _names_of(names, wire.NAME_KIND_SVC,
                                hi[is_svc], lo[is_svc])
        task = ~is_svc
        if task.any():
            out[task] = (task_names_fn(hi[task], lo[task])
                         if task_names_fn is not None
                         else _hex_id(hi[task], lo[task]))
        return out

    def name_cols(idx=slice(None)):
        return {"cliname": cli_names(idx),
                "sername": _names_of(names, wire.NAME_KIND_SVC,
                                     ser_hi[idx], ser_lo[idx])}

    group_of = {"cliid": "id", "serid": "id",
                "cliname": "name", "sername": "name"}
    loaders = {"id": ids, "name": name_cols}
    cols = LazyCols(
        eager, group_of, loaders, loaders,
        keys_of={"cliid": ("cli_hi", "cli_lo"),
                 "serid": ("ser_hi", "ser_lo")},
        on_rows=None if obs is None else (
            lambda n: obs.stats.bump("dep_rows_materialised", n)))
    return cols, np.ones(len(live), bool)


class _TaskComms:
    """Process-group id → comm name over one state's task slab, read
    back and indexed at the first ask (a view's row loaders share it)."""

    def __init__(self, st: AggState, names):
        self._st, self._names = st, names
        self._slab = None

    def __call__(self, task_hi, task_lo):
        if self._names is None:
            return _hex_id(task_hi, task_lo)
        slab = self._slab
        if slab is None:      # workers may race here: both get the same
            slab = self._slab = _sorted_task_comms(
                *_task_slab_arrays(self._st))
        return _comm_names(self._names, *slab, task_hi, task_lo)


def dep_columns(cfg: EngineCfg, st: AggState, names=None,
                dep=None, obs=None):
    """svcdependency subsystem: one row per (caller → service) edge,
    read straight from the shard's edge slab."""
    return dep_cols_from_edges(dep_edges_view(dep, obs), names,
                               _TaskComms(st, names), obs)


def mesh_columns(cfg: EngineCfg, st: AggState, names=None,
                 dep=None, obs=None) -> dict:
    """svcmesh subsystem: one row per service in the dependency mesh,
    labelled with its coalesced cluster (ref svc mesh clusters,
    ``server/gy_shconnhdlr.h:1301``)."""
    from gyeeta_tpu.ingest import wire

    if dep is None:
        raise ValueError("svcmesh needs a dependency graph")
    snap = {k: np.asarray(v)
            for k, v in readback.dep_mesh_snapshot(dep).items()}
    cols = {
        "svcid": _hex_id(snap["n_hi"], snap["n_lo"]),
        "svcname": _names_of(names, wire.NAME_KIND_SVC,
                             snap["n_hi"], snap["n_lo"]),
        "clusterid": snap["n_label"],
        "clustersize": snap["n_size"],
    }
    return cols, snap["n_mask"]


_COLUMNS_OF = {
    fieldmaps.SUBSYS_SVCSTATE: svc_columns,
    fieldmaps.SUBSYS_HOSTSTATE: host_columns,
    fieldmaps.SUBSYS_CLUSTERSTATE: cluster_columns,
    fieldmaps.SUBSYS_FLOWSTATE: flow_columns,
    fieldmaps.SUBSYS_TASKSTATE: task_columns,
    fieldmaps.SUBSYS_TOPCPU: task_columns,
    fieldmaps.SUBSYS_TOPRSS: task_columns,
    fieldmaps.SUBSYS_TOPDELAY: task_columns,
    fieldmaps.SUBSYS_TOPFORK: task_columns,
    fieldmaps.SUBSYS_CPUMEM: cpumem_columns,
    fieldmaps.SUBSYS_TRACEREQ: trace_columns,
}

def _group_edges(snap: dict, end: str):
    """Group live dep edges by one endpoint (``cli`` or ``ser``) →
    (hi, lo, inv, segsum, live_idx). One np.unique over the packed
    64-bit ids + np.add.at segment sums — shared by the activeconn
    (group by server) and clientconn (group by caller) views."""
    live = np.nonzero(snap["e_live"])[0]
    ids64 = ((snap[f"e_{end}_hi"][live].astype(np.uint64) << np.uint64(32))
             | snap[f"e_{end}_lo"][live].astype(np.uint64))
    ids, inv = np.unique(ids64, return_inverse=True)
    n = len(ids)

    def segsum(vals):
        out = np.zeros(n, np.float64)
        np.add.at(out, inv, vals.astype(np.float64))
        return out

    hi = (ids >> np.uint64(32)).astype(np.uint32)
    lo = (ids & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    return hi, lo, inv, segsum, live


def activeconn_from_edges(snap: dict, names=None):
    """Group a dep-edge column snapshot by server service (shared by the
    single-node and sharded activeconn providers)."""
    from gyeeta_tpu.ingest import wire

    hi, lo, inv, segsum, live = _group_edges(snap, "ser")
    cols = {
        "svcid": _hex_id(hi, lo),
        "svcname": _names_of(names, wire.NAME_KIND_SVC, hi, lo),
        "nclients": segsum(np.ones(len(live))),
        "nconn": segsum(snap["e_nconn"][live]),
        "bytes": segsum(snap["e_bytes"][live]),
        "nsvccli": segsum(snap["e_cli_svc"][live]),
    }
    return cols, np.ones(len(hi), bool)


def activeconn_columns(cfg: EngineCfg, st: AggState, names=None,
                       dep=None, obs=None) -> dict:
    """activeconn subsystem: per-service caller rollup of the dep edges
    (ref activeconn/clientconn views over DEPENDS maps)."""
    return activeconn_from_edges(dep_edges_view(dep, obs), names)


def svcinfo_columns(cfg: EngineCfg, st: AggState, names=None,
                    svcreg=None) -> dict:
    """svcinfo subsystem: host-side listener-metadata registry."""
    if svcreg is None:
        raise ValueError("svcinfo needs the listener-info registry")
    return svcreg.columns(names)


def clientconn_from_edges(snap: dict, names=None, task_names_fn=None):
    """Group dep edges by CALLER (the clientconn view: what does this
    process-group / service call, ref remoteconn/clientconn tables).

    ``task_names_fn(hi, lo) -> names`` resolves task-group callers
    (single-node: the local task slab; sharded: gathered slabs)."""
    from gyeeta_tpu.ingest import wire

    hi, lo, inv, segsum, live = _group_edges(snap, "cli")
    is_svc = np.zeros(len(hi), bool)
    np.maximum.at(is_svc, inv, snap["e_cli_svc"][live].astype(bool))
    svc_names = _names_of(names, wire.NAME_KIND_SVC, hi, lo)
    task_names = (task_names_fn(hi, lo) if task_names_fn is not None
                  else _hex_id(hi, lo))
    cols = {
        "cliid": _hex_id(hi, lo),
        "cliname": np.where(is_svc, svc_names, task_names),
        "clisvc": is_svc,
        "nservers": segsum(np.ones(len(live))),
        "nconn": segsum(snap["e_nconn"][live]),
        "bytes": segsum(snap["e_bytes"][live]),
    }
    return cols, np.ones(len(hi), bool)


def clientconn_columns(cfg: EngineCfg, st: AggState, names=None,
                       dep=None, obs=None) -> dict:
    return clientconn_from_edges(dep_edges_view(dep, obs), names,
                                 _TaskComms(st, names))


def svcsumm_from_svc(cols, live, names=None):
    """Group svcstate columns by host → svcsumm columns. Takes the
    ALREADY-MERGED columns so single-node and sharded paths summarize
    identically (grouping per shard would fragment hosts whose services
    land on several shards)."""
    from gyeeta_tpu.ingest import wire
    from gyeeta_tpu.semantic import states as S

    idx = np.nonzero(live)[0]
    hosts = np.asarray(cols["hostid"])[idx].astype(np.int64)
    ids, inv = np.unique(hosts, return_inverse=True)
    n = len(ids)

    def segsum(vals):
        out = np.zeros(n, np.float64)
        np.add.at(out, inv, np.asarray(vals, np.float64))
        return out

    state = np.asarray(cols["state"])[idx]
    if names is not None:
        hostnames = names.resolve_array(
            wire.NAME_KIND_HOST, ids.astype(np.uint64))
    else:
        hostnames = np.array([str(i) for i in ids], object)
    out = {
        "hostid": ids.astype(np.float64),
        "hostname": hostnames,
        "nsvc": segsum(np.ones(len(idx))),
        "nidle": segsum(state == S.STATE_IDLE),
        "ngood": segsum(state == S.STATE_GOOD),
        "nok": segsum(state == S.STATE_OK),
        "nbad": segsum(state == S.STATE_BAD),
        "nsevere": segsum(state == S.STATE_SEVERE),
        "ndown": segsum(state == S.STATE_DOWN),
        "nissue": segsum(state >= S.STATE_BAD),
        "totqps": segsum(np.asarray(cols["qps5s"])[idx]),
        "totactive": segsum(np.asarray(cols["nactive"])[idx]),
        "totkbin": segsum(np.asarray(cols["kbin15s"])[idx]),
        "totkbout": segsum(np.asarray(cols["kbout15s"])[idx]),
    }
    return out, np.ones(n, bool)


def svcsumm_columns(cfg: EngineCfg, st: AggState, names=None):
    """svcsumm subsystem: per-host service-state summary (the
    LISTEN_SUMM_STATS rollup, ``server/gy_msocket.h:841``)."""
    cols, live = svc_columns(cfg, st, names=names)
    return svcsumm_from_svc(cols, live, names)


_EXT_JOIN_KEYS = (("ip", ""), ("port", 0.0), ("comm", ""),
                  ("cmdline", ""), ("pid", 0.0), ("tstart", 0.0))


def info_join(cols, live, info_cols, idcol="svcid",
              keys=_EXT_JOIN_KEYS):
    """Left-join svcinfo metadata columns onto any column set whose
    ``idcol`` holds service glob-id hex strings — the "extended"
    subsystem mechanic (state ⋈ info, ``gy_mnodehandle.cc:4657``).
    Rows without announced metadata keep defaults."""
    from gyeeta_tpu.query.lazycols import LazyCols
    n = len(cols[idcol])
    # ext views are full-width joins: a lazy column set must
    # materialize everything (dict() alone would copy only the
    # already-loaded groups)
    joined = cols.full() if isinstance(cols, LazyCols) else dict(cols)
    out = {}
    for key, default in keys:
        col = np.empty(n, object if isinstance(default, str)
                       else np.float64)
        col[:] = default
        out[key] = col
    if info_cols:
        pos_of = {sid: j for j, sid in enumerate(info_cols["svcid"])}
        for i in np.nonzero(np.asarray(live, bool))[0]:
            j = pos_of.get(cols[idcol][i])
            if j is not None:
                for key, _ in keys:
                    out[key][i] = info_cols[key][j]
    joined.update(out)
    return joined, live


def extsvc_join(cols, live, info_cols):
    """Join svcstate columns with svcinfo columns on svcid (shared by
    single-node and sharded extsvcstate providers)."""
    return info_join(cols, live, info_cols)


def traceuniq_from_trace(tcols, tlive):
    """Group per-(svc, api) trace columns by service → traceuniq
    columns (ref traceuniqtbl). Shared by both runtimes."""
    idx = np.nonzero(np.asarray(tlive, bool))[0]
    svc = np.asarray(tcols["svcid"])[idx]
    ids, inv = np.unique(svc, return_inverse=True)
    n = len(ids)

    def segsum(vals):
        out = np.zeros(n, np.float64)
        np.add.at(out, inv, np.asarray(vals, np.float64))
        return out

    name_of = {}
    for j, i in enumerate(idx):
        name_of.setdefault(svc[j], tcols["svcname"][i])
    cols = {
        "svcid": ids.astype(object),
        "svcname": np.array([name_of[s] for s in ids], object),
        "napis": segsum(np.ones(len(idx))),
        "nreq": segsum(np.asarray(tcols["nreq"])[idx]),
        "nerr": segsum(np.asarray(tcols["nerr"])[idx]),
    }
    return cols, np.ones(n, bool)


def extsvcstate_columns(cfg: EngineCfg, st: AggState, names=None,
                        svcreg=None):
    """extsvcstate: svcstate ⋈ svcinfo on svcid (the reference's
    "extended" subsystems join state+info records,
    ``server/gy_mnodehandle.cc:4657``). State rows without announced
    metadata still appear, with empty info columns."""
    cols, live = svc_columns(cfg, st, names=names)
    info_cols, _ = (svcreg.columns(names) if svcreg is not None
                    else ({}, None))
    return extsvc_join(cols, live, info_cols)


def svcprocmap_columns(cfg: EngineCfg, st: AggState, names=None,
                       svcreg=None):
    """svcprocmap: listener ↔ process-group mapping via the shared
    related_listen_id (ref LISTEN_TASKMAP_NOTIFY,
    ``gy_comm_proto.h:2813``)."""
    tcols, tlive = task_columns(cfg, st, names=names)
    info_cols, _ = (svcreg.columns(names) if svcreg is not None
                    else (None, None))
    return svcprocmap_join(tcols, tlive, info_cols)


def svcprocmap_join(tcols, tlive, info_cols):
    """Join task columns with svcinfo on related_listen_id (shared by
    single-node and sharded providers — pass MERGED task columns)."""
    rows = {"svcid": [], "svcname": [], "relsvcid": [], "taskid": [],
            "comm": [], "hostid": []}
    if info_cols is not None and len(tcols["taskid"]):
        by_rel: dict[str, list[int]] = {}
        for i in np.nonzero(tlive)[0]:
            by_rel.setdefault(tcols["relsvcid"][i], []).append(i)
        for j, rel in enumerate(info_cols["relsvcid"]):
            for i in by_rel.get(rel, ()):
                rows["svcid"].append(info_cols["svcid"][j])
                rows["svcname"].append(info_cols["svcname"][j])
                rows["relsvcid"].append(rel)
                rows["taskid"].append(tcols["taskid"][i])
                rows["comm"].append(tcols["comm"][i])
                rows["hostid"].append(float(tcols["hostid"][i]))
    n = len(rows["svcid"])
    cols = {}
    for k, vals in rows.items():
        if k == "hostid":
            cols[k] = np.array(vals, np.float64)
        else:
            col = np.empty(n, object)
            col[:] = vals
            cols[k] = col
    return cols, np.ones(n, bool)


def _task_identity_cols(snap, names):
    """Shared identity columns over a task snapshot (taskid/comm/
    relsvcid rendering in ONE place for taskstate + procinfo)."""
    from gyeeta_tpu.ingest import wire

    return {
        "taskid": _hex_id(snap["key_hi"], snap["key_lo"]),
        "comm": _names_of(names, wire.NAME_KIND_COMM,
                          snap["comm_hi"], snap["comm_lo"]),
        "relsvcid": _hex_id(snap["rel_hi"], snap["rel_lo"]),
    }


def procinfo_columns(cfg: EngineCfg, st: AggState, names=None):
    """procinfo: the static face of the process-group slab (identity,
    placement, service linkage — ref aggrtaskinfotbl). Built straight
    from the task snapshot: the related-listener ids exist as (hi, lo)
    arrays there — no hex round trip."""
    from gyeeta_tpu.ingest import wire

    snap = {k: np.asarray(v)
            for k, v in readback.task_snapshot(cfg, st).items()}
    rel_ids = ((snap["rel_hi"].astype(np.uint64) << np.uint64(32))
               | snap["rel_lo"].astype(np.uint64))
    if names is not None:
        svcnames = names.resolve_array(wire.NAME_KIND_SVC, rel_ids,
                                       fallback_hex=False)
    else:
        svcnames = np.full(len(rel_ids), "", object)
    cols = _task_identity_cols(snap, names)
    cols.update({
        "svcname": np.where(rel_ids == 0, "", svcnames),
        "ntasks": snap["stats"][:, D.TASK_NTASKS],
        "hostid": snap["hostid"],
    })
    return cols, snap["live"]


# svcsumm derives from svc_columns (defined below the map literal)
_COLUMNS_OF[fieldmaps.SUBSYS_SVCSUMM] = svcsumm_columns
_COLUMNS_OF[fieldmaps.SUBSYS_PROCINFO] = procinfo_columns
_COLUMNS_OF[fieldmaps.SUBSYS_TOPPGCPU] = task_columns

# subsystems whose columns come from the dependency graph, not AggState
_DEP_COLUMNS_OF = {
    fieldmaps.SUBSYS_SVCDEP: dep_columns,
    fieldmaps.SUBSYS_SVCMESH: mesh_columns,
    fieldmaps.SUBSYS_ACTIVECONN: activeconn_columns,
    fieldmaps.SUBSYS_CLIENTCONN: clientconn_columns,
}

# subsystems backed by the host-side listener-metadata registry
_SVCREG_COLUMNS_OF = {
    fieldmaps.SUBSYS_SVCINFO: svcinfo_columns,
    fieldmaps.SUBSYS_EXTSVCSTATE: extsvcstate_columns,
    fieldmaps.SUBSYS_SVCPROCMAP: svcprocmap_columns,
}

# top-N views: preset sort + limit over taskstate columns
# (ref TASK_TOP_PROCS top-15 CPU / top-8 RSS, gy_comm_proto.h:1415)
_TOP_PRESETS = {
    fieldmaps.SUBSYS_TOPCPU: ("cpu", 15),
    fieldmaps.SUBSYS_TOPPGCPU: ("cpu", 10),   # ref top-10 PG CPU
    fieldmaps.SUBSYS_TOPRSS: ("rssmb", 8),
    fieldmaps.SUBSYS_TOPDELAY: ("cpudelms", 15),
    fieldmaps.SUBSYS_TOPFORK: ("forks", 15),
}


def columns_for(cfg: EngineCfg, st: AggState, subsys: str, names=None,
                dep=None, svcreg=None, aux=None, obs=None):
    """Resolve a subsystem to its (cols, base_mask) column source —
    the ONE dispatch over aux providers ≻ host-side registries ≻
    dep-graph views ≻ device-slab readbacks. Shared by query execution
    and realtime alertdef evaluation so a subsystem added to one is
    automatically visible to the other. ``obs`` (a runtime) lets the
    dep-graph views time and count their builds."""
    if aux is not None and subsys in aux:
        return aux[subsys]()
    if subsys in _SVCREG_COLUMNS_OF:
        return _SVCREG_COLUMNS_OF[subsys](cfg, st, names=names,
                                          svcreg=svcreg)
    if subsys in _DEP_COLUMNS_OF:
        return _DEP_COLUMNS_OF[subsys](cfg, st, names=names, dep=dep,
                                       obs=obs)
    return _COLUMNS_OF[subsys](cfg, st, names=names)


def serverstatus_columns(rt, tick: int, nhosts: int, nsvc: float):
    """serverstatus subsystem (ref madhavastatus): one-row self status
    — shared by both runtimes and the snapshot tier, which differ only
    in where ``tick`` / ``nhosts`` / ``nsvc`` are read from. The event
    counters are the exact host-side ints (the () f32 device scalars
    lose increments past ~2^24 events); ``platform`` / ``devicekind`` /
    ``ndevices`` are the backend jax took, as jax reports it."""
    from gyeeta_tpu import version as V
    from gyeeta_tpu.obs.xlamon import device_info

    c = rt.stats.counters
    dev = device_info()
    obj = lambda v: np.array([v], object)             # noqa: E731
    num = lambda v: np.array([float(v)], np.float64)  # noqa: E731
    cols = {
        "uptime": num(rt._clock() - rt._t_started),
        "tick": num(tick),
        "nhosts": num(nhosts),
        "nsvc": num(nsvc),
        "connevents": num(c.get("conn_events", 0)),
        "respevents": num(c.get("resp_events", 0)),
        "queries": num(c.get("queries", 0)),
        "alertsfired": num(rt.alerts.stats.get("nfired", 0)),
        "wirever": num(V.CURR_WIRE_VERSION),
        "version": obj(V.__version__),
        "platform": obj(dev["platform"]),
        "devicekind": obj(dev["devicekind"]),
        "ndevices": num(dev["ndevices"]),
    }
    return cols, np.ones(1, bool)


# process-local subsystems answered by the runtime itself (no engine
# columns): self-metrics readback + Prometheus exposition. Shared by
# Runtime and ShardedRuntime so the two surfaces cannot drift.
LOCAL_SUBSYS = ("selfstats", "metrics")


def local_response(rt, req: dict, snapshot=None):
    """Answer a process-local subsystem for a runtime-like object
    (``.stats``/``.alerts``, optional ``.spans`` ring, and
    ``.engine_health()`` for the batched device readback), or None
    when ``req`` targets an engine subsystem.

    ``snapshot`` (an ``EngineSnapshot``) selects the snapshot-serving
    path: the scrape renders the gauges the last tick's health pass
    already refreshed instead of touching live device state — a
    /metrics scrape fleet can no longer stall the fold."""
    subsys = req.get("subsys")
    if subsys == "selfstats":
        from gyeeta_tpu.utils.selfstats import selfstats_response
        return selfstats_response(rt.stats, rt.alerts,
                                  spans=getattr(rt, "spans", None))
    if subsys == "metrics":
        from gyeeta_tpu.obs import prom
        if snapshot is None:
            # strong path: fold staged records + refresh the engine-
            # health gauges so the scrape sees current device state
            # (one batched transfer)
            rt.flush()
            rt.engine_health()
        else:
            # snapshot path: no flush, no device readback — refresh
            # only the snapshot-freshness gauges (the tracked-staleness
            # surface: alert when age exceeds ~3x the tick interval)
            rt.stats.gauge("snapshot_age_seconds", max(
                0.0, rt._clock() - snapshot.published_at))
            rt.stats.gauge("snapshot_tick", float(snapshot.tick))
        return prom.metrics_response(rt.stats, rt.alerts)
    return None


def execute(cfg: EngineCfg, st: AggState, opts: QueryOptions,
            names=None, dep=None, columns_fn=None, svcreg=None,
            aux=None) -> dict:
    """Run one point-in-time query → {"recs": [...], "nrecs": N}.

    ``columns_fn(subsys) -> (cols, base_mask)`` overrides the column
    source — the sharded runtime injects gathered/merged columns here so
    filter/sort/aggregation/projection run identically on one shard or a
    whole mesh (the multi-madhava scatter the Node webserver performs,
    ``server/gy_mnodehandle.cc:203``).

    ``aux`` maps extra subsystem names to zero-arg column providers —
    host-side registries (hostinfo, cgroupstate) and alert-manager views
    (alerts/alertdef/silences/inhibits) plug in here without this module
    importing them.
    """
    if opts.subsys not in fieldmaps.FIELDS_OF_SUBSYS:
        raise ValueError(f"unknown subsystem {opts.subsys!r}")
    if columns_fn is None and not any(
            opts.subsys in m for m in (_COLUMNS_OF, _DEP_COLUMNS_OF,
                                       _SVCREG_COLUMNS_OF, aux or {})):
        raise ValueError(f"unknown subsystem {opts.subsys!r}")
    preset = _TOP_PRESETS.get(opts.subsys)
    if preset is not None and opts.sortcol is None and not opts.aggr:
        opts = opts._replace(sortcol=preset[0],
                             maxrecs=min(opts.maxrecs, preset[1]))
    if columns_fn is not None:
        cols, base_mask = columns_fn(opts.subsys)
    else:
        cols, base_mask = columns_for(cfg, st, opts.subsys, names=names,
                                      dep=dep, svcreg=svcreg, aux=aux)
    tree = criteria.parse(opts.filter) if opts.filter else None
    mask = base_mask & criteria.evaluate(tree, cols, opts.subsys)
    idx = np.nonzero(mask)[0]

    if opts.aggr:
        from gyeeta_tpu.query import aggr as A

        if opts.groupby and "time" in opts.groupby:
            raise ValueError("groupby 'time' is historical-only")
        specs = [A.parse_aggr(s, opts.subsys) for s in opts.aggr]
        gb = A.parse_groupby(opts.groupby, opts.subsys)
        fmap = fieldmaps.field_map(opts.subsys)
        if opts.sortcol and opts.sortcol not in (
                tuple(s.alias for s in specs) + gb):
            raise ValueError(
                f"sortcol {opts.sortcol!r} must be a groupby field "
                f"or aggregation alias")
        recs, ngroups = A.aggregate_columns(
            cols, idx, specs, gb, fmap, sortcol=opts.sortcol,
            sortdesc=opts.sortdesc, maxrecs=opts.maxrecs)
        return {"recs": recs, "nrecs": len(recs), "ngroups": ngroups}

    if opts.sortcol:
        fmap = fieldmaps.field_map(opts.subsys)
        fd = fmap.get(opts.sortcol)
        if fd is None:
            raise ValueError(f"unknown sort column {opts.sortcol!r}")
        key = np.asarray(cols[fd.col])[idx]
        order = np.argsort(key, kind="stable")
        idx = idx[order[::-1] if opts.sortdesc else order]
    idx = idx[: opts.maxrecs]

    fmap = fieldmaps.field_map(opts.subsys)
    want = opts.columns or tuple(fmap)
    unknown = [c for c in want if c not in fmap]
    if unknown:
        raise ValueError(f"unknown columns {unknown}")
    # late materialization: project only the RESULT rows — lazy column
    # groups (svcstate quantiles, hex ids, name resolution) compute
    # over len(idx) rows, not capacity (VERDICT r4 #6)
    from gyeeta_tpu.query.lazycols import LazyCols
    colnames = [fmap[c].col for c in want if fmap[c].col in cols]
    if isinstance(cols, LazyCols):
        sliced = cols.rows_many(colnames, idx)
        recs = [fieldmaps.row_to_json(
            opts.subsys, {c: sliced[c][j] for c in colnames})
            for j in range(len(idx))]
    else:
        recs = [fieldmaps.row_to_json(
            opts.subsys, {c: cols[c][i] for c in colnames})
            for i in idx]
    return {"recs": recs, "nrecs": len(recs),
            "ntotal": int(base_mask.sum())}


def query_json(cfg: EngineCfg, st: AggState, req: dict,
               names=None, dep=None, svcreg=None, aux=None) -> dict:
    """JSON-envelope entry point (the NM-conn QUERY_CMD analogue)."""
    return execute(cfg, st, QueryOptions.from_json(req), names=names,
                   dep=dep, svcreg=svcreg, aux=aux)
