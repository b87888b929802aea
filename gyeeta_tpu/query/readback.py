"""Point-in-time readbacks of live AggState (the ``web_curr_*`` analogue).

Each snapshot function is a single jitted device computation returning a
dense column dict over service rows (or hosts / flows); the host then
filters/serializes. This is the freshness-critical path of the north star
(<1s p99 query freshness): no DB, no RCU walk — a readback of sketch
tensors (ref: live-path triads ``server/gy_mnodehandle.cc:798``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from gyeeta_tpu.engine import table
from gyeeta_tpu.engine.aggstate import (
    AggState, EngineCfg, CTR_BYTES_SENT, CTR_BYTES_RCVD, CTR_NCONN_CLOSED,
    CTR_DUR_SUM_US,
)
from gyeeta_tpu.sketch import countmin, flat, hyperloglog as hll, \
    loghist, tdigest, topk, windows

DEFAULT_QS = (0.25, 0.5, 0.95, 0.99)


@partial(jax.jit, static_argnums=(0, 2))
def svc_snapshot(cfg: EngineCfg, st: AggState, level: int = 0):
    """Per-service live snapshot at a window level (0=5min, 1=5d, 2=all).

    Returns dense (S,) columns; row validity in ``live``. Quantiles from the
    windowed loghist (the bulk path); all-time digest quantiles alongside
    (the high-accuracy path).
    """
    live = table.live_mask(st.tbl)
    resp_hist = windows.read(st.resp_win, level)
    ctr = windows.read(st.ctr_win, level)
    qs = jnp.asarray(DEFAULT_QS, jnp.float32)
    resp_q_us = loghist.quantiles(resp_hist, cfg.resp_spec, qs)
    td_q_us = tdigest.quantiles_entities(st.svc_td, qs)
    nresp = loghist.counts_total(resp_hist)
    elapsed = jnp.maximum(st.resp_win.tick.astype(jnp.float32), 1.0)
    if level < len(cfg.levels):
        lv = cfg.levels[level] if level >= 0 else None
        span_ticks = 1.0 if lv is None else float(lv.stride_ticks * lv.nslots)
        # before the window fills, the data only covers `elapsed` ticks —
        # dividing by the full span would underreport rates until then
        span_sec = jnp.minimum(elapsed, span_ticks) * 5.0
    else:
        # all-time: elapsed base ticks × 5 s (dynamic, min one tick)
        span_sec = elapsed * 5.0
    return {
        "glob_id_hi": st.tbl.key_hi,
        "glob_id_lo": st.tbl.key_lo,
        "live": live,
        "nresp": nresp,
        "qps": nresp / span_sec,
        "resp_p25_us": resp_q_us[:, 0],
        "resp_p50_us": resp_q_us[:, 1],
        "resp_p95_us": resp_q_us[:, 2],
        "resp_p99_us": resp_q_us[:, 3],
        "td_p50_us": td_q_us[:, 1],
        "td_p95_us": td_q_us[:, 2],
        "td_p99_us": td_q_us[:, 3],
        "bytes_sent": ctr[:, CTR_BYTES_SENT],
        "bytes_rcvd": ctr[:, CTR_BYTES_RCVD],
        "nconn_closed": ctr[:, CTR_NCONN_CLOSED],
        "mean_conn_dur_us": ctr[:, CTR_DUR_SUM_US]
        / jnp.maximum(ctr[:, CTR_NCONN_CLOSED], 1.0),
        "distinct_clients": hll.estimate(hll.rows(st.svc_hll,
                                                  cfg.hll_p_svc)),
        "stats": st.svc_stats,
    }


# ------------------------------------------------ grouped svcstate readback
# The monolithic svcstate_snapshot reads EVERY window's (S, B)
# histograms per call — ~2 s at the 65k north-star geometry on one CPU
# core (VERDICT r4 weak #4). Queries rarely reference every group, so
# the query path reads column GROUPS on demand (cached per state
# version) and computes projection-only groups over just the result
# rows. svcstate_snapshot stays for whole-fleet consumers (history
# snapshots at capacity, scale artifacts).

_QS3 = (0.5, 0.95, 0.99)


@partial(jax.jit, static_argnums=(0,))
def svcstate_base(cfg: EngineCfg, st: AggState):
    """Cheap gauges: ids, liveness, classification, stats panel — no
    histogram/HLL sweeps."""
    return {
        "glob_id_hi": st.tbl.key_hi,
        "glob_id_lo": st.tbl.key_lo,
        "live": table.live_mask(st.tbl),
        "state": st.svc_state,
        "issue": st.svc_issue,
        "hostid": st.svc_host,
        "stats": st.svc_stats,
    }


@partial(jax.jit, static_argnums=(0,))
def svcstate_vol(cfg: EngineCfg, st: AggState):
    """Query volume from the current 5s slab (one (S, B) pass)."""
    from gyeeta_tpu.ingest.decode import STAT_NQRYS

    nqrys = jnp.maximum(
        loghist.counts_total(windows.cur_rows(st.resp_win)),
        st.svc_stats[:, STAT_NQRYS])
    return {"nqry5s": nqrys, "qps5s": nqrys / 5.0}


@partial(jax.jit, static_argnums=(0,))
def svcstate_cli(cfg: EngineCfg, st: AggState):
    return {"nclients": hll.estimate(hll.rows(st.svc_hll, cfg.hll_p_svc))}


@partial(jax.jit, static_argnums=(0, 2))
def svcstate_qlevel(cfg: EngineCfg, st: AggState, level: int):
    """Latency columns for ONE window level (full capacity)."""
    qs = jnp.asarray(_QS3, jnp.float32)
    h = windows.read(st.resp_win, level)
    q = loghist.quantiles(h, cfg.resp_spec, qs)
    if level == -1:
        return {"resp5s_us": loghist.mean(h, cfg.resp_spec),
                "p95resp5s_us": q[:, 1], "p99resp5s_us": q[:, 2]}
    if level == 0:
        return {"p95resp5m_us": q[:, 1]}
    return {"p50resp5d_us": q[:, 0], "p95resp5d_us": q[:, 1]}


@partial(jax.jit, static_argnums=(0, 3))
def svcstate_qlevel_rows(cfg: EngineCfg, st: AggState, idx, level: int):
    """Latency columns for one level over just rows ``idx`` — the
    row-sliced projection path: the window total is gathered BEFORE
    the (ring + cur) add, so cost scales with len(idx), not capacity.
    ``idx`` is a padded fixed-size int32 array (see api._pad_idx)."""
    qs = jnp.asarray(_QS3, jnp.float32)
    h = flat.take_rows(st.resp_win.cur, idx, cfg.resp_spec.nbuckets)
    if 0 <= level < len(st.resp_win.totals):
        h = st.resp_win.totals[level][idx] + h
    elif level >= len(st.resp_win.totals):
        h = st.resp_win.alltime[idx] + h
    q = loghist.quantiles(h, cfg.resp_spec, qs)
    if level == -1:
        return {"resp5s_us": loghist.mean(h, cfg.resp_spec),
                "p95resp5s_us": q[:, 1], "p99resp5s_us": q[:, 2]}
    if level == 0:
        return {"p95resp5m_us": q[:, 1]}
    return {"p50resp5d_us": q[:, 0], "p95resp5d_us": q[:, 1]}


@partial(jax.jit, static_argnums=(0,))
def svcstate_vol_rows(cfg: EngineCfg, st: AggState, idx):
    from gyeeta_tpu.ingest.decode import STAT_NQRYS

    nqrys = jnp.maximum(
        loghist.counts_total(flat.take_rows(st.resp_win.cur, idx,
                                            cfg.resp_spec.nbuckets)),
        st.svc_stats[idx, STAT_NQRYS])
    return {"nqry5s": nqrys, "qps5s": nqrys / 5.0}


@partial(jax.jit, static_argnums=(0,))
def svcstate_cli_rows(cfg: EngineCfg, st: AggState, idx):
    return {"nclients": hll.estimate(
        hll.take_rows(st.svc_hll, cfg.hll_p_svc, idx))}


@partial(jax.jit, static_argnums=(0,))
def svcstate_snapshot(cfg: EngineCfg, st: AggState):
    """The svcstate-subsystem readback: current 5s window + gauges + the
    semantic classification — the ``web_curr_svcstate`` analogue
    (``server/gy_mnodehandle.cc``), one device program for the fleet."""
    spec = cfg.resp_spec
    qs = jnp.asarray((0.5, 0.95, 0.99), jnp.float32)
    h5 = windows.cur_rows(st.resp_win)
    h5m = windows.read(st.resp_win, 0)
    h5d = windows.read(st.resp_win, 1)
    q5 = loghist.quantiles(h5, spec, qs)
    q5m = loghist.quantiles(h5m, spec, qs)
    q5d = loghist.quantiles(h5d, spec, qs)
    from gyeeta_tpu.ingest.decode import STAT_NQRYS
    nqrys = jnp.maximum(loghist.counts_total(h5),
                        st.svc_stats[:, STAT_NQRYS])
    return {
        "glob_id_hi": st.tbl.key_hi,
        "glob_id_lo": st.tbl.key_lo,
        "live": table.live_mask(st.tbl),
        "nqry5s": nqrys,
        "qps5s": nqrys / 5.0,
        "resp5s_us": loghist.mean(h5, spec),
        "p95resp5s_us": q5[:, 1],
        "p99resp5s_us": q5[:, 2],
        "p95resp5m_us": q5m[:, 1],
        "p50resp5d_us": q5d[:, 0],
        "p95resp5d_us": q5d[:, 1],
        "state": st.svc_state,
        "issue": st.svc_issue,
        "hostid": st.svc_host,
        "nclients": hll.estimate(hll.rows(st.svc_hll, cfg.hll_p_svc)),
        "stats": st.svc_stats,
    }


@partial(jax.jit, static_argnums=(0, 2))
def flow_snapshot(cfg: EngineCfg, st: AggState, k: int = 64):
    """Heavy-hitter flows by bytes + global distinct-endpoint estimate."""
    f_hi, f_lo, f_bytes = topk.query(st.flow_topk, k)
    return {
        "flow_hi": f_hi,
        "flow_lo": f_lo,
        "flow_bytes": f_bytes,
        "evicted_bytes": st.flow_topk.evicted,
        "distinct_flows": hll.estimate(st.glob_hll),
        "total_bytes": countmin.total(st.cms),
    }


@partial(jax.jit, static_argnums=(0,))
def host_snapshot(cfg: EngineCfg, st: AggState):
    return {"panel": st.host_panel}


@partial(jax.jit, static_argnums=(0,))
def task_snapshot(cfg: EngineCfg, st: AggState):
    """Per-process-group live snapshot (the ``web_curr_aggrtaskstate``
    analogue): gauges + agent classification + learned CPU baseline."""
    cpu_p95 = loghist.quantiles(
        st.task_cpu_hist, cfg.taskcpu_spec,
        jnp.asarray([0.95], jnp.float32))[:, 0]
    return {
        "key_hi": st.task_tbl.key_hi,
        "key_lo": st.task_tbl.key_lo,
        "live": table.live_mask(st.task_tbl),
        "stats": st.task_stats,
        "state": st.task_state,
        "issue": st.task_issue,
        "hostid": st.task_host,
        "comm_hi": st.task_comm_hi,
        "comm_lo": st.task_comm_lo,
        "rel_hi": st.task_rel_hi,
        "rel_lo": st.task_rel_lo,
        "cpu_p95": cpu_p95,
    }


def edge_cols(es) -> dict:
    """An ``EdgeSet`` as the dependency views' device-side columns."""
    return {
        "e_live": table.live_mask(es.tbl),
        "e_cli_hi": es.cli_hi, "e_cli_lo": es.cli_lo,
        "e_cli_svc": es.cli_svc,
        "e_ser_hi": es.ser_hi, "e_ser_lo": es.ser_lo,
        "e_nconn": es.nconn, "e_bytes": es.byts,
        "e_dropped": es.n_dropped,
    }


@jax.jit
def dep_edges_snapshot(dep):
    """One shard's dependency-edge columns (svcdependency), straight
    from its edge slab: one device program, no clustering work (that is
    :func:`dep_mesh_snapshot`) and no merge (that is the mesh's)."""
    from gyeeta_tpu.parallel import depgraph as dg

    return edge_cols(dg.edges_local(dep))


@partial(jax.jit, static_argnums=(1,))
def dep_mesh_snapshot(dep, n_iters: int = 16):
    """Mesh-cluster labels over the svc→svc edges (svcmesh): the
    ``coalesce_svc_mesh_clusters`` readout
    (``server/gy_shconnhdlr.cc:5198``). The node table holds up to two
    distinct endpoints per edge, so it is sized 2× the edge slab."""
    from gyeeta_tpu.parallel import depgraph as dg

    es = dg.edges_local(dep)
    node_capacity = 2 * es.nconn.shape[0]
    ntbl, labels, sizes = dg.mesh_clusters(es, node_capacity, n_iters)
    return {
        "n_hi": ntbl.key_hi, "n_lo": ntbl.key_lo,
        "n_mask": table.live_mask(ntbl),
        "n_label": labels, "n_size": sizes,
    }


@partial(jax.jit, static_argnums=(0,))
def trace_snapshot(cfg: EngineCfg, st: AggState):
    """Per-(svc, api) live snapshot: counters + latency percentiles
    (the ``web_curr_tracereq`` analogue; north-star config #5)."""
    qs = jnp.asarray((0.5, 0.95, 0.99), jnp.float32)
    q = loghist.quantiles(st.api_resp_hist, cfg.apiresp_spec, qs)
    return {
        "live": table.live_mask(st.api_tbl),
        "svc_hi": st.api_svc_hi, "svc_lo": st.api_svc_lo,
        "api_hi": st.api_id_hi, "api_lo": st.api_id_lo,
        "proto": st.api_proto,
        "ctr": st.api_ctr,
        "p50_us": q[:, 0], "p95_us": q[:, 1], "p99_us": q[:, 2],
        "hostid": st.api_host,
    }


def svc_rows_to_host(cfg: EngineCfg, snap: dict) -> list[dict]:
    """Device snapshot → list of per-service dicts (live rows only).

    One device→host transfer per column (hoisted), then pure-python row
    assembly — this is on the <1s-freshness query path.
    """
    host = {k: np.asarray(v) for k, v in snap.items()}
    live = host["live"]
    idx = np.nonzero(live)[0]
    gid = (host["glob_id_hi"].astype(np.uint64) << np.uint64(32)) \
        | host["glob_id_lo"].astype(np.uint64)
    scalar_cols = [k for k, v in host.items()
                   if k not in ("glob_id_hi", "glob_id_lo", "live", "stats")
                   and v.ndim == 1]
    out = []
    for i in idx:
        row = {"glob_id": int(gid[i])}
        for k in scalar_cols:
            row[k] = float(host[k][i])
        out.append(row)
    return out
