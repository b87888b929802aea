"""Jitted microbatch update steps — the hot loop of the framework.

Replaces the reference's per-event handler chain (madhava L1 dispatch →
L2 ``partha_*`` RCU walks, ``server/gy_mconnhdlr.cc:2521-3490,4700``) with
four batched tensor folds, each one traced once and fused by XLA:

- ``ingest_conn``   — TCP_CONN flow records → per-svc counters, per-svc
  distinct-client HLL, global HLL, CMS bytes, heavy-hitter top-K
  (the ``partha_tcp_conn_info``/``add_tcp_conn_cli`` analogue)
- ``ingest_resp``   — raw response samples → per-svc windowed loghist +
  per-svc t-digest (replacing agent-side ``resp_hist_`` updates,
  ``common/gy_socket_stat.cc:1554``)
- ``ingest_listener`` / ``ingest_host`` — 5s state sweeps → gauge panels
  (the ``partha_listener_state`` hot loop, ``gy_mconnhdlr.cc:10993``)
- ``tick_5s``       — closes the 5s window slab (scheduler cadence,
  ``common/gy_scheduler.h`` 5s domain)

All functions are pure ``state, batch → state`` and donate-friendly. Batches
are the columnar pytrees from ``ingest/decode.py`` (device arrays inside
jit). `fold_step` is the fused flagship step used by bench + __graft_entry__.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from gyeeta_tpu.engine import table
from gyeeta_tpu.engine.aggstate import (
    AggState, EngineCfg, CTR_BYTES_SENT, CTR_BYTES_RCVD, CTR_NCONN_CLOSED,
    CTR_DUR_SUM_US,
)
from gyeeta_tpu.sketch import countmin, hyperloglog as hll, invertible, \
    loghist, tdigest, topk, windows


def ingest_conn(cfg: EngineCfg, st: AggState, cb) -> AggState:
    """Fold a ConnBatch. cb fields are (B,) device arrays.

    Only accept-observed (server-side) lanes touch the per-service slab
    — a client-observed record names a REMOTE service and must not
    materialize (or re-home) its row; the reference likewise keeps
    client-half conns in remote/unknown maps, not the listener table
    (``server/gy_mconnhdlr.h:614-632``). The global HLL sees every
    valid lane (it dedups by flow key, so dual observation is safe);
    the additive CMS / flow top-K fold accept-observed lanes only, so a
    dual-observed flow's bytes are never counted twice. The dep graph
    dedups its halves via scatter-max.
    """
    # each component folds inside a ``jax.named_scope`` (metadata only:
    # the compiled program does the same work) so that a device trace
    # can put an op down to its component
    scope = jax.named_scope
    valid = cb.valid
    svc_side = valid & cb.is_accept
    with scope("conn.upsert"):
        tbl, rows, any_new, probe = table.upsert_fast2(
            st.tbl, cb.svc_hi, cb.svc_lo, svc_side)
    ok = svc_side & (rows >= 0)
    rowz = jnp.where(ok, rows, 0)
    S = cfg.svc_capacity

    # per-svc windowed counters: ONE row scatter-add of a (B, NCTR)
    # update block (columns in CTR_* order). Four per-column scatters
    # cost 4x the index-resolution work on both CPU and TPU (measured
    # 6.3 ms → 1.9 ms per 32k-lane dispatch on one core); per-slot
    # accumulation order per column is still lane order, so the result
    # is bit-identical to the per-column form.
    lanes = jnp.where(ok, rowz, S)  # S = dropped (mode=drop)
    with scope("conn.ctr"):
        upd = jnp.stack(
            [cb.bytes_sent, cb.bytes_rcvd,
             cb.is_close.astype(jnp.float32), cb.duration_us], axis=1)
        cur = st.ctr_win.cur.at[lanes].add(upd, mode="drop")
    ctr_win = st.ctr_win._replace(cur=cur)

    # the service→host homing column only changes when a NEW row is
    # claimed (existing rows re-write the value they already hold;
    # rehoming re-announces through the listener sweep, which upserts)
    # — so the scatter-set rides the upsert's own miss signal and the
    # all-hit steady state pays nothing for it
    with scope("conn.upsert"):
        svc_host = jax.lax.cond(
            any_new,
            lambda col: col.at[lanes].set(cb.host_id, mode="drop"),
            lambda col: col, st.svc_host)
    with scope("conn.svc_hll"):
        svc_hll = hll.update_entities(st.svc_hll, rowz, cb.cli_hi,
                                      cb.cli_lo, valid=ok)
    with scope("conn.glob_hll"):
        glob_hll = hll.update(st.glob_hll, cb.flow_hi, cb.flow_lo,
                              valid=valid)
    # byte accounting takes the ACCEPT side only (valid=svc_side below
    # already masks client-observed lanes): a dual-observed flow would
    # otherwise count twice into the additive CMS/top-K. Server-side
    # listener accounting is also where the reference attaches traffic
    # stats.
    tot_bytes = cb.bytes_sent + cb.bytes_rcvd
    with scope("conn.cms"):
        cms = countmin.update(st.cms, cb.flow_hi, cb.flow_lo, tot_bytes,
                              valid=svc_side)
    # sketch-assisted candidate compaction (CMS+heap, the shape of
    # the FPGA sketch-acceleration papers): the CMS — queried AFTER
    # this batch folded into it — upper-bounds every flow's
    # cumulative mass, so only the topk_budget best lanes enter the
    # grouping sort. One hash row is enough for a safe-side
    # ranking signal (sketch/countmin.py:upper_bound).
    est = hot = sel = None
    n = cb.flow_hi.shape[0]
    with scope("conn.topk_select"):
        if 0 < cfg.topk_budget:
            est = countmin.upper_bound(cms, cb.flow_hi, cb.flow_lo)
        # priority-aware hot admission (PSketch): on top of the
        # budget's relative ranking, a lane enters the exact top-K
        # merge only when its estimate clears an absolute floor of the
        # total folded mass — colder lanes keep their mass in the CMS
        # and their excluded mass lands in ``evicted`` (the bound stays
        # honest because a floored lane scores −1, same as padding, and
        # unselected valid mass is always accounted).
        if est is not None and cfg.hh_hot_frac > 0:
            thresh = jnp.float32(cfg.hh_hot_frac) * countmin.total(cms)
            hot = est >= thresh
        if est is not None and 0 < cfg.topk_budget < n:
            # ONE shared candidate selection feeds BOTH heavy-hitter
            # structures (the exact merge's grouping sort and the
            # invertible bucket-ownership writes): score = estimate on
            # admitted lanes, −1 on padding/cold lanes. Mass excluded
            # by the selection is charged to ``evicted`` here, so the
            # undercount bound stays exactly as honest as the in-update
            # compaction it replaces.
            score = jnp.where(svc_side, est.astype(jnp.float32), -1.0)
            if hot is not None:
                score = jnp.where(hot, score, -1.0)
            _, sel = jax.lax.top_k(score, cfg.topk_budget)
            sel_ok = score[sel] >= 0.0
            c_hi, c_lo = cb.flow_hi[sel], cb.flow_lo[sel]
            c_vals = jnp.where(sel_ok,
                               tot_bytes[sel].astype(jnp.float32), 0.0)
            c_prio = jnp.where(sel_ok, est[sel].astype(jnp.float32), 0.0)
            extra_evicted = (jnp.sum(jnp.where(svc_side, tot_bytes, 0.0))
                             - jnp.sum(c_vals))
    with scope("conn.topk"):
        if sel is not None:
            ftk = st.flow_topk._replace(
                evicted=st.flow_topk.evicted + extra_evicted)
            flow_topk = topk.update(ftk, c_hi, c_lo, c_vals,
                                    valid=sel_ok)
        else:
            flow_topk = topk.update(
                st.flow_topk, cb.flow_hi, cb.flow_lo, tot_bytes,
                valid=svc_side, est=est, budget=cfg.topk_budget)
    with scope("conn.inv"):
        if cfg.hh_width <= 0:
            inv = st.inv
        else:
            # invertible candidate buckets (sketch/invertible.py): the
            # selected (admitted) lanes compete for bucket ownership
            # with their estimate as priority — per-tick decoding
            # recovers heavy keys straight from this state, no
            # candidate list. Without a top-K budget (no estimate)
            # every accept-side lane competes with its own mass.
            if sel is not None:
                inv = invertible.update(st.inv, c_hi, c_lo, c_prio,
                                        valid=sel_ok)
            else:
                inv_prio = est if est is not None else tot_bytes
                inv = invertible.update(st.inv, cb.flow_hi, cb.flow_lo,
                                        inv_prio, valid=svc_side,
                                        budget=cfg.topk_budget)
            if hot is not None:
                inv = inv._replace(n_hot=inv.n_hot + jnp.sum(
                    svc_side & hot).astype(jnp.float32))
    return st._replace(
        tbl=tbl, ctr_win=ctr_win, svc_host=svc_host, svc_hll=svc_hll,
        glob_hll=glob_hll, cms=cms, flow_topk=flow_topk, inv=inv,
        n_conn=st.n_conn + jnp.sum(valid).astype(jnp.float32),
        n_probe=st.n_probe + probe,
    )


def ingest_resp(cfg: EngineCfg, st: AggState, rb) -> AggState:
    """Fold one RespBatch of raw (glob_id, resp_us) samples — the
    single-microbatch path (partial slabs at cadence/query boundaries,
    sharded per-batch folds). Identical semantics to the hot loop
    (``ingest_resp_bulk``): digest samples STAGE; compression happens
    via the pressure-triggered ``td_flush_partial``/``td_drain``. An
    earlier inline route-and-compress here vmapped the compression
    sort over every entity per call — O(capacity), 1.1 s per
    microbatch at the 65k north-star geometry (the r4 fold collapse).

    Lookup-only: a response sample never CREATES a service row —
    services enter the table via conn/listener streams (the reference
    resolves resp events against listener_tbl_ and drops misses,
    ``gy_socket_stat.cc`` handle_tcp_resp_event). Unknowns are counted,
    not folded, so all paths agree regardless of batching.
    """
    return ingest_resp_flat(cfg, st, rb)


def td_flush(cfg: EngineCfg, st: AggState) -> AggState:
    """Compress the staged digest samples into the per-svc digests (one
    vmapped pass) and clear the stage."""
    svc_td, stage, stage_n = tdigest.flush_staged(
        st.svc_td, st.td_stage, st.td_stage_n)
    return st._replace(svc_td=svc_td, td_stage=stage, td_stage_n=stage_n)


def td_flush_partial(cfg: EngineCfg, st: AggState) -> AggState:
    """Compress the ``cfg.td_flush_m`` fullest digest stages and clear
    them — the hot-loop flush. O(m) per call regardless of capacity;
    the runtime triggers it from a host-side pressure check instead of
    an in-graph ``lax.cond`` (a cond carrying the 128 MB stage forced
    whole-buffer copies every dispatch — measured 110 ms/dispatch at
    65k capacity even when the branch was NOT taken)."""
    svc_td, stage, stage_n = tdigest.flush_staged_topm(
        st.svc_td, st.td_stage, st.td_stage_n, cfg.td_flush_m)
    return st._replace(svc_td=svc_td, td_stage=stage, td_stage_n=stage_n)


def stage_pressure(st: AggState):
    """Max staged-sample count over entities — the host-side flush
    trigger signal (a () int32; readback is one scalar)."""
    return jnp.max(st.td_stage_n)


def ingest_resp_bulk(cfg: EngineCfg, st: AggState, rbs) -> AggState:
    """Flatten a (K, B) stacked resp batch and fold it in one pass."""
    flat = jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), rbs)
    return ingest_resp_flat(cfg, st, flat)


def ingest_resp_flat(cfg: EngineCfg, st: AggState, flat) -> AggState:
    """Process response samples in ONE vectorized pass over flat lanes
    — the fold_many epilogue and the sharded per-shard fold.

    Replaces per-microbatch ``ingest_resp`` calls: one table lookup,
    one loghist scatter-add, one digest staging route (compression
    amortizes via pressure-triggered ``td_flush_partial``). Unknown
    services (never
    announced by conn/listener streams) drop and are counted — the
    reference likewise only folds response stats into *known* listeners
    (``gy_socket_stat.cc`` resp events resolve against listener_tbl_).
    """
    scope = jax.named_scope
    valid = flat.valid
    with scope("resp.lookup"):
        rows, probe = table.lookup_counted(st.tbl, flat.svc_hi,
                                           flat.svc_lo, valid)
    ok = valid & (rows >= 0)
    n_unknown = jnp.sum(valid & (rows < 0)).astype(jnp.float32)
    rowz = jnp.where(ok, rows, 0)
    with scope("resp.loghist"):
        cur = loghist.update_entities(
            st.resp_win.cur, cfg.resp_spec, rowz, flat.resp_us,
            valid=ok)
    resp_win = st.resp_win._replace(cur=cur)
    # duty-cycled digest sampling (the reference samples response
    # events at the source, RESP_SAMPLING ~50%, common/gy_ebpf.h:29):
    # the loghist above folds EVERY sample (lossless counts); the
    # digest — a tail-quantile estimator — takes a strided 1-in-N
    # subsample, shrinking the routing sort and flush cadence N×.
    # Static stride keeps shapes fixed; lane order is arrival order,
    # uncorrelated with service identity.
    k = max(1, cfg.td_sample_stride)
    with scope("resp.td_stage"):
        stage, stage_n, n_over = tdigest.stage_samples(
            st.td_stage, st.td_stage_n, jnp.where(ok, rows, -1)[::k],
            flat.resp_us[::k])
    return st._replace(
        resp_win=resp_win, td_stage=stage, td_stage_n=stage_n,
        n_resp=st.n_resp + jnp.sum(valid).astype(jnp.float32),
        n_resp_unknown=st.n_resp_unknown + n_unknown,
        n_td_overflow=st.n_td_overflow + n_over.astype(jnp.float32),
        n_probe=st.n_probe + probe,
    )


def ingest_listener(cfg: EngineCfg, st: AggState, lb) -> AggState:
    """Fold a ListenerBatch: gauges + learned QPS/active-conn baselines.

    The baseline histograms are the self-learning signal of the reference
    classifier (qps_hist_/active_conn_hist_, common/gy_socket_stat.h:365):
    every 5s sweep contributes one QPS and one active-conn sample per
    service; the classifier later compares current values against the
    p95/p25 of these histograms.
    """
    from gyeeta_tpu.ingest import decode as D

    valid = lb.valid
    tbl, rows = table.upsert(st.tbl, lb.svc_hi, lb.svc_lo, valid)
    ok = valid & (rows >= 0)
    rowz = jnp.where(ok, rows, 0)
    lanes = jnp.where(ok, rows, cfg.svc_capacity)
    svc_stats = st.svc_stats.at[lanes].set(lb.stats, mode="drop")
    svc_host = st.svc_host.at[lanes].set(lb.host_id, mode="drop")
    qps = lb.stats[:, D.STAT_NQRYS] / 5.0
    qps_hist = loghist.update_entities(
        st.qps_hist, cfg.qps_spec, rowz, qps, valid=ok)
    active_hist = loghist.update_entities(
        st.active_hist, cfg.active_spec, rowz,
        lb.stats[:, D.STAT_NCONNS_ACTIVE], valid=ok)
    return st._replace(tbl=tbl, svc_stats=svc_stats, svc_host=svc_host,
                       qps_hist=qps_hist, active_hist=active_hist)


def ingest_task(cfg: EngineCfg, st: AggState, tb) -> AggState:
    """Fold a TaskBatch (5s process-group sweep, ref MAGGR_TASK updates in
    ``partha_aggr_task_state``): gauges + agent state + learned CPU%%
    baseline + last-seen tick for ageing."""
    valid = tb.valid
    tbl, rows = table.upsert(st.task_tbl, tb.key_hi, tb.key_lo, valid)
    ok = valid & (rows >= 0)
    rowz = jnp.where(ok, rows, 0)
    lanes = jnp.where(ok, rows, cfg.task_capacity)
    stats = st.task_stats.at[lanes].set(tb.stats, mode="drop")
    state = st.task_state.at[lanes].set(tb.state, mode="drop")
    issue = st.task_issue.at[lanes].set(tb.issue, mode="drop")
    host = st.task_host.at[lanes].set(tb.host_id, mode="drop")
    c_hi = st.task_comm_hi.at[lanes].set(
        tb.comm_hi.astype(jnp.uint32), mode="drop")
    c_lo = st.task_comm_lo.at[lanes].set(
        tb.comm_lo.astype(jnp.uint32), mode="drop")
    r_hi = st.task_rel_hi.at[lanes].set(
        tb.rel_hi.astype(jnp.uint32), mode="drop")
    r_lo = st.task_rel_lo.at[lanes].set(
        tb.rel_lo.astype(jnp.uint32), mode="drop")
    from gyeeta_tpu.ingest import decode as D
    cpu_hist = loghist.update_entities(
        st.task_cpu_hist, cfg.taskcpu_spec, rowz,
        tb.stats[:, D.TASK_CPU_PCT], valid=ok)
    last = st.task_last_tick.at[lanes].set(st.resp_win.tick, mode="drop")
    return st._replace(
        task_tbl=tbl, task_stats=stats, task_state=state, task_issue=issue,
        task_host=host, task_comm_hi=c_hi, task_comm_lo=c_lo,
        task_rel_hi=r_hi, task_rel_lo=r_lo, task_cpu_hist=cpu_hist,
        task_last_tick=last)


# api_ctr column indices
APIC_NREQ = 0
APIC_NERR = 1
APIC_BYTES_IN = 2
APIC_BYTES_OUT = 3


def ingest_trace(cfg: EngineCfg, st: AggState, tb) -> AggState:
    """Fold a TraceBatch into the per-(svc, api) slab: counters +
    response-time loghist (the REQ_TRACE_TRAN fan-in aggregation,
    ``gy_comm_proto.h:3288`` — per-API latency sketches, north-star
    config #5).

    Also upserts the SERVICE row: a parsed server-side transaction is
    direct evidence of a live listener (stronger than a resp sample,
    which stays lookup-only) — so trace-only sources (pcap files,
    traced conns without a listener stream) still materialize svcstate
    rows for the trace→resp bridge to land on."""
    svc_tbl, svc_rows = table.upsert_fast(st.tbl, tb.svc_hi, tb.svc_lo,
                                          tb.valid)
    svc_ok = tb.valid & (svc_rows >= 0)
    svc_lanes = jnp.where(svc_ok, svc_rows, cfg.svc_capacity)
    svc_host = st.svc_host.at[svc_lanes].set(tb.host_id, mode="drop")
    # parsed server-side errors accumulate into the svc ser_errors
    # gauge — REAL error counts for trace-observed services (the
    # err-HTTP cheap tier's destination, gy_svc_net_capture.h:286).
    # Hosts with a listener stream overwrite the gauge each 5s sweep
    # (the agent's own count wins); trace-only sources keep the sum.
    from gyeeta_tpu.ingest.decode import STAT_SER_ERRORS
    svc_stats = st.svc_stats.at[svc_lanes, STAT_SER_ERRORS].add(
        jnp.where(svc_ok & tb.is_err, 1.0, 0.0), mode="drop")
    st = st._replace(tbl=svc_tbl, svc_host=svc_host,
                     svc_stats=svc_stats)
    valid = tb.valid
    tbl, rows = table.upsert(st.api_tbl, tb.key_hi, tb.key_lo, valid)
    ok = valid & (rows >= 0)
    rowz = jnp.where(ok, rows, 0)
    A = cfg.api_capacity
    lanes = jnp.where(ok, rows, A)
    set_ = lambda col, v: col.at[lanes].set(v, mode="drop")  # noqa: E731
    ctr = st.api_ctr
    ctr = ctr.at[lanes, APIC_NREQ].add(jnp.where(ok, 1.0, 0.0),
                                       mode="drop")
    ctr = ctr.at[lanes, APIC_NERR].add(
        jnp.where(ok & tb.is_err, 1.0, 0.0), mode="drop")
    ctr = ctr.at[lanes, APIC_BYTES_IN].add(jnp.where(ok, tb.byin, 0.0),
                                           mode="drop")
    ctr = ctr.at[lanes, APIC_BYTES_OUT].add(jnp.where(ok, tb.byout, 0.0),
                                            mode="drop")
    hist = loghist.update_entities(st.api_resp_hist, cfg.apiresp_spec,
                                   rowz, tb.resp_us, valid=ok)
    return st._replace(
        api_tbl=tbl,
        api_svc_hi=set_(st.api_svc_hi, tb.svc_hi.astype(jnp.uint32)),
        api_svc_lo=set_(st.api_svc_lo, tb.svc_lo.astype(jnp.uint32)),
        api_id_hi=set_(st.api_id_hi, tb.api_hi.astype(jnp.uint32)),
        api_id_lo=set_(st.api_id_lo, tb.api_lo.astype(jnp.uint32)),
        api_proto=set_(st.api_proto, tb.proto),
        api_resp_hist=hist, api_ctr=ctr,
        api_host=set_(st.api_host, tb.host_id),
        api_last_tick=set_(st.api_last_tick, st.resp_win.tick),
    )


def age_apis(cfg: EngineCfg, st: AggState, max_age_ticks: int) -> AggState:
    """Tombstone (svc, api) rows unseen for ``max_age_ticks`` ticks."""
    seen = st.api_last_tick >= 0
    stale = seen & (st.resp_win.tick - st.api_last_tick
                    > jnp.int32(max_age_ticks))
    tbl, killed = table.tombstone_rows(st.api_tbl, stale)
    z32 = lambda col: jnp.where(killed, jnp.uint32(0), col)  # noqa: E731
    return st._replace(
        api_tbl=tbl,
        api_svc_hi=z32(st.api_svc_hi), api_svc_lo=z32(st.api_svc_lo),
        api_id_hi=z32(st.api_id_hi), api_id_lo=z32(st.api_id_lo),
        api_proto=jnp.where(killed, 0, st.api_proto),
        api_resp_hist=jnp.where(killed[:, None], 0.0, st.api_resp_hist),
        api_ctr=jnp.where(killed[:, None], 0.0, st.api_ctr),
        api_host=jnp.where(killed, -1, st.api_host),
        api_last_tick=jnp.where(killed, -1, st.api_last_tick),
    )


def ping_tasks(cfg: EngineCfg, st: AggState, pb) -> AggState:
    """Fold a PingBatch (process-group keepalives, the ref
    PING_TASK_AGGR ``gy_comm_proto.h:1384``): refresh ``task_last_tick``
    for rows that EXIST — lookup, never upsert. A quiet long-lived group
    keeps its slot (and its learned CPU baseline) without a stats sweep;
    pings for unknown groups are dropped (the reference asks the partha
    to re-announce instead of fabricating empty rows)."""
    rows = table.lookup(st.task_tbl, pb.key_hi, pb.key_lo, pb.valid)
    lanes = jnp.where(rows >= 0, rows, cfg.task_capacity)
    last = st.task_last_tick.at[lanes].set(st.resp_win.tick, mode="drop")
    return st._replace(task_last_tick=last)


def ingest_delta(cfg: EngineCfg, st: AggState, dep, db, tick):
    """Fold a DeltaBatch (``ingest/decode.py:delta_batch``) — the edge
    pre-aggregation path: agents fold their own conn/resp streams
    locally (``sketch/edgefold.py``) and the wire carries mergeable
    partials instead of raw tuples. Every merge here is the SAME
    monotone operation the raw fold (and the history downsampler)
    applies, so a delta-fed engine reaches the same state the raw-fed
    fold would, up to float-addition order and the declared truncation
    bounds:

    - counters / loghist buckets / CMS mass / dep edges: scatter-add
      of per-sweep sums (counts are exact; float byte sums differ only
      in addition order);
    - HLL registers: scatter-max of the agent's register maxes —
      BIT-IDENTICAL to folding the raw keys;
    - flows: aggregated (key, bytes) lanes feed CMS/top-K/invertible
      exactly like raw lanes, with the agent's truncated residual mass
      charged to the top-K ``evicted`` undercount bound — bound
      honesty survives the edge fold.

    One table upsert per dispatch (the unique-svc section); every
    family then row-resolves with lookups against the updated table.
    Returns ``(state, dep)``.
    """
    from gyeeta_tpu.parallel import depgraph as dg

    S = cfg.svc_capacity
    # ---- ONE upsert over the unique svc keys of the whole dispatch
    tbl, urows, any_new, _ = table.upsert_fast2(
        st.tbl, db.svc_hi, db.svc_lo, db.svc_valid)
    ok_u = db.svc_valid & (urows >= 0)
    lanes_u = jnp.where(ok_u, urows, S)
    # owning-host column rides the upsert's own miss signal (see
    # ingest_conn: existing rows re-write the value they already hold)
    svc_host = jax.lax.cond(
        any_new,
        lambda col: col.at[lanes_u].set(db.svc_host, mode="drop"),
        lambda col: col, st.svc_host)

    # ---- per-svc exact counters (ctr_win order) + event counts
    rc = table.lookup(tbl, db.ctr_hi, db.ctr_lo, db.ctr_valid)
    ok_c = db.ctr_valid & (rc >= 0)
    lanes_c = jnp.where(ok_c, rc, S)
    upd = jnp.where(ok_c[:, None], db.ctr_vals[:, :4],
                    jnp.float32(0.0))
    ctr_win = st.ctr_win._replace(
        cur=st.ctr_win.cur.at[lanes_c].add(upd, mode="drop"))
    n_conn_add = jnp.sum(jnp.where(ok_c, db.ctr_vals[:, 4], 0.0))
    n_resp_add = jnp.sum(jnp.where(ok_c, db.ctr_vals[:, 5], 0.0))

    # ---- per-svc resp loghist bucket counts (exact scatter-add)
    rh = table.lookup(tbl, db.hist_hi, db.hist_lo, db.hist_valid)
    ok_h = db.hist_valid & (rh >= 0)
    roww = jnp.where(ok_h, rh, 0)
    w = jnp.where(ok_h, db.hist_w, 0.0)
    resp_win = st.resp_win._replace(
        cur=st.resp_win.cur.at[roww, db.hist_bucket].add(w))

    # ---- per-svc distinct-client HLL register maxes (scatter-max)
    rs = table.lookup(tbl, db.shll_hi, db.shll_lo, db.shll_valid)
    ok_s = db.shll_valid & (rs >= 0)
    rank_s = jnp.where(ok_s, db.shll_rank, 0)
    svc_hll = st.svc_hll._replace(
        regs=st.svc_hll.regs.at[jnp.where(ok_s, rs, 0),
                                db.shll_reg].max(rank_s))

    # ---- global flow HLL register maxes
    rank_g = jnp.where(db.ghll_valid, db.ghll_rank, 0)
    glob_hll = st.glob_hll._replace(
        regs=st.glob_hll.regs.at[db.ghll_reg].max(rank_g))

    # ---- t-digest stage (pre-strided at the agent — the same duty
    # cycle the raw fold applies; compression stays pressure-driven)
    rt_ = table.lookup(tbl, db.td_hi, db.td_lo, db.td_valid)
    ok_t = db.td_valid & (rt_ >= 0)
    stage, stage_n, n_over = tdigest.stage_samples(
        st.td_stage, st.td_stage_n, jnp.where(ok_t, rt_, -1),
        db.td_val)

    # ---- flow aggregates → CMS, top-K, invertible buckets (with the
    # agent-side truncation residual charged to the undercount bound)
    fv = db.flow_valid
    cms = countmin.update(st.cms, db.flow_hi, db.flow_lo, db.flow_val,
                          valid=fv)
    est = countmin.upper_bound(cms, db.flow_hi, db.flow_lo)
    ftk = st.flow_topk._replace(
        evicted=st.flow_topk.evicted + db.evicted_add[0])
    hot = None
    vhot = fv
    if cfg.hh_hot_frac > 0:
        thresh = jnp.float32(cfg.hh_hot_frac) * countmin.total(cms)
        hot = est >= thresh
        vhot = fv & hot
        # cold valid mass never reaches the exact merge — accounted
        # (the PSketch floor, same semantics as ingest_conn)
        ftk = ftk._replace(evicted=ftk.evicted + jnp.sum(
            jnp.where(fv & ~hot, db.flow_val, 0.0)))
    flow_topk = topk.update(ftk, db.flow_hi, db.flow_lo, db.flow_val,
                            valid=vhot, est=est,
                            budget=cfg.topk_budget)
    if cfg.hh_width <= 0:
        inv = st.inv
    else:
        inv = invertible.update(st.inv, db.flow_hi, db.flow_lo,
                                jnp.where(vhot, est, 0.0), valid=vhot,
                                budget=cfg.topk_budget)
        if hot is not None:
            inv = inv._replace(n_hot=inv.n_hot + jnp.sum(
                fv & hot).astype(jnp.float32))

    # ---- dependency edges (pre-aggregated direct edges)
    dep = dg.fold_edges(dep, db.dep_cli_hi, db.dep_cli_lo,
                        db.dep_cli_svc, db.dep_ser_hi, db.dep_ser_lo,
                        db.dep_bytes, db.dep_valid, tick,
                        nconn=db.dep_nconn)

    st = st._replace(
        tbl=tbl, ctr_win=ctr_win, resp_win=resp_win, svc_host=svc_host,
        svc_hll=svc_hll, glob_hll=glob_hll, td_stage=stage,
        td_stage_n=stage_n, cms=cms, flow_topk=flow_topk, inv=inv,
        n_conn=st.n_conn + n_conn_add,
        n_resp=st.n_resp + n_resp_add,
        n_td_overflow=st.n_td_overflow + n_over.astype(jnp.float32),
    )
    return st, dep


def age_tasks(cfg: EngineCfg, st: AggState, max_age_ticks: int) -> AggState:
    """Tombstone process groups not seen for ``max_age_ticks`` base ticks
    (the reference ages MAGGR_TASK entries via ping/delete msgs,
    ``gy_comm_proto.h:1384-1399``; we age by last-sweep tick)."""
    seen = st.task_last_tick >= 0
    stale = seen & (st.resp_win.tick - st.task_last_tick
                    > jnp.int32(max_age_ticks))
    tbl, killed = table.tombstone_rows(st.task_tbl, stale)
    return st._replace(
        task_tbl=tbl,
        task_stats=jnp.where(killed[:, None], 0.0, st.task_stats),
        task_state=jnp.where(killed, 0, st.task_state),
        task_issue=jnp.where(killed, 0, st.task_issue),
        task_host=jnp.where(killed, -1, st.task_host),
        # cpu_hist is scatter-added, never overwritten: zero it here or a
        # reclaimed slot inherits the dead group's learned baseline
        task_cpu_hist=jnp.where(killed[:, None], 0.0, st.task_cpu_hist),
        task_last_tick=jnp.where(killed, -1, st.task_last_tick),
    )


def compact_tasks(cfg: EngineCfg, st: AggState) -> AggState:
    """Rebuild the task slab without tombstones (cf. compact_state)."""
    cols = {
        "stats": st.task_stats, "state": st.task_state,
        "issue": st.task_issue, "host": st.task_host,
        "comm_hi": st.task_comm_hi, "comm_lo": st.task_comm_lo,
        "rel_hi": st.task_rel_hi, "rel_lo": st.task_rel_lo,
        "cpu_hist": st.task_cpu_hist, "last": st.task_last_tick,
    }
    tbl, c = table.compact(st.task_tbl, cols)
    live = table.live_mask(tbl)
    return st._replace(
        task_tbl=tbl, task_stats=c["stats"], task_state=c["state"],
        task_issue=c["issue"],
        task_host=jnp.where(live, c["host"], -1),
        task_comm_hi=c["comm_hi"], task_comm_lo=c["comm_lo"],
        task_rel_hi=c["rel_hi"], task_rel_lo=c["rel_lo"],
        task_cpu_hist=c["cpu_hist"],
        task_last_tick=jnp.where(live, c["last"], -1))


def ingest_host(cfg: EngineCfg, st: AggState, hb) -> AggState:
    """Fold a HostBatch (decode.host_batch): dense panel write by host_id."""
    hid = jnp.where(hb.valid, hb.host_id, cfg.n_hosts)
    panel = st.host_panel.at[hid].set(
        hb.panel.astype(jnp.float32), mode="drop")
    last = st.host_last_tick.at[hid].set(st.resp_win.tick, mode="drop")
    return st._replace(host_panel=panel, host_last_tick=last)


def ingest_cpumem(cfg: EngineCfg, st: AggState, cm) -> AggState:
    """Fold a CpuMemBatch (the 2s path): panel write + fleet-wide
    server-side classification (``semantic/cpumem.py`` — the SYS_CPU/
    SYS_MEM issue scans, ``common/gy_sys_stat.h:131``)."""
    from gyeeta_tpu.semantic import cpumem as CM

    hid = jnp.where(cm.valid, cm.host_id, cfg.n_hosts)
    vals = st.host_cm.at[hid].set(cm.vals.astype(jnp.float32),
                                  mode="drop")
    cpu_state, cpu_issue = CM.classify_cpu(vals)
    mem_state, mem_issue = CM.classify_mem(vals)
    last = st.cm_last_tick.at[hid].set(st.resp_win.tick, mode="drop")
    return st._replace(
        host_cm=vals, cm_cpu_state=cpu_state, cm_cpu_issue=cpu_issue,
        cm_mem_state=mem_state, cm_mem_issue=mem_issue,
        cm_last_tick=last)


def tick_5s(cfg: EngineCfg, st: AggState) -> AggState:
    """Close the 5s base slab on all windowed state."""
    return st._replace(
        resp_win=windows.tick(st.resp_win, cfg.levels),
        ctr_win=windows.tick(st.ctr_win, cfg.levels),
    )


# ------------------------------------------------------- health readback
# engine_health_vec layout: one f32 scalar per key, packed so the WHOLE
# device-health surface reads back in a single small transfer per report
# cadence (never per event). Reductions are sum over shards for counts
# (stacked (n,) leaves on a mesh) and max for the stage-pressure signal.
HEALTH_KEYS = (
    "svc_live", "svc_tomb", "svc_drop",
    "task_live", "task_tomb", "task_drop",
    "api_live", "api_tomb", "api_drop",
    "td_stage_max",
    "n_conn", "n_resp", "n_resp_unknown", "n_td_overflow",
    "dep_half_live", "dep_edge_live", "dep_edge_drop",
    "dep_paired", "dep_expired", "dep_dropped",
    # heavy-hitter tier: the top-K undercount bound (mass truncation
    # ever dropped — the per-key error bar every flow row reports),
    # invertible-bucket fill, and hot-admission lane count
    "topk_evicted", "hh_occupied", "hh_hot_lanes",
    # the staged table probe (engine/table.py:lookup_counted) of the
    # slab fold: lanes that needed stage 2, lookups that overflowed it
    "probe_residue", "probe_fallbacks",
)


def engine_health_vec(cfg: EngineCfg, st: AggState, dep) -> jnp.ndarray:
    """Device-state health as ONE (len(HEALTH_KEYS),) f32 vector.

    The PSketch lesson (PAPERS.md): sketch/slab occupancy and eviction
    pressure are first-class monitored signals, and accelerator-side
    aggregation structures fail silently (probe exhaustion, stage
    saturation) unless their state is read back and exported. This is
    the batched readback: slab fills + tombstones + probe-failure drop
    counters for every keyed table, digest-stage pressure, dep-graph
    pair/edge fill and drop counters, and the device event counters —
    folded to scalars ON DEVICE so the host does one small transfer.
    Works on single-chip state (() scalars) and stacked sharded state
    ((n,) leaves) alike: ``sum`` reduces over shards, ``max`` keeps the
    worst shard's pressure.
    """
    s = lambda v: jnp.sum(v).astype(jnp.float32)       # noqa: E731
    vals = (
        s(st.tbl.n_live), s(st.tbl.n_tomb), s(st.tbl.n_drop),
        s(st.task_tbl.n_live), s(st.task_tbl.n_tomb),
        s(st.task_tbl.n_drop),
        s(st.api_tbl.n_live), s(st.api_tbl.n_tomb), s(st.api_tbl.n_drop),
        jnp.max(st.td_stage_n).astype(jnp.float32),
        s(st.n_conn), s(st.n_resp), s(st.n_resp_unknown),
        s(st.n_td_overflow),
        s(dep.half_tbl.n_live), s(dep.edge_tbl.n_live),
        s(dep.edge_tbl.n_drop),
        s(dep.n_paired), s(dep.n_expired), s(dep.n_dropped),
        s(st.flow_topk.evicted), s(st.inv.prio > 0), s(st.inv.n_hot),
    )
    # int32 on the device (a float32 counter stops counting past 2^24);
    # read as uint32 so the gauges wrap at 2^32, not at 2^31
    probe = jnp.sum((st.n_probe + dep.n_probe).reshape(-1, 2), axis=0)
    return jnp.concatenate(
        [jnp.stack(vals), jax.lax.bitcast_convert_type(
            probe, jnp.uint32).astype(jnp.float32)])


def heavy_recover(cfg: EngineCfg, st: AggState) -> dict:
    """Per-tick heavy-hitter recovery: decode the invertible buckets
    (verify fingerprints + bucket positions, point-query the CMS for
    every candidate) and read the exact top-K lanes alongside — ONE
    read-only dispatch whose outputs are the whole recovery readback
    (the acceptance contract: recovery adds at most one readback per
    tick; the fold path itself never pays a single op for it)."""
    out = invertible.decode(st.inv, st.cms)
    k = cfg.topk_capacity
    t_hi, t_lo, t_counts = topk.query(st.flow_topk, k)
    # CMS estimate for the exact lanes too: truth ∈ [count, est], so
    # the merge reports est (never undercounts) with errbound est−count
    # — the exact lane's job is TIGHTENING the bound, and the window
    # shrinks the longer a key stays admitted
    t_est = countmin.query(st.cms, t_hi, t_lo).astype(jnp.float32)
    out.update({
        "topk_hi": t_hi, "topk_lo": t_lo, "topk_counts": t_counts,
        "topk_est": jnp.where(t_counts > 0, t_est, 0.0),
        "evicted": st.flow_topk.evicted,
        "total_mass": countmin.total(st.cms),
        "n_hot": st.inv.n_hot,
    })
    return out


def fold_step(cfg: EngineCfg, st: AggState, cb, rb) -> AggState:
    """The flagship fused step: one conn batch + one resp batch."""
    st = ingest_conn(cfg, st, cb)
    st = ingest_resp(cfg, st, rb)
    return st


def jit_fold_step(cfg: EngineCfg):
    """Compiled fold_step with state donation (in-place HBM update)."""
    return jax.jit(
        lambda st, cb, rb: fold_step(cfg, st, cb, rb), donate_argnums=(0,))


def fold_many(cfg: EngineCfg, st: AggState, cbs, rbs) -> AggState:
    """Fold K stacked microbatches in one flattened device dispatch.

    cbs/rbs leaves have leading axis K. The microbatch framing is a
    WIRE artifact (≤2048-conn messages, ``gy_comm_proto.h:1711``), not
    a compute boundary: every fold op is shape-generic and
    order-independent (scatter-add counters, scatter-max HLL registers,
    dup-safe table upsert), so the whole dispatch folds as ONE
    (K*B,)-lane batch — one table upsert instead of K, one top-K
    combine instead of K, no ``lax.scan`` sequencing at all. This is
    the TPU-first shape: maximal batch, minimal op count (vs the
    reference amortizing syscalls per 2048-element DB_WRITE_ARR,
    ``server/gy_mconnhdlr.h:350``).

    Response-side work (lookup + loghist + digest staging) is likewise
    one vectorized pass (``ingest_resp_bulk``); digest compression
    amortizes across dispatches via the persistent stage. The flush
    itself is NOT in this graph: the runtime watches ``stage_pressure``
    host-side and dispatches ``td_flush_partial`` when the stage runs
    out of headroom — an in-graph ``lax.cond`` here cost 110 ms per
    dispatch at 65k capacity (untaken!) from whole-buffer copies at the
    cond boundary.
    """
    flatc = jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), cbs)
    st = ingest_conn(cfg, st, flatc)
    return ingest_resp_bulk(cfg, st, rbs)


def jit_fold_many(cfg: EngineCfg):
    return jax.jit(
        lambda st, cbs, rbs: fold_many(cfg, st, cbs, rbs),
        donate_argnums=(0,))


# --------------------------------------------------------- fused megakernel
# Canonical sub-fold order inside fold_all: the sections of one dispatch
# fold as if each ``ingest_*`` above had been dispatched on its own in
# this order, conn/resp slab last (tests/test_fusedfold.py holds every
# dispatch of a Runtime to exactly that composition, bit for bit).
FOLD_ALL_ORDER = ("listener", "host", "task", "cpumem", "trace", "ping",
                  "delta", "connresp")


def fold_all(cfg: EngineCfg, st: AggState, dep, tick, *, listener=None,
             host=None, task=None, cpumem=None, trace=None, ping=None,
             delta=None, connresp=None):
    """The fused per-batch megakernel: every staged subsystem section +
    the conn/resp K-slab + the dependency-graph fold + the digest-stage
    pressure scalar, in ONE compiled dispatch with full state donation.

    Sections are Python-``None`` when absent, so each distinct presence
    combination traces its own lean variant (the hot feed path — conn/
    resp only — never pays a single op for listener/task/trace lanes;
    a 5s sweep batch compiles one "everything" variant). The runtimes
    key their jit cache on the presence tuple; in practice two or three
    variants exist per process.

    One jit-call overhead and one host→device transfer per feed batch
    where a dispatch per subsystem would pay 6+, and the pressure
    scalar is a graph OUTPUT so the hot loop never issues a second
    dispatch just to observe it (the lagged host-side flush trigger
    reads a scalar that is already materialized).

    Returns ``(state, dep, pressure)``.
    """
    from gyeeta_tpu.parallel import depgraph as dg

    # one named scope per section fold (``sect.<kind>``); the conn/resp
    # slab's components carry their own (``conn.*``, ``resp.*``)
    scope = jax.named_scope
    if listener is not None:
        with scope("sect.listener"):
            st = ingest_listener(cfg, st, listener)
    if host is not None:
        with scope("sect.host"):
            st = ingest_host(cfg, st, host)
    if task is not None:
        with scope("sect.task"):
            st = ingest_task(cfg, st, task)
    if cpumem is not None:
        with scope("sect.cpumem"):
            st = ingest_cpumem(cfg, st, cpumem)
    if trace is not None:
        with scope("sect.trace"):
            st = ingest_trace(cfg, st, trace)
    if ping is not None:
        with scope("sect.ping"):
            st = ping_tasks(cfg, st, ping)
    if delta is not None:
        with scope("sect.delta"):
            st, dep = ingest_delta(cfg, st, dep, delta, tick)
    if connresp is not None:
        cbs, rbs = connresp
        st = fold_many(cfg, st, cbs, rbs)
        with scope("dep.fold"):
            dep = dg.dep_fold_many(dep, cbs, tick)
    return st, dep, stage_pressure(st)
