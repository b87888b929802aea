"""Derive classifier signals from live AggState + run the 5s classify pass.

The tensor equivalent of the reference's 5-second ``listener_stats_update``
sweep (``common/gy_socket_stat.cc:3898``): for every service row at once,
read current/historical percentiles out of the sketch state, build
``SvcSignals``, run the rule cascade, and store the resulting state/issue
(and the 8-tick high-response bit history) back into the engine state.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from gyeeta_tpu.engine.aggstate import AggState, EngineCfg
from gyeeta_tpu.ingest import decode as D
from gyeeta_tpu.semantic import svcstate
from gyeeta_tpu.sketch import loghist, windows

_QS = (0.95, 0.99)


def _popcount8(x):
    return sum((x >> k) & 1 for k in range(8))


def signals(cfg: EngineCfg, st: AggState):
    """AggState → (SvcSignals, high_resp_now) over all service rows."""
    spec = cfg.resp_spec
    qs = jnp.asarray(_QS, jnp.float32)
    h5 = windows.cur_rows(st.resp_win)         # current 5s slab
    h300 = windows.read(st.resp_win, 0)        # 5 min
    h5day = windows.read(st.resp_win, 1)       # 5 days
    q5 = loghist.quantiles(h5, spec, qs)
    q300 = loghist.quantiles(h300, spec, qs)
    q5day = loghist.quantiles(h5day, spec, qs)

    b5 = loghist.bucket_of(spec, q5[:, 0])
    b300 = loghist.bucket_of(spec, q300[:, 0])
    b5day = loghist.bucket_of(spec, q5day[:, 0])
    # static bucket of 1ms (resp values are usec) — same formula as
    # loghist.bucket_of, computed in python at trace time
    import math
    b_1ms = int(min(spec.nbuckets - 1, max(0, math.floor(
        math.log(max(1000.0, spec.vmin) / spec.vmin)
        / math.log(spec.gamma)))))

    nqrys = loghist.counts_total(h5)
    gauges = st.svc_stats
    # engine-resident query count: prefer live resp samples; fall back to
    # the agent-reported gauge when the resp stream is sampled out
    nqrys = jnp.maximum(nqrys, gauges[:, D.STAT_NQRYS])
    curr_qps = nqrys / 5.0

    qps_q = loghist.quantiles(st.qps_hist, cfg.qps_spec,
                              jnp.asarray([0.95, 0.25], jnp.float32))
    act_q = loghist.quantiles(st.active_hist, cfg.active_spec,
                              jnp.asarray([0.95, 0.25], jnp.float32))

    ntasks = gauges[:, D.STAT_NTASKS]
    ntasks_issue = gauges[:, D.STAT_NTASKS_ISSUE]
    delay_ms = (gauges[:, D.STAT_TASKS_DELAY_US]
                + gauges[:, D.STAT_TASKS_CPUDELAY_US]
                + gauges[:, D.STAT_TASKS_BLKIODELAY_US]) / 1000.0

    # task-tier join: fold the process-group sweeps into per-service
    # signals via related_listen_id (the reference joins MAGGR_TASK →
    # MTCP_LISTENER through related_listen_id_ and feeds listener task
    # counts from it). Segment-sum over the svc slab; elementwise max with
    # the listener gauges (same underlying facts, different paths — the
    # fresher/stronger signal wins, never double-counts).
    from gyeeta_tpu.engine import table as _table
    task_live = _table.live_mask(st.task_tbl)
    rel_rows = _table.lookup(st.tbl, st.task_rel_hi, st.task_rel_lo,
                             valid=task_live)
    tgt = jnp.where(rel_rows >= 0, rel_rows, cfg.svc_capacity)
    tstats = st.task_stats
    t_issue_by_svc = jnp.zeros((cfg.svc_capacity,), jnp.float32).at[tgt].add(
        tstats[:, D.TASK_NTASKS_ISSUE], mode="drop")
    t_ntasks_by_svc = jnp.zeros((cfg.svc_capacity,), jnp.float32).at[tgt].add(
        tstats[:, D.TASK_NTASKS], mode="drop")
    t_delay_by_svc = jnp.zeros((cfg.svc_capacity,), jnp.float32).at[tgt].add(
        tstats[:, D.TASK_CPU_DELAY_MS] + tstats[:, D.TASK_VM_DELAY_MS]
        + tstats[:, D.TASK_BLKIO_DELAY_MS], mode="drop")
    ntasks = jnp.maximum(ntasks, t_ntasks_by_svc)
    ntasks_issue = jnp.maximum(ntasks_issue, t_issue_by_svc)
    delay_ms = jnp.maximum(delay_ms, t_delay_by_svc)
    # simplified is_task_issue (ref gy_socket_stat.h:699): any flagged task
    # is an issue; severe when every task is flagged or delays are heavy
    task_issue = ntasks_issue > 0
    task_severe = task_issue & ((ntasks_issue >= ntasks)
                                | (delay_ms >= 1000.0))
    task_delay = delay_ms > 0

    # host pressure flags looked up through the service→host mapping
    hostz = jnp.clip(st.svc_host, 0, cfg.n_hosts - 1)
    has_host = st.svc_host >= 0
    cpu_issue = has_host & (
        st.host_panel[hostz, D.HOST_CPU_ISSUE] > 0)
    mem_issue = has_host & (
        st.host_panel[hostz, D.HOST_MEM_ISSUE] > 0)

    mean5 = loghist.mean(h5, spec)
    mean5day = loghist.mean(h5day, spec)

    low = (b5 <= b_1ms) | (q5[:, 0] < q5day[:, 0])
    same = b5 == b5day
    high_now = ~low & ~same

    sig = svcstate.SvcSignals(
        b5=b5, b300=b300, b5day=b5day,
        r5p95=q5[:, 0], r5p99=q5[:, 1],
        r5dayp95=q5day[:, 0], r5dayp99=q5day[:, 1],
        mean5=mean5, mean5day=mean5day,
        nqrys_5s=nqrys, curr_qps=curr_qps,
        qps_p95=qps_q[:, 0], qps_p25=qps_q[:, 1],
        curr_active=gauges[:, D.STAT_NCONNS_ACTIVE],
        active_p95=act_q[:, 0], active_p25=act_q[:, 1],
        nconn=gauges[:, D.STAT_NCONNS],
        ser_errors=gauges[:, D.STAT_SER_ERRORS],
        task_issue=task_issue, task_severe=task_severe,
        task_delay=task_delay,
        ntasks_issue=ntasks_issue,
        ntasks_noissue=jnp.maximum(ntasks - ntasks_issue, 0.0),
        tasks_delay_msec=delay_ms,
        total_resp_msec=gauges[:, D.STAT_TOTAL_RESP_MS],
        cpu_issue=cpu_issue, mem_issue=mem_issue,
        high_resp_ticks=_popcount8(
            ((st.resp_hi_bits << 1)
             | high_now.astype(jnp.int32)) & 0xFF),
        b_1ms=b_1ms,
    )
    return sig, high_now


def classify_pass(cfg: EngineCfg, st: AggState):
    """One 5s classification sweep → updated AggState (state/issue/bits)."""
    sig, high_now = signals(cfg, st)
    state, issue = svcstate.classify(sig)
    from gyeeta_tpu.engine import table
    live = table.live_mask(st.tbl)
    state = jnp.where(live, state, 0)
    issue = jnp.where(live, issue, 0)
    bits = ((st.resp_hi_bits << 1) | high_now.astype(jnp.int32)) & 0xFF
    return st._replace(svc_state=state, svc_issue=issue, resp_hi_bits=bits)


def jit_classify_pass(cfg: EngineCfg):
    return jax.jit(partial(classify_pass, cfg), donate_argnums=(0,))
