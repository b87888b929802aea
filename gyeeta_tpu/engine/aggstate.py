"""AggState: the full device-resident aggregation state as one pytree.

This is the TPU replacement for a madhava's in-memory model
(``server/gy_msocket.h`` MTCP_LISTENER/MAGGR_TASK rows + per-listener
histograms): one keyed entity slab for services, struct-of-arrays sketch
columns per service, global flow sketches, and a dense per-host stat panel.
A single jitted step (see ``engine/step.py``) folds whole columnar
microbatches into this state; queries are pure readbacks (``query/``).

Memory (defaults, f32): per-service loghist windows dominate —
(S=1024 rows × 256 buckets) × (1 cur + 12 + 24 ring slabs + 2 totals + 1
alltime) ≈ 40 MB. Scale S/buckets per deployment; HBM is the budget.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from gyeeta_tpu.engine import table
from gyeeta_tpu.ingest import decode
from gyeeta_tpu.sketch import countmin, hyperloglog as hll, invertible, \
    loghist, tdigest, topk, windows

# conn-counter columns (windowed, per service)
CTR_BYTES_SENT = 0
CTR_BYTES_RCVD = 1
CTR_NCONN_CLOSED = 2
CTR_DUR_SUM_US = 3
NCTR = 4

# host panel columns (canonical order defined by the decode layer)
from gyeeta_tpu.ingest.decode import (  # noqa: E402,F401
    HOST_NTASKS, HOST_NTASKS_ISSUE, HOST_NTASKS_SEVERE, HOST_NLISTEN,
    HOST_NLISTEN_ISSUE, HOST_NLISTEN_SEVERE, HOST_CPU_ISSUE, HOST_MEM_ISSUE,
    HOST_SEVERE_CPU, HOST_SEVERE_MEM, HOST_STATE, NHOSTCOL,
)


class EngineCfg(NamedTuple):
    """Static engine geometry (all sizes are compile-time constants)."""
    svc_capacity: int = 1024          # service slab rows (power of two)
    n_hosts: int = 64                 # dense host panel rows
    resp_spec: loghist.LogHistSpec = loghist.LogHistSpec(
        vmin=1.0, vmax=1e8, nbuckets=256)   # usec: 1us..100s, <2% error
    # learned per-svc baselines (ref: qps_hist_/active_conn_hist_,
    # common/gy_socket_stat.h:365): QPS 1..1M, active conns 1..100k
    qps_spec: loghist.LogHistSpec = loghist.LogHistSpec(
        vmin=1.0, vmax=1e6, nbuckets=64)
    active_spec: loghist.LogHistSpec = loghist.LogHistSpec(
        vmin=1.0, vmax=1e5, nbuckets=32)
    levels: tuple = windows.LEVELS_DEFAULT
    task_capacity: int = 2048         # process-group slab rows (power of 2)
    api_capacity: int = 4096          # (svc, api) trace slab rows (pow 2)
    # per-API response-time loghist (north-star config #5): 1us..100s,
    # 128 γ-buckets → ~±7% quantile error
    apiresp_spec: loghist.LogHistSpec = loghist.LogHistSpec(
        vmin=1.0, vmax=1e8, nbuckets=128)
    # learned per-group CPU%% baseline (ref AGGR_TASK_HIST_STATS cpu pct
    # histogram, gy_comm_proto.h:2966): 0.1%..10k% (100 cores)
    taskcpu_spec: loghist.LogHistSpec = loghist.LogHistSpec(
        vmin=0.1, vmax=1e4, nbuckets=32)
    hll_p_svc: int = 10               # per-svc distinct clients (±3.2%)
    hll_p_global: int = 14            # global distinct endpoints (±0.8%)
    cms_depth: int = 2                # fold cost is depth-linear (one
    #                                   scatter lane per row per event —
    #                                   the 2nd-largest fold op); depth 2
    #                                   at DOUBLE width spends the same
    #                                   memory on halved per-row
    #                                   collision rates. Estimates stay
    #                                   strict upper bounds (the top-K
    #                                   candidate filter depends on
    #                                   that); the weaker tail bound
    #                                   (err ≤ e·N/width w.p. 1-e⁻²) is
    #                                   a documented CPU-geometry
    #                                   tradeoff — raise GYT_CMS_DEPTH
    #                                   back on accelerators with
    #                                   scatter headroom (OPERATIONS.md
    #                                   "Fold-path tuning")
    cms_width: int = 1 << 17
    topk_capacity: int = 512
    topk_budget: int = 2048           # sketch-assisted top-K candidate
    #                                   compaction: only the budget
    #                                   highest-CMS-estimate lanes of a
    #                                   fold dispatch enter the O(n
    #                                   log n) grouping sort (the
    #                                   dominant fold op at slab width;
    #                                   33k→2.6k lanes ≈ 11.6→2 ms per
    #                                   dispatch on one core; 4x the
    #                                   top-K capacity). 0 = every
    #                                   lane (exact truncation). Mass
    #                                   excluded by the budget is
    #                                   accounted in ``evicted`` —
    #                                   see sketch/topk.py:update
    hh_depth: int = 2                 # invertible heavy-hitter tier
    #                                   (sketch/invertible.py): rows of
    #                                   candidate buckets; a heavy key
    #                                   is missed only if it loses its
    #                                   bucket argmax in EVERY row
    hh_width: int = 4096              # buckets per row; d·w candidate
    #                                   slots ≈ 8k (160 KB of state, a
    #                                   ~128 KB readback per tick). 0
    #                                   disables the tier entirely.
    hh_hot_frac: float = 1e-5         # PSketch hot-admission floor: a
    #                                   lane enters the exact top-K
    #                                   merge only when its CMS
    #                                   estimate ≥ hh_hot_frac × total
    #                                   folded mass (on TOP of the
    #                                   topk_budget relative ranking);
    #                                   colder lanes stay in the
    #                                   invertible array + CMS, their
    #                                   mass lands in ``evicted``. 0
    #                                   disables the absolute floor
    #                                   (budget-only admission).
    td_capacity: int = 64             # per-svc t-digest centroids
    # staged-digest buffer: samples accumulate here across a fold_many
    # dispatch (K microbatches) and compress ONCE at its end — the
    # vmapped compression sort is ~80% of the naive fold cost
    td_stage_cap: int = 512           # per-svc staged samples (flush at
    #                                   half-full: size ≥4× the expected
    #                                   per-svc fill per dispatch)
    td_sample_stride: int = 16        # digest duty-cycle: stage 1-in-N
    #                                   resp samples. The loghist folds
    #                                   EVERY sample and stays the
    #                                   lossless estimator behind the
    #                                   windowed resp_p* columns; the
    #                                   digest is the ALL-TIME tail
    #                                   refinement (td_p*), where the
    #                                   duty cycle only slows
    #                                   convergence (samples accumulate
    #                                   unboundedly). Its staging sort +
    #                                   flush compression scale ~1/N:
    #                                   16 vs the old 2 is ~45% of the
    #                                   whole toy fold cost (r07). The
    #                                   reference samples resp events
    #                                   ~50% at the SOURCE (gy_ebpf.h:29)
    #                                   — here the full stream still
    #                                   reaches the loghist. GYT_TD_
    #                                   SAMPLE_STRIDE tunes it; see
    #                                   OPERATIONS.md "Fold-path tuning"
    td_flush_m: int = 256             # entities compressed per partial
    #                                   flush — flush cost is O(m), not
    #                                   O(capacity); the runtime drains
    #                                   iteratively under pressure.
    #                                   Small m beats m≈S under skewed
    #                                   load: pressure is driven by the
    #                                   few HOT stages, and sorting the
    #                                   mostly-empty rest was ~2/3 of
    #                                   the flush cost (107→27 ms per
    #                                   flush on the toy geometry, r07)
    conn_batch: int = 2048            # static microbatch lanes
    resp_batch: int = 4096
    listener_batch: int = 512
    fold_k: int = 16                  # microbatches per fold_many dispatch


class AggState(NamedTuple):
    tbl: table.Table                  # service key slab (glob_id → row)
    resp_win: windows.MultiWindow     # (S, B) resp-time loghist, windowed
    ctr_win: windows.MultiWindow      # (S, NCTR) conn counters, windowed
    svc_hll: hll.HLL                  # (S, m) distinct client endpoints
    svc_td: tdigest.TDigest           # (S, C) per-svc resp digest
    td_stage: jnp.ndarray             # (S, cap) staged raw samples
    td_stage_n: jnp.ndarray           # (S,) int32 staged fill counts
    svc_stats: jnp.ndarray            # (S, NSTAT) last listener-state gauges
    qps_hist: jnp.ndarray             # (S, Bq) learned QPS baseline hist
    active_hist: jnp.ndarray          # (S, Ba) learned active-conn baseline
    svc_host: jnp.ndarray             # (S,) int32 owning host id (-1 unset)
    svc_state: jnp.ndarray            # (S,) int32 semantic.STATE_*
    svc_issue: jnp.ndarray            # (S,) int32 semantic.ISSUE_*
    resp_hi_bits: jnp.ndarray         # (S,) int32 8-tick high-resp history
    #                                   (ref high_resp_bit_hist_,
    #                                    gy_comm_proto.h:2212)
    host_panel: jnp.ndarray           # (H, NHOSTCOL) last host state
    host_last_tick: jnp.ndarray       # (H,) int32 tick of last host report
    #                                   (-1 = never; staleness → Down)
    # --- 2s cpu/mem path (ref CPU_MEM_STATE_NOTIFY gy_comm_proto.h:2024,
    #     classified server-side by semantic/cpumem.py) ---
    host_cm: jnp.ndarray              # (H, NCM) last raw 2s gauges
    cm_cpu_state: jnp.ndarray         # (H,) int32 STATE_*
    cm_cpu_issue: jnp.ndarray         # (H,) int32 CISSUE_*
    cm_mem_state: jnp.ndarray         # (H,) int32 STATE_*
    cm_mem_issue: jnp.ndarray         # (H,) int32 MISSUE_*
    cm_last_tick: jnp.ndarray         # (H,) int32
    # --- task tier (process groups, ref MAGGR_TASK server/gy_msocket.h) ---
    task_tbl: table.Table             # aggr_task_id → row
    task_stats: jnp.ndarray           # (T, NTASKSTAT) last 5s sweep gauges
    task_state: jnp.ndarray           # (T,) int32 agent-classified state
    task_issue: jnp.ndarray           # (T,) int32 issue source
    task_host: jnp.ndarray            # (T,) int32 owning host (-1 unset)
    task_comm_hi: jnp.ndarray         # (T,) interned comm id halves
    task_comm_lo: jnp.ndarray
    task_rel_hi: jnp.ndarray          # (T,) related listener id halves
    task_rel_lo: jnp.ndarray
    task_cpu_hist: jnp.ndarray        # (T, Bc) learned CPU%% baseline
    task_last_tick: jnp.ndarray       # (T,) int32 tick of last sweep
    # --- request-trace tier (per-(svc, api) aggregates, ref
    #     REQ_TRACE_TRAN fan-in gy_comm_proto.h:3288) ---
    api_tbl: table.Table              # mix(svc, api) → row
    api_svc_hi: jnp.ndarray           # (A,) service glob id halves
    api_svc_lo: jnp.ndarray
    api_id_hi: jnp.ndarray            # (A,) interned api signature halves
    api_id_lo: jnp.ndarray
    api_proto: jnp.ndarray            # (A,) int32 trace.PROTO_*
    api_resp_hist: jnp.ndarray        # (A, Ba) response-time loghist
    api_ctr: jnp.ndarray              # (A, 4) nreq/nerr/bytes_in/bytes_out
    api_host: jnp.ndarray             # (A,) int32 last reporting host
    api_last_tick: jnp.ndarray        # (A,) int32
    glob_hll: hll.HLL                 # distinct flow endpoints global
    cms: countmin.CMS                 # flow-key → bytes
    flow_topk: topk.TopK              # heavy-hitter flows by bytes
    inv: invertible.InvSketch         # invertible candidate buckets —
    #                                   per-tick key recovery decodes
    #                                   heavy keys straight from here
    n_conn: jnp.ndarray               # () f32 counters
    n_resp: jnp.ndarray
    n_td_overflow: jnp.ndarray        # samples that missed the digest path
    n_resp_unknown: jnp.ndarray       # resp samples for unannounced svcs
    n_probe: jnp.ndarray              # (2,) int32 — conn/resp probe lanes
    #                                   sent to stage 2, lookups that
    #                                   overflowed it (table.lookup_counted)


def init(cfg: EngineCfg) -> AggState:
    S = cfg.svc_capacity
    B = cfg.resp_spec.nbuckets
    return AggState(
        tbl=table.init(S),
        resp_win=windows.init((S, B), cfg.levels),
        ctr_win=windows.init((S, NCTR), cfg.levels),
        svc_hll=hll.init(p=cfg.hll_p_svc, entities=(S,)),
        svc_td=tdigest.init(capacity=cfg.td_capacity, entities=(S,)),
        td_stage=jnp.zeros((S, cfg.td_stage_cap), jnp.float32),
        td_stage_n=jnp.zeros((S,), jnp.int32),
        svc_stats=jnp.zeros((S, decode.NSTAT), jnp.float32),
        qps_hist=jnp.zeros((S, cfg.qps_spec.nbuckets), jnp.float32),
        active_hist=jnp.zeros((S, cfg.active_spec.nbuckets), jnp.float32),
        svc_host=jnp.full((S,), -1, jnp.int32),
        svc_state=jnp.zeros((S,), jnp.int32),
        svc_issue=jnp.zeros((S,), jnp.int32),
        resp_hi_bits=jnp.zeros((S,), jnp.int32),
        host_panel=jnp.zeros((cfg.n_hosts, NHOSTCOL), jnp.float32),
        host_last_tick=jnp.full((cfg.n_hosts,), -1, jnp.int32),
        host_cm=jnp.zeros((cfg.n_hosts, decode.NCM), jnp.float32),
        cm_cpu_state=jnp.zeros((cfg.n_hosts,), jnp.int32),
        cm_cpu_issue=jnp.zeros((cfg.n_hosts,), jnp.int32),
        cm_mem_state=jnp.zeros((cfg.n_hosts,), jnp.int32),
        cm_mem_issue=jnp.zeros((cfg.n_hosts,), jnp.int32),
        cm_last_tick=jnp.full((cfg.n_hosts,), -1, jnp.int32),
        task_tbl=table.init(cfg.task_capacity),
        task_stats=jnp.zeros((cfg.task_capacity, decode.NTASKSTAT),
                             jnp.float32),
        task_state=jnp.zeros((cfg.task_capacity,), jnp.int32),
        task_issue=jnp.zeros((cfg.task_capacity,), jnp.int32),
        task_host=jnp.full((cfg.task_capacity,), -1, jnp.int32),
        task_comm_hi=jnp.zeros((cfg.task_capacity,), jnp.uint32),
        task_comm_lo=jnp.zeros((cfg.task_capacity,), jnp.uint32),
        task_rel_hi=jnp.zeros((cfg.task_capacity,), jnp.uint32),
        task_rel_lo=jnp.zeros((cfg.task_capacity,), jnp.uint32),
        task_cpu_hist=jnp.zeros(
            (cfg.task_capacity, cfg.taskcpu_spec.nbuckets), jnp.float32),
        task_last_tick=jnp.full((cfg.task_capacity,), -1, jnp.int32),
        api_tbl=table.init(cfg.api_capacity),
        api_svc_hi=jnp.zeros((cfg.api_capacity,), jnp.uint32),
        api_svc_lo=jnp.zeros((cfg.api_capacity,), jnp.uint32),
        api_id_hi=jnp.zeros((cfg.api_capacity,), jnp.uint32),
        api_id_lo=jnp.zeros((cfg.api_capacity,), jnp.uint32),
        api_proto=jnp.zeros((cfg.api_capacity,), jnp.int32),
        api_resp_hist=jnp.zeros(
            (cfg.api_capacity, cfg.apiresp_spec.nbuckets), jnp.float32),
        api_ctr=jnp.zeros((cfg.api_capacity, 4), jnp.float32),
        api_host=jnp.full((cfg.api_capacity,), -1, jnp.int32),
        api_last_tick=jnp.full((cfg.api_capacity,), -1, jnp.int32),
        glob_hll=hll.init(p=cfg.hll_p_global),
        cms=countmin.init(cfg.cms_depth, cfg.cms_width),
        flow_topk=topk.init(cfg.topk_capacity),
        inv=invertible.init(cfg.hh_depth, max(cfg.hh_width, 1)),
        n_conn=jnp.zeros((), jnp.float32),
        n_resp=jnp.zeros((), jnp.float32),
        n_td_overflow=jnp.zeros((), jnp.float32),
        n_resp_unknown=jnp.zeros((), jnp.float32),
        n_probe=jnp.zeros((2,), jnp.int32),
    )
