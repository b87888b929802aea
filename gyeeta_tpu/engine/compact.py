"""Whole-engine slab compaction: rebuild the service table, permute every
row-indexed column tensor, and reset non-additive per-row state.

The device analogue of an RCU grace-period sweep after deletions
(``common/gy_rcu_inc.h:487``; delete flow ``server/gy_mconnhdlr.cc:11195``):
runs entirely on device in one jitted call — no host round-trip, no pause
in ingest (call between microbatches).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from gyeeta_tpu.engine import table
from gyeeta_tpu.engine.aggstate import AggState, EngineCfg, flat_widths
from gyeeta_tpu.sketch import flat

# the columns whose leaves are stored flat, (S·W,), by leaf path
_FLAT = {"resp_cur": "resp_win.cur", "svc_hll": "svc_hll.regs",
         "td_means": "svc_td.means", "td_weights": "svc_td.weights",
         "td_stage": "td_stage"}


def _rows_leading(cfg: EngineCfg, st: AggState) -> dict:
    """Collect row-indexed arrays, moving the row axis to the front.

    Window rings are (nslots, S, ...) — moveaxis to (S, nslots, ...);
    the leaves stored flat (``aggstate.flat_widths``) give their (S, W)
    view."""
    cols = {
        "resp_cur": st.resp_win.cur,
        "resp_alltime": st.resp_win.alltime,
        "ctr_cur": st.ctr_win.cur,
        "ctr_alltime": st.ctr_win.alltime,
        "svc_hll": st.svc_hll.regs,
        "td_means": st.svc_td.means,
        "td_weights": st.svc_td.weights,
        "td_vmin": st.svc_td.vmin,
        "td_vmax": st.svc_td.vmax,
        "td_stage": st.td_stage,
        "td_stage_n": st.td_stage_n,
        "svc_stats": st.svc_stats,
        "qps_hist": st.qps_hist,
        "active_hist": st.active_hist,
        "svc_host": st.svc_host,
        "svc_state": st.svc_state,
        "svc_issue": st.svc_issue,
        "resp_hi_bits": st.resp_hi_bits,
    }
    for i, (ring, tot) in enumerate(zip(st.resp_win.rings,
                                        st.resp_win.totals)):
        cols[f"resp_ring{i}"] = jnp.moveaxis(ring, 0, 1)
        cols[f"resp_tot{i}"] = tot
    for i, (ring, tot) in enumerate(zip(st.ctr_win.rings,
                                        st.ctr_win.totals)):
        cols[f"ctr_ring{i}"] = jnp.moveaxis(ring, 0, 1)
        cols[f"ctr_tot{i}"] = tot
    W = flat_widths(cfg)
    for k, path in _FLAT.items():
        cols[k] = flat.rows(cols[k], W[path])
    return cols


@partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
def compact_state(cfg: EngineCfg, st: AggState) -> AggState:
    """Rebuild the slab without tombstones; all per-row state follows."""
    cols = _rows_leading(cfg, st)
    new_tbl, new_cols = table.compact(st.tbl, cols)
    for k in _FLAT:
        new_cols[k] = new_cols[k].reshape(-1)
    live = table.live_mask(new_tbl)

    # non-additive resets for rows that are now empty
    new_cols["td_vmin"] = jnp.where(live, new_cols["td_vmin"], jnp.inf)
    new_cols["td_vmax"] = jnp.where(live, new_cols["td_vmax"], -jnp.inf)
    new_cols["svc_host"] = jnp.where(live, new_cols["svc_host"], -1)

    resp_rings = tuple(
        jnp.moveaxis(new_cols[f"resp_ring{i}"], 1, 0)
        for i in range(len(st.resp_win.rings)))
    ctr_rings = tuple(
        jnp.moveaxis(new_cols[f"ctr_ring{i}"], 1, 0)
        for i in range(len(st.ctr_win.rings)))
    return st._replace(
        tbl=new_tbl,
        resp_win=st.resp_win._replace(
            cur=new_cols["resp_cur"], alltime=new_cols["resp_alltime"],
            rings=resp_rings,
            totals=tuple(new_cols[f"resp_tot{i}"]
                         for i in range(len(st.resp_win.totals)))),
        ctr_win=st.ctr_win._replace(
            cur=new_cols["ctr_cur"], alltime=new_cols["ctr_alltime"],
            rings=ctr_rings,
            totals=tuple(new_cols[f"ctr_tot{i}"]
                         for i in range(len(st.ctr_win.totals)))),
        svc_hll=st.svc_hll._replace(regs=new_cols["svc_hll"]),
        svc_td=st.svc_td._replace(
            means=new_cols["td_means"], weights=new_cols["td_weights"],
            vmin=new_cols["td_vmin"], vmax=new_cols["td_vmax"]),
        td_stage=new_cols["td_stage"],
        td_stage_n=new_cols["td_stage_n"],
        svc_stats=new_cols["svc_stats"],
        qps_hist=new_cols["qps_hist"],
        active_hist=new_cols["active_hist"],
        svc_host=new_cols["svc_host"],
        svc_state=new_cols["svc_state"],
        svc_issue=new_cols["svc_issue"],
        resp_hi_bits=new_cols["resp_hi_bits"],
    )


def delete_services(cfg: EngineCfg, st: AggState, khi, klo):
    """Tombstone services + zero their gauges (LISTEN_FLAG_DELETE path).

    Sketch/window state is left for ``compact_state`` to sweep."""
    tbl, rows = table.delete(st.tbl, khi, klo)
    S = cfg.svc_capacity
    tgt = jnp.where(rows >= 0, rows, S)
    stats = st.svc_stats.at[tgt].set(0.0, mode="drop")
    state = st.svc_state.at[tgt].set(0, mode="drop")
    issue = st.svc_issue.at[tgt].set(0, mode="drop")
    return st._replace(tbl=tbl, svc_stats=stats, svc_state=state,
                       svc_issue=issue), rows
