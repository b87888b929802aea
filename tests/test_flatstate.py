"""The per-service arrays stored flat (``aggstate.flat_widths``).

The slab fold scatters into the per-service HLL registers, the resp
histogram's current slab and the digest stage at ``row * W + col`` of a
1-D array, and the digest flush gathers and writes back its centroids
the same way. Held here: the fold's three targets, in their row views,
equal a numpy recount bit for bit; a checkpoint that holds these leaves
in their logical (S, W) shape restores into the flat state; every other
leaf's shape is still checked exactly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gyeeta_tpu.engine import aggstate, step, table
from gyeeta_tpu.engine.aggstate import EngineCfg
from gyeeta_tpu.ingest import decode
from gyeeta_tpu.parallel import depgraph as dg
from gyeeta_tpu.sim.partha import ParthaSim
from gyeeta_tpu.sketch import flat, hyperloglog as hll, loghist, tdigest, \
    windows
from gyeeta_tpu.utils import checkpoint as ckpt

CFG = EngineCfg(svc_capacity=64, n_hosts=8, hll_p_svc=6,
                resp_spec=loghist.LogHistSpec(1.0, 1e8, 32),
                td_capacity=16, td_stage_cap=64, td_sample_stride=4,
                conn_batch=64, resp_batch=128, fold_k=2)
SLABS = 3


def _rows_of(tbl, hi, lo):
    return np.asarray(table.lookup(tbl, jnp.asarray(hi), jnp.asarray(lo)))


def _fold_slabs(seed):
    """Fold ``SLABS`` seeded conn/resp slabs through ``fold_all``;
    return the state and, per slab, its decoded columns (numpy) and the
    service table after its fold (which placed its conn lanes before
    its resp lanes looked their rows up)."""
    cfg, k = CFG, CFG.fold_k
    sim = ParthaSim(n_hosts=8, n_svcs=4, seed=seed)
    fold = jax.jit(lambda st, dep, cbs, rbs: step.fold_all(
        cfg, st, dep, 0, connresp=(cbs, rbs))[:2])
    st = aggstate.init(cfg)
    dep = dg.init(64, 256)
    slabs = []
    for _ in range(SLABS):
        cbs = decode.conn_slab(sim.conn_records(k * cfg.conn_batch), k,
                               cfg.conn_batch)
        rbs = decode.resp_slab(sim.resp_records(k * cfg.resp_batch), k,
                               cfg.resp_batch)
        st, dep = fold(st, dep, cbs, rbs)
        cbs, rbs = jax.tree.map(lambda x: np.asarray(x).reshape(-1),
                                (cbs, rbs))
        slabs.append((cbs, rbs, st.tbl))
    return st, slabs


@pytest.mark.parametrize("seed", [5, 2147483901])
def test_slab_fold_targets_equal_numpy_recount(seed):
    cfg = CFG
    S, p = cfg.svc_capacity, cfg.hll_p_svc
    B, cap = cfg.resp_spec.nbuckets, cfg.td_stage_cap
    st, slabs = _fold_slabs(seed % 2**31)
    assert st.svc_hll.regs.shape == (S << p,)
    assert st.resp_win.cur.shape == (S * B,)
    assert st.td_stage.shape == (S * cap,)
    regs = np.zeros((S, 1 << p), np.int32)
    hist = np.zeros((S, B), np.float32)
    stage = np.zeros((S, cap), np.float32)
    fill = np.zeros(S, np.int64)
    for cb, rb, tbl in slabs:
        ok = cb.valid & cb.is_accept
        rows = _rows_of(tbl, cb.svc_hi[ok], cb.svc_lo[ok])
        assert (rows >= 0).all()
        idx, rank = hll._idx_rank(cb.cli_hi[ok], cb.cli_lo[ok], p)
        np.maximum.at(regs, (rows, idx), rank)
        rrows = np.where(rb.valid, _rows_of(tbl, rb.svc_hi, rb.svc_lo), -1)
        known = rrows >= 0
        # the buckets as the device computes them: the recount holds the
        # scatter, not the transcendental
        bucket = np.asarray(loghist.bucket_of(cfg.resp_spec,
                                              jnp.asarray(rb.resp_us)))
        np.add.at(hist, (rrows[known], bucket[known]), np.float32(1.0))
        # the digest stages every td_sample_stride-th lane, in lane
        # order within a row
        for r, v in zip(rrows[::cfg.td_sample_stride],
                        rb.resp_us[::cfg.td_sample_stride]):
            if r >= 0 and fill[r] < cap:
                stage[r, fill[r]] = v
                fill[r] += 1
    assert np.array_equal(np.asarray(hll.rows(st.svc_hll, p).regs), regs)
    assert np.asarray(windows.cur_rows(st.resp_win)).tobytes() \
        == hist.tobytes()
    assert np.array_equal(np.asarray(st.td_stage_n), fill)
    got = flat.rows(np.asarray(st.td_stage), cap)
    for r in np.nonzero(fill)[0]:
        assert got[r, :fill[r]].tobytes() == stage[r, :fill[r]].tobytes()
    assert not got[np.arange(cap)[None, :] >= fill[:, None]].any()


def test_partial_flush_writes_back_only_its_rows():
    """The flat partial flush equals the full flush over the row views
    on the rows it selects, and leaves every other row untouched."""
    cfg = CFG
    st, _ = _fold_slabs(9)
    n = np.asarray(st.td_stage_n)
    m = 3
    part = jax.jit(lambda s: step.td_flush_partial(
        cfg._replace(td_flush_m=m), s))(st)
    full = jax.jit(lambda s: step.td_flush(cfg, s))(st)
    sel = np.argsort(-n, kind="stable")[:m]
    assert (n[sel] > 0).all()
    rp, rf = tdigest.rows(part.svc_td), tdigest.rows(full.svc_td)
    r0 = tdigest.rows(st.svc_td)
    other = np.setdiff1d(np.arange(cfg.svc_capacity), sel)
    for a, b, c in ((rp.means, rf.means, r0.means),
                    (rp.weights, rf.weights, r0.weights)):
        a, b, c = np.asarray(a), np.asarray(b), np.asarray(c)
        assert a[sel].tobytes() == b[sel].tobytes()
        assert a[other].tobytes() == c[other].tobytes()
    sp = flat.rows(np.asarray(part.td_stage), cfg.td_stage_cap)
    s0 = flat.rows(np.asarray(st.td_stage), cfg.td_stage_cap)
    assert not sp[sel].any()
    assert sp[other].tobytes() == s0[other].tobytes()


def _leaf_paths(state):
    kl, _ = jax.tree_util.tree_flatten_with_path(state)
    return [jax.tree_util.keystr(k, simple=True, separator=".")
            for k, _ in kl]


def _reshaped(state, shapes: dict):
    """``state`` with the leaves named in ``shapes`` reshaped."""
    leaves, treedef = jax.tree.flatten(state)
    return jax.tree.unflatten(treedef, [
        np.asarray(x).reshape(shapes.get(path, np.shape(x)))
        for path, x in zip(_leaf_paths(state), leaves)])


def test_checkpoint_of_logical_shapes_restores_flat(tmp_path):
    """A checkpoint holding the flat leaves as (S, W), as the state
    stored them before, restores bit for bit into the flat state —
    through ``Runtime.restore`` too."""
    from gyeeta_tpu.runtime import Runtime

    cfg = CFG
    st, _ = _fold_slabs(13)
    S = cfg.svc_capacity
    widths = aggstate.flat_widths(cfg)
    assert set(widths) <= set(_leaf_paths(st))
    old = _reshaped(st, {k: (S, w) for k, w in widths.items()})
    path = ckpt.save(tmp_path / "old.npz", cfg, old, extra={"tick": 3})
    got, extra = ckpt.restore(path, cfg, aggstate.init(cfg),
                              flat_widths=widths)
    assert extra == {"tick": 3}
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(st)):
        assert a.shape == b.shape
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    rt = Runtime(cfg)
    try:
        rt.restore(path)
        assert np.asarray(rt.state.svc_hll.regs).tobytes() \
            == np.asarray(st.svc_hll.regs).tobytes()
        assert np.asarray(rt.state.td_stage).tobytes() \
            == np.asarray(st.td_stage).tobytes()
    finally:
        rt.close()


@pytest.mark.parametrize("leaf,shape", [
    ("ctr_win.cur", (64 * 4,)),                 # any other leaf: exact
    ("qps_hist", (64 * 64,)),
    ("svc_hll.regs", (2 * 64, 32)),             # a flat leaf, wrong rows
    ("resp_win.alltime", (64 * 32,)),
])
def test_checkpoint_other_leaf_shapes_still_raise(tmp_path, leaf, shape):
    cfg = CFG
    st = aggstate.init(cfg)
    bad = _reshaped(st, {leaf: shape})
    path = ckpt.save(tmp_path / "bad.npz", cfg, bad)
    with pytest.raises(ValueError, match="shape"):
        ckpt.restore(path, cfg, st, flat_widths=aggstate.flat_widths(cfg))
