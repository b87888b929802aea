"""Staged t-digest machinery: slot routing, flush, fold_many accuracy."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gyeeta_tpu.engine import aggstate, step
from gyeeta_tpu.engine.aggstate import EngineCfg
from gyeeta_tpu.ingest import decode
from gyeeta_tpu.sim.partha import ParthaSim
from gyeeta_tpu.sketch import exact, flat, tdigest


def _np_stage(S, cap, stage_v, stage_n, rows, vals):
    """Exact reference for stage_samples: per-entity append with drop."""
    sv = stage_v.copy()
    sn = stage_n.copy()
    over = 0
    for r, v in zip(rows, vals):
        if r < 0 or r >= S:
            continue
        if sn[r] >= cap:
            over += 1
            continue
        sv[r, sn[r]] = v
        sn[r] += 1
    return sv, sn, over


def test_stage_samples_matches_reference():
    S, cap, B = 16, 8, 256
    rng = np.random.default_rng(3)
    stage_v = np.zeros((S, cap), np.float32)
    stage_n = rng.integers(0, 5, S).astype(np.int32)  # pre-filled offsets
    # mask already-claimed slots so the reference agrees
    for s in range(S):
        stage_v[s, : stage_n[s]] = 100 + s
    rows = rng.integers(-1, S, B).astype(np.int32)    # incl. invalid -1
    vals = rng.random(B).astype(np.float32) * 50

    got_v, got_n, got_over = jax.jit(tdigest.stage_samples)(
        jnp.asarray(stage_v.reshape(-1)), jnp.asarray(stage_n),
        jnp.asarray(rows), jnp.asarray(vals))
    got_v = flat.rows(np.asarray(got_v), cap)
    ref_v, ref_n, ref_over = _np_stage(S, cap, stage_v, stage_n,
                                       rows, vals)
    np.testing.assert_array_equal(np.asarray(got_n), ref_n)
    assert int(got_over) == ref_over
    # slot CONTENTS may be permuted within an entity (order of equal-row
    # lanes follows the sort); compare as per-entity multisets
    for s in range(S):
        np.testing.assert_allclose(
            np.sort(got_v[s, : ref_n[s]]),
            np.sort(ref_v[s, : ref_n[s]]), rtol=1e-6)


def test_flush_staged_quantiles_and_counts():
    S, C, cap = 4, 32, 512
    rng = np.random.default_rng(7)
    sk = tdigest.init(capacity=C, entities=(S,))
    stage_v = np.zeros((S, cap), np.float32)
    stage_n = np.zeros(S, np.int32)
    all_vals = {s: [] for s in range(S)}
    for s in range(S):
        n = 200 + 100 * s
        vals = rng.lognormal(0, 0.6, n).astype(np.float32) * (s + 1) * 100
        stage_v[s, :n] = vals
        stage_n[s] = n
        all_vals[s] = vals
    sk2, zv, zn = jax.jit(tdigest.flush_staged)(
        sk, jnp.asarray(stage_v.reshape(-1)), jnp.asarray(stage_n))
    assert int(np.asarray(zn).sum()) == 0
    assert float(np.asarray(zv).sum()) == 0.0
    cnt = np.asarray(tdigest.count(sk2))
    r2 = tdigest.rows(sk2)
    for s in range(S):
        assert cnt[s] == stage_n[s]
        q = np.asarray(tdigest.quantiles(
            tdigest.TDigest(r2.means[s], r2.weights[s],
                            r2.vmin[s], r2.vmax[s]),
            jnp.array([0.5, 0.95])))
        ex = exact.quantiles(np.asarray(all_vals[s], np.float64),
                             (0.5, 0.95))
        assert abs(q[0] - ex[0]) / ex[0] < 0.15
        assert abs(q[1] - ex[1]) / ex[1] < 0.15
    # double flush of an empty stage is a no-op on the digest mass
    sk3, _, _ = jax.jit(tdigest.flush_staged)(sk2, zv, zn)
    np.testing.assert_allclose(np.asarray(tdigest.count(sk3)), cnt,
                               rtol=1e-6)


def test_flush_staged_topm_partial_and_iterative_drain():
    """The production flush path: top-m selection, stage clearing,
    untouched-row passthrough, and iterative drain equivalence with the
    full flush."""
    S, C, cap, m = 16, 32, 64, 4
    rng = np.random.default_rng(11)
    sk = tdigest.init(capacity=C, entities=(S,))
    stage_v = np.zeros((S, cap), np.float32)
    stage_n = np.zeros(S, np.int32)
    vals_of = {}
    active = [1, 3, 4, 7, 8, 12, 13, 14, 15]   # 9 active entities
    for i, s in enumerate(active):
        n = 30 + 3 * i
        v = rng.lognormal(0, 0.5, n).astype(np.float32) * (s + 1) * 10
        stage_v[s, :n] = v
        stage_n[s] = n
        vals_of[s] = v
    jfp = jax.jit(tdigest.flush_staged_topm, static_argnums=(3,))
    sk1, sv1, sn1 = jfp(sk, jnp.asarray(stage_v.reshape(-1)),
                        jnp.asarray(stage_n), m)
    # exactly the m fullest entities flushed + cleared; others untouched
    fullest = sorted(active, key=lambda s: -stage_n[s])[:m]
    sn1 = np.asarray(sn1)
    cnt1 = np.asarray(tdigest.count(sk1))
    for s in range(S):
        if s in fullest:
            assert sn1[s] == 0
            assert cnt1[s] == stage_n[s]
        else:
            assert sn1[s] == stage_n[s]
            assert cnt1[s] == 0
            np.testing.assert_array_equal(
                flat.rows(np.asarray(sv1), cap)[s], stage_v[s])
    # iterative drain (the td_drain loop) must converge and match the
    # one-shot full flush in mass and quantiles
    sk_i, sv_i, sn_i = (sk, jnp.asarray(stage_v.reshape(-1)),
                        jnp.asarray(stage_n))
    iters = 0
    while int(jnp.max(sn_i)) > 0:
        sk_i, sv_i, sn_i = jfp(sk_i, sv_i, sn_i, m)
        iters += 1
        assert iters <= -(-len(active) // m) + 1
    sk_full, _, _ = jax.jit(tdigest.flush_staged)(
        sk, jnp.asarray(stage_v.reshape(-1)), jnp.asarray(stage_n))
    np.testing.assert_allclose(np.asarray(tdigest.count(sk_i)),
                               np.asarray(tdigest.count(sk_full)),
                               rtol=1e-6)
    r_i = tdigest.rows(sk_i)
    for s in active:
        q_i = np.asarray(tdigest.quantiles(
            tdigest.TDigest(r_i.means[s], r_i.weights[s],
                            r_i.vmin[s], r_i.vmax[s]),
            jnp.array([0.5, 0.95])))
        ex = exact.quantiles(np.asarray(vals_of[s], np.float64),
                             (0.5, 0.95))
        assert abs(q_i[0] - ex[0]) / ex[0] < 0.15
        # p95 at n≈30-60 samples: order-statistic discretization widens
        # the achievable accuracy regardless of sketch quality
        assert abs(q_i[1] - ex[1]) / ex[1] < 0.25


def test_runtime_pressure_triggered_flush_and_drain():
    """Runtime hot loop: the host-side pressure check must fire
    td_flush_partial before the stage overflows, and td_drain must
    leave the digest exactly covering the staged subsample."""
    from gyeeta_tpu.runtime import Runtime
    from gyeeta_tpu.ingest import wire

    cfg = EngineCfg(n_hosts=4, svc_capacity=64, conn_batch=32,
                    resp_batch=64, fold_k=2, td_sample_stride=1,
                    td_stage_cap=64, td_flush_m=8)
    rt = Runtime(cfg)
    sim = ParthaSim(n_hosts=4, n_svcs=1, seed=23)   # 4 hot services
    nresp = 0
    # enough resp volume that per-svc staged counts cross cap//2 (32)
    # repeatedly: 4 svcs × cap//2 = 128 staged → trigger every ~2 slabs
    for _ in range(12):
        rt.feed(sim.conn_frames(cfg.fold_k * cfg.conn_batch)
                + sim.resp_frames(cfg.fold_k * cfg.resp_batch))
        nresp += cfg.fold_k * cfg.resp_batch
    assert rt.stats.counters.get("td_partial_flushes", 0) > 0
    rt.td_drain()
    assert int(np.asarray(rt.state.td_stage_n).sum()) == 0
    cnt = float(np.asarray(tdigest.count(rt.state.svc_td)).sum())
    over = float(np.asarray(rt.state.n_td_overflow))
    unknown = float(np.asarray(rt.state.n_resp_unknown))
    # every known-service staged sample is in the digest or counted
    assert cnt + over == float(np.asarray(rt.state.n_resp)) - unknown
    rt.close()


@pytest.mark.parametrize("stride", [1, 2])
def test_fold_many_digest_accuracy(stride):
    """End-to-end hot path: jit_fold_many (bulk resp + staged digest +
    maybe-flush) must serve accurate per-service quantiles."""
    cfg = EngineCfg(n_hosts=4, svc_capacity=64, conn_batch=64,
                    resp_batch=128, fold_k=4, td_sample_stride=stride,
                    td_stage_cap=256)
    sim = ParthaSim(n_hosts=4, n_svcs=2, seed=21)
    st = aggstate.init(cfg)
    fold = step.jit_fold_many(cfg)
    K = cfg.fold_k
    all_resps = []
    staged_resps = []       # the exact lanes the stride subsample stages
    for _ in range(3):
        cbs = [decode.conn_batch(sim.conn_records(cfg.conn_batch))
               for _ in range(K)]
        rraws = [sim.resp_records(cfg.resp_batch) for _ in range(K)]
        flat = np.concatenate(rraws)
        all_resps.append(flat)
        staged_resps.append(flat[::stride])
        rbs = [decode.resp_batch(r) for r in rraws]
        stack = lambda bs: jax.tree.map(  # noqa: E731
            lambda *xs: np.stack(xs), *bs)
        st = fold(st, stack(cbs), stack(rbs))
    st = jax.jit(lambda s: step.td_flush(cfg, s))(st)
    resps = np.concatenate(all_resps)
    assert float(st.n_resp) == len(resps)
    assert int(np.asarray(st.td_stage_n).sum()) == 0
    # digest holds ~1/stride of all samples (minus counted overflow)
    cnt = float(np.asarray(tdigest.count(st.svc_td)).sum())
    over = float(np.asarray(st.n_td_overflow))
    assert cnt + over == -(-len(resps) // stride)  # ceil-div per stride
    # per-service p50/p95: the sketch must track the exact quantiles of
    # the lanes it actually staged to ~sketch error (machinery test),
    # and the full stream loosely (sampling-variance test)
    from gyeeta_tpu.engine import table
    staged = np.concatenate(staged_resps)
    checked = 0
    for gid in np.unique(resps["glob_id"]):
        vals = resps["resp_usec"][resps["glob_id"] == gid].astype(
            np.float64)
        svals = staged["resp_usec"][staged["glob_id"] == gid].astype(
            np.float64)
        if len(vals) < 150:
            continue
        row = int(np.asarray(table.lookup(
            st.tbl, np.array([gid >> np.uint64(32)], np.uint32),
            np.array([gid & np.uint64(0xFFFFFFFF)], np.uint32)))[0])
        assert row >= 0
        td = tdigest.rows(st.svc_td)
        q = np.asarray(tdigest.quantiles(
            tdigest.TDigest(td.means[row], td.weights[row],
                            td.vmin[row], td.vmax[row]),
            jnp.array([0.5, 0.95])))
        exs = exact.quantiles(svals, (0.5, 0.95))
        assert abs(q[0] - exs[0]) / exs[0] < 0.12, (gid, q[0], exs[0])
        assert abs(q[1] - exs[1]) / exs[1] < 0.12, (gid, q[1], exs[1])
        ex = exact.quantiles(vals, (0.5, 0.95))
        assert abs(q[0] - ex[0]) / ex[0] < 0.35   # sampling variance
        assert abs(q[1] - ex[1]) / ex[1] < 0.35
        checked += 1
    assert checked >= 4
