"""Edge pre-aggregation (sketch-at-the-edge, wire v5): delta merge
math, delta-fed vs raw-fed fold parity, WAL replay determinism, and
the serve-negotiated agent handshake.

The contract (ISSUE 11): an agent folds its own conn/resp streams
locally (``sketch/edgefold.py``) and ships ONE mergeable-delta stream
(``NOTIFY_SKETCH_DELTA``); the server folds it with the SAME monotone
merges the raw fold applies, so HLL registers and loghist bucket
counts are BIT-IDENTICAL to raw mode, counters match up to float
addition order, and the flow tiers' errbounds stay honest through the
agent-side truncation (residual mass → the top-K ``evicted``
undercount bound).
"""

from __future__ import annotations

import numpy as np
import pytest

from gyeeta_tpu.engine import table as T
from gyeeta_tpu.engine.aggstate import EngineCfg
from gyeeta_tpu.ingest import wire
from gyeeta_tpu.runtime import Runtime
from gyeeta_tpu.sim.partha import ParthaSim
from gyeeta_tpu.sketch import edgefold as EF
from gyeeta_tpu.sketch import hyperloglog as hll, loghist, windows


def _cfg(**over) -> EngineCfg:
    base = dict(
        svc_capacity=64, n_hosts=8,
        resp_spec=loghist.LogHistSpec(vmin=1.0, vmax=1e8, nbuckets=32),
        hll_p_svc=4, hll_p_global=8, cms_depth=2, cms_width=1 << 10,
        topk_capacity=16, topk_budget=48, td_capacity=16,
        hh_depth=2, hh_width=256,
        conn_batch=64, resp_batch=128, listener_batch=32, fold_k=4)
    base.update(over)
    return EngineCfg(**base)


def _params(cfg, **over):
    p = EF.params_of_cfg(cfg, env={})
    p.update(over)
    return p


def _rows_of(rt, keys64: np.ndarray) -> np.ndarray:
    import jax.numpy as jnp
    hi = (keys64 >> np.uint64(32)).astype(np.uint32)
    lo = keys64.astype(np.uint32)
    return np.asarray(T.lookup(rt.state.tbl, jnp.asarray(hi),
                               jnp.asarray(lo),
                               jnp.ones(len(keys64), bool)))


def _feed_raw(rt, conn, resp):
    rt.feed(wire.encode_frames_chunked(wire.NOTIFY_TCP_CONN, conn))
    rt.feed(wire.encode_frames_chunked(wire.NOTIFY_RESP_SAMPLE, resp))


def _feed_delta(rt, ef, conn, resp):
    d = ef.fold_sweep(conn, resp)
    rt.feed(wire.encode_frames_chunked(wire.NOTIFY_SKETCH_DELTA, d))
    return d


def _assert_parity(rtA, rtB, keys64, resid: float, rtol=1e-5):
    """Delta-fed rtB vs raw-fed rtA over the same stream: bit parity
    where the merges are exact, allclose where only float addition
    order differs, accounted mass where the agent truncated."""
    sA, sB = rtA.state, rtB.state
    ra, rb = _rows_of(rtA, keys64), _rows_of(rtB, keys64)
    assert (ra >= 0).all() and (rb >= 0).all()
    # HLL registers: scatter-max of identical (register, rank) pairs →
    # BIT-identical, both tiers
    assert np.array_equal(np.asarray(sA.glob_hll.regs),
                          np.asarray(sB.glob_hll.regs))
    p = rtA.cfg.hll_p_svc
    assert np.array_equal(np.asarray(hll.rows(sA.svc_hll, p).regs)[ra],
                          np.asarray(hll.rows(sB.svc_hll, p).regs)[rb])
    # loghist bucket counts: integer scatter-adds — exact totals per
    # svc; individual samples sitting ON a bucket boundary may round
    # into the neighbor bucket (host-numpy vs XLA transcendental 1-ulp
    # differences in bucket_of; ~1e-5 of samples, within the spec's
    # stated quantile error), so allow a tiny flip budget
    ha = np.asarray(windows.cur_rows(sA.resp_win))[ra]
    hb = np.asarray(windows.cur_rows(sB.resp_win))[rb]
    np.testing.assert_array_equal(ha.sum(axis=1), hb.sum(axis=1))
    flips = float(np.abs(ha - hb).sum()) / 2
    assert flips <= max(2.0, 1e-4 * ha.sum()), flips
    # per-svc counters: float byte sums, addition order differs
    np.testing.assert_allclose(np.asarray(sA.ctr_win.cur)[ra],
                               np.asarray(sB.ctr_win.cur)[rb],
                               rtol=rtol, atol=1e-3)
    # event counts: exact
    assert float(sA.n_conn) == float(sB.n_conn)
    assert float(sA.n_resp) == float(sB.n_resp)
    # CMS: the delta fold carries exactly the shipped flow mass; the
    # agent's truncated residual accounts for the rest
    mA = float(np.asarray(sA.cms.counts)[0].sum())
    mB = float(np.asarray(sB.cms.counts)[0].sum())
    assert mB <= mA * (1 + 1e-6)
    np.testing.assert_allclose(mA, mB + resid, rtol=1e-5)
    # dep edges: aggregated nconn/bytes per (cli, ser) edge match
    ea, eb = rtA.dep, rtB.dep
    ka = _edge_dict(ea)
    kb = _edge_dict(eb)
    assert set(ka) == set(kb)
    for k in ka:
        np.testing.assert_allclose(ka[k], kb[k], rtol=1e-5, atol=1e-3)


def _edge_dict(dep):
    live = np.asarray(T.live_mask(dep.edge_tbl))
    chi = np.asarray(dep.e_cli_hi)[live]
    clo = np.asarray(dep.e_cli_lo)[live]
    shi = np.asarray(dep.e_ser_hi)[live]
    slo = np.asarray(dep.e_ser_lo)[live]
    ctr = np.asarray(dep.e_ctr)[live]
    return {(int(a), int(b), int(c), int(d)): (float(n), float(by))
            for a, b, c, d, (n, by) in zip(chi, clo, shi, slo, ctr)}


# ------------------------------------------------------- merge math units
def test_empty_sweep_is_a_noop():
    cfg = _cfg()
    ef = EF.EdgeFold(_params(cfg), host_id=0)
    d = ef.fold_sweep(np.empty(0, wire.TCP_CONN_DT),
                      np.empty(0, wire.RESP_SAMPLE_DT))
    assert len(d) == 0
    assert wire.encode_frames_chunked(wire.NOTIFY_SKETCH_DELTA, d) \
        == b""
    rt = Runtime(cfg)
    before = float(rt.state.n_conn)
    rt.ingest_records({wire.NOTIFY_SKETCH_DELTA: d})
    rt.flush()
    assert float(rt.state.n_conn) == before


def test_single_record_sweep():
    cfg = _cfg()
    sim = ParthaSim(n_hosts=2, n_svcs=2, seed=3)
    simB = ParthaSim(n_hosts=2, n_svcs=2, seed=3)
    rtA, rtB = Runtime(cfg), Runtime(cfg)
    ef = EF.EdgeFold(_params(cfg), host_id=0)
    rtA.feed(sim.listener_frames())
    rtB.feed(simB.listener_frames())
    conn, resp = sim.conn_records(1), sim.resp_records(1)
    conn2, resp2 = simB.conn_records(1), simB.resp_records(1)
    assert np.array_equal(conn, conn2)
    _feed_raw(rtA, conn, resp)
    d = _feed_delta(rtB, ef, conn2, resp2)
    assert len(d) > 0
    rtA.flush(), rtB.flush()
    keys = sim.glob_ids.reshape(-1)
    _assert_parity(rtA, rtB, keys, ef.stats["resid_bytes"])


def test_sketch_merge_math_multisweep():
    """Agent-side partial merge == host-side fold of the same records,
    per sketch (HLL bit parity, loghist exact, counters allclose, CMS
    mass accounted, dep edges equal) across several sweeps incl. the
    incremental-HLL steady state."""
    cfg = _cfg()
    simA = ParthaSim(n_hosts=8, n_svcs=4, seed=7)
    simB = ParthaSim(n_hosts=8, n_svcs=4, seed=7)
    rtA, rtB = Runtime(cfg), Runtime(cfg)
    ef = EF.EdgeFold(_params(cfg, flow_max=64), host_id=0,
                     hll_refresh_every=3)
    rtA.feed(simA.listener_frames())
    rtB.feed(simB.listener_frames())
    for _ in range(5):
        conn, resp = simA.conn_records(200), simA.resp_records(400)
        conn2, resp2 = simB.conn_records(200), simB.resp_records(400)
        _feed_raw(rtA, conn, resp)
        _feed_delta(rtB, ef, conn2, resp2)
    rtA.flush(), rtB.flush()
    _assert_parity(rtA, rtB, simA.glob_ids.reshape(-1),
                   ef.stats["resid_bytes"])
    # the incremental registers actually shrink after the first sweep
    # (steady-state deltas carry only risen registers)
    assert ef.stats["delta_records"] > 0


def test_flow_truncation_residual_reaches_evicted_bound():
    """flow_max truncation: the dropped mass ships as DK_RESID and
    lands in the top-K evicted undercount bound — never silent."""
    cfg = _cfg()
    sim = ParthaSim(n_hosts=4, n_svcs=2, seed=19)
    rt = Runtime(cfg)
    ef = EF.EdgeFold(_params(cfg, flow_max=4), host_id=0)
    rt.feed(sim.listener_frames())
    ev0 = float(rt.state.flow_topk.evicted)
    _feed_delta(rt, ef, sim.conn_records(300),
                np.empty(0, wire.RESP_SAMPLE_DT))
    rt.flush()
    resid = ef.stats["resid_bytes"]
    assert resid > 0
    assert float(rt.state.flow_topk.evicted) >= ev0 + resid * 0.999


# ------------------------------------------------ forward compat / decode
def test_delta_batch_oob_items_dropped_counted():
    """Payload indices outside the negotiated geometry are dropped AND
    counted — a mis-negotiated agent can't scatter out of range."""
    from gyeeta_tpu.ingest import decode
    from gyeeta_tpu.utils.selfstats import Stats

    r = np.zeros(1, wire.DELTA_DT)
    r["kind"] = wire.DK_SVC_HIST
    r["key_hi"], r["key_lo"] = 1, 2
    r["nitem"] = 2
    pv = r["payload"].reshape(-1)[:12].view(wire.DELTA_PAIR_DT)
    pv["idx"] = [3, 4000]            # 4000 >= nbuckets → dropped
    pv["wt"] = [1.0, 1.0]
    st = Stats()
    db = decode.delta_batch(r, 8, stats=st, resp_nbuckets=32,
                            hll_m_svc=16, hll_m_glob=256)
    assert int(db.hist_valid.sum()) == 1
    assert st.counters["preagg_oob_items"] == 1


def test_unknown_delta_kind_skipped_counted():
    from gyeeta_tpu.ingest import decode
    from gyeeta_tpu.utils.selfstats import Stats

    r = np.zeros(2, wire.DELTA_DT)
    r["kind"] = [wire.DK_SVC_CTR, 99]
    st = Stats()
    db = decode.delta_batch(r, 8, stats=st, resp_nbuckets=32,
                            hll_m_svc=16, hll_m_glob=256)
    assert int(db.ctr_valid.sum()) == 1
    assert st.counters["preagg_unknown_kinds"] == 1


# --------------------------------------------------- 500-stream parity fuzz
def test_delta_vs_raw_parity_fuzz_500_streams():
    """≥500 mixed sweeps through BOTH paths: the delta-fed fold stays
    within bounds of the raw-fed fold of the same stream, and every
    heavy-flow row's errbound annotation stays honest vs an exact
    offline count (undercount ≤ evicted; overcount ≤ errbound +
    the CMS collision term)."""
    cfg = _cfg(cms_width=1 << 14)
    simA = ParthaSim(n_hosts=4, n_svcs=2, n_clients=256, seed=23)
    simB = ParthaSim(n_hosts=4, n_svcs=2, n_clients=256, seed=23)
    rtA, rtB = Runtime(cfg), Runtime(cfg)
    ef = EF.EdgeFold(_params(cfg, flow_max=24), host_id=0,
                     hll_refresh_every=100)
    rtA.feed(simA.listener_frames())
    rtB.feed(simB.listener_frames())
    rng = np.random.default_rng(4)
    exact: dict = {}
    for i in range(500):
        nc = int(rng.integers(8, 80))
        nr = int(rng.integers(8, 120))
        conn, resp = simA.conn_records(nc), simA.resp_records(nr)
        conn2, resp2 = simB.conn_records(nc), simB.resp_records(nr)
        _feed_raw(rtA, conn, resp)
        _feed_delta(rtB, ef, conn2, resp2)
        if i % 25 == 7:
            # mixed-subsystem interleave: the 5s state sweeps stay RAW
            # in delta mode and must coexist with delta folds (same
            # frames into both runtimes)
            state_a = (simA.listener_frames() + simA.task_frames()
                       + wire.encode_frames_chunked(
                           wire.NOTIFY_HOST_STATE,
                           simA.host_state_records())
                       + wire.encode_frames_chunked(
                           wire.NOTIFY_CPU_MEM_STATE,
                           simA.cpu_mem_records()))
            state_b = (simB.listener_frames() + simB.task_frames()
                       + wire.encode_frames_chunked(
                           wire.NOTIFY_HOST_STATE,
                           simB.host_state_records())
                       + wire.encode_frames_chunked(
                           wire.NOTIFY_CPU_MEM_STATE,
                           simB.cpu_mem_records()))
            assert state_a == state_b
            rtA.feed(state_a)
            rtB.feed(state_b)
        # exact offline per-flow totals (accept side, like the fold)
        from gyeeta_tpu.ingest import decode as D
        cb = D.conn_batch(conn, size=len(conn))
        acc = cb.valid & cb.is_accept
        k64 = ((cb.flow_hi.astype(np.uint64) << np.uint64(32))
               | cb.flow_lo.astype(np.uint64))
        tot = (cb.bytes_sent + cb.bytes_rcvd).astype(np.float64)
        for k, v in zip(k64[acc].tolist(), tot[acc].tolist()):
            exact[k] = exact.get(k, 0.0) + v
    rtA.flush(), rtB.flush()
    _assert_parity(rtA, rtB, simA.glob_ids.reshape(-1),
                   ef.stats["resid_bytes"], rtol=1e-4)
    # ---- errbound honesty on the delta-fed heavy-flow view
    rec = rtB.heavy_recover()
    evicted = rec["evicted"]
    err_term = rec["err_term"]
    total = sum(exact.values())
    slack = 1e-6 * total
    n_rows = 0
    over = 0
    for key_hex, value, errbound, _src in rec["flows"]:
        tv = exact.get(int(key_hex, 16), 0.0)
        n_rows += 1
        # the HARD guarantee (the acceptance gate): value never
        # undercounts beyond the stated bound — deterministic through
        # the agent-side truncation (residual → evicted)
        assert tv - value <= evicted + slack, (key_hex, tv, value)
        # the overcount side is bounded only in PROBABILITY (the CMS
        # Markov term holds w.p. 1−2^−depth per row — depth 2 here):
        # budget the tail instead of asserting certainty per row
        if value - tv > errbound + err_term + slack:
            over += 1
    assert n_rows > 0
    assert over <= max(2, 0.02 * n_rows), (over, n_rows)


# ------------------------------------------------------- WAL replay parity
def test_wal_replay_delta_capture_byte_parity(tmp_path):
    """Replaying a delta-mode WAL capture reproduces the same engine
    state BYTE-FOR-BYTE (the delta fold is deterministic through the
    normal decode/fold path — durability semantics unchanged)."""
    import jax

    from gyeeta_tpu.utils.config import RuntimeOpts

    cfg = _cfg()
    sim = ParthaSim(n_hosts=4, n_svcs=4, seed=11)
    ef = EF.EdgeFold(_params(cfg), host_id=0)
    rt = Runtime(cfg, RuntimeOpts(journal_dir=str(tmp_path)))
    rt.feed(sim.listener_frames())
    for _ in range(3):
        _feed_delta(rt, ef, sim.conn_records(150),
                    sim.resp_records(300))
    rt.flush()
    rt.journal.fsync()
    rt2 = Runtime(cfg, RuntimeOpts(journal_dir=str(tmp_path)))
    rt2.replay_journal()
    rt2.flush()
    for a, b in zip(jax.tree.leaves(rt.state),
                    jax.tree.leaves(rt2.state)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    rt.close(), rt2.close()


# ---------------------------------------------------- negotiation (e2e)
def test_agent_negotiates_delta_mode(monkeypatch):
    """GYT_PREAGG=1 on the server → the REGISTER_RESP advert flips a
    default agent into delta sweeps; an opted-out agent stays raw on
    the same server; gyt_preagg_* counters appear server-side."""
    import asyncio

    from gyeeta_tpu.net import GytServer, NetAgent

    monkeypatch.setenv("GYT_PREAGG", "1")
    cfg = _cfg(n_hosts=8, svc_capacity=256)

    async def scenario():
        rt = Runtime(cfg)
        srv = GytServer(rt, tick_interval=None)
        host, port = await srv.start()
        a_delta = NetAgent(seed=1, n_svcs=2, n_groups=3)
        a_raw = NetAgent(seed=2, n_svcs=2, n_groups=3, preagg=False)
        await a_delta.connect(host, port)
        await a_raw.connect(host, port)
        assert a_delta._preagg_params is not None
        assert a_delta._preagg_params["resp_nbuckets"] \
            == cfg.resp_spec.nbuckets
        assert a_raw._preagg_params is None
        for _ in range(2):
            await a_delta.send_sweep(n_conn=64, n_resp=128)
            await a_raw.send_sweep(n_conn=64, n_resp=128)
        await asyncio.sleep(0.1)
        rt.flush()
        c = rt.stats.counters
        assert c.get("preagg_delta_records", 0) > 0
        assert c.get("preagg_agents_negotiated", 0) >= 2
        assert c.get("conn_events", 0) > 0          # the raw agent
        assert int(a_delta.stats.counters["preagg_sweeps"]) == 2
        assert "preagg_sweeps" not in a_raw.stats.counters
        # both hosts materialized fleet-view rows
        out = rt.query({"subsys": "svcstate", "maxrecs": 100,
                        "consistency": "strong"})
        hosts = {int(float(r["hostid"])) for r in out["recs"]}
        assert {a_delta.host_id, a_raw.host_id} <= hosts
        await a_delta.close()
        await a_raw.close()
        await srv.stop()
        rt.close()

    asyncio.run(scenario())


def test_no_advert_stays_raw(monkeypatch):
    """Against a server that never advertised (GYT_PREAGG unset), even
    a preagg=True agent stays raw — counted, never guessing geometry."""
    import asyncio

    from gyeeta_tpu.net import GytServer, NetAgent

    monkeypatch.delenv("GYT_PREAGG", raising=False)

    async def scenario():
        rt = Runtime(_cfg(n_hosts=8, svc_capacity=256))
        srv = GytServer(rt, tick_interval=None)
        host, port = await srv.start()
        a = NetAgent(seed=3, n_svcs=2, n_groups=3, preagg=True)
        await a.connect(host, port)
        assert a._preagg_params is None
        assert int(a.stats.counters["preagg_not_advertised"]) == 1
        await a.send_sweep(n_conn=32, n_resp=32)
        await asyncio.sleep(0.05)
        rt.flush()
        assert rt.stats.counters.get("conn_events", 0) > 0
        assert rt.stats.counters.get("preagg_delta_records", 0) == 0
        await a.close()
        await srv.stop()
        rt.close()

    asyncio.run(scenario())


# ----------------------------------------------------- sharded parity fuzz
@pytest.mark.slow
def test_sharded_delta_vs_raw_parity_fuzz_500_streams():
    """The same ≥500-stream parity contract on ShardedRuntime: delta
    records route by hid like raw records, each shard folds its own
    hosts' partials, and the merged fleet view agrees within bounds."""
    from gyeeta_tpu.parallel.mesh import make_mesh
    from gyeeta_tpu.parallel.shardedrt import ShardedRuntime

    cfg = _cfg(cms_width=1 << 12)
    mesh = make_mesh()
    rtA = ShardedRuntime(cfg, mesh=mesh)
    rtB = ShardedRuntime(cfg, mesh=mesh)
    simA = ParthaSim(n_hosts=8, n_svcs=2, n_clients=256, seed=29)
    simB = ParthaSim(n_hosts=8, n_svcs=2, n_clients=256, seed=29)
    ef = EF.EdgeFold(_params(cfg, flow_max=24), host_id=0)
    rtA.feed(simA.listener_frames())
    rtB.feed(simB.listener_frames())
    rng = np.random.default_rng(5)
    for i in range(500):
        nc = int(rng.integers(8, 48))
        nr = int(rng.integers(8, 64))
        _feed_raw(rtA, simA.conn_records(nc), simA.resp_records(nr))
        _feed_delta(rtB, ef, simB.conn_records(nc),
                    simB.resp_records(nr))
        if i % 100 == 13:
            rtA.feed(simA.listener_frames() + simA.task_frames())
            rtB.feed(simB.listener_frames() + simB.task_frames())
    rtA.flush(), rtB.flush()
    qa = rtA.query({"subsys": "svcstate", "maxrecs": 100,
                    "consistency": "strong"})
    qb = rtB.query({"subsys": "svcstate", "maxrecs": 100,
                    "consistency": "strong"})
    rows_a = {r["svcid"]: r for r in qa["recs"]}
    rows_b = {r["svcid"]: r for r in qb["recs"]}
    assert set(rows_a) == set(rows_b) and rows_a
    for sid, ra in rows_a.items():
        rb = rows_b[sid]
        # listener-gauge columns identical (raw in both modes); the
        # HLL-backed distinct-client estimate is bit-parity
        for col in ("nconns", "nactive", "hostid"):
            assert float(ra[col]) == float(rb[col]), (sid, col)
    # cluster event totals: exact
    sa, sb = rtA.rollup_stats(), rtB.rollup_stats()
    assert sa["n_conn"] == sb["n_conn"]
    assert sa["n_resp"] == sb["n_resp"]
    assert sa["n_svc_live"] == sb["n_svc_live"]
    rtA.close(), rtB.close()
