"""Time-travel history tier: WAL compaction → columnar snapshot shards
→ ``at=``/``window=`` queries (ISSUE 8).

Done-criteria exercised here:
- REPLAY PARITY: the shard-materialized snapshot at tick T is
  bit-identical to the live fold state captured at T — every engine
  leaf AND every dep-graph leaf — on Runtime (fast tier) and
  ShardedRuntime (slow tier);
- CRASH SAFETY: a SIGKILL mid-compaction (simulated at every window of
  the tmp-shard → rename → manifest-rewrite sequence) leaves the
  manifest consistent; stranded tmp/orphan files are swept on start
  like ``checkpoint.sweep_stale_tmp``; recompaction converges to the
  same shards;
- RETENTION: raw shards age into downsampled mid shards (sketch-merge
  semantics) and the manifest never names a missing file;
- QUERY: at=-pinned and windowed queries on the engine path, including
  ``topk`` with honest bounds, plus windowed alertdef evaluation;
- HISTORY WRITER: the per-tick relational write rides a bounded
  single-writer queue (drop-oldest counted, barrier read-your-writes)
  instead of synchronous SQL inside run_tick.
"""

from __future__ import annotations

import json
import os

import jax
import numpy as np
import pytest

from gyeeta_tpu.engine.aggstate import EngineCfg
from gyeeta_tpu.history.compactor import Compactor
from gyeeta_tpu.history.shards import ShardStore
from gyeeta_tpu.runtime import Runtime
from gyeeta_tpu.sim.partha import ParthaSim
from gyeeta_tpu.utils.config import RuntimeOpts

CFG = EngineCfg(n_hosts=8, svc_capacity=64, task_capacity=64,
                conn_batch=128, resp_batch=256, fold_k=2)


def _opts(tmp_path, **kw):
    base = dict(journal_dir=str(tmp_path / "wal"),
                hist_shard_dir=str(tmp_path / "shards"),
                hist_window_ticks=2,
                dep_pair_capacity=1024, dep_edge_capacity=512)
    base.update(kw)
    return RuntimeOpts(**base)


def _drive(rt, sim, ticks: int) -> None:
    for _ in range(ticks):
        rt.feed(sim.conn_frames(256) + sim.resp_frames(512)
                + sim.listener_frames() + sim.task_frames())
        rt.run_tick()


def _leaves(tree) -> list:
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def _assert_leaves_equal(got, want, what: str) -> None:
    assert len(got) == len(want)
    for i, (a, b) in enumerate(zip(got, want)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            f"{what} leaf {i} diverged"


# ------------------------------------------------------------ shard store
def test_shard_store_roundtrip_and_resolution(tmp_path):
    store = ShardStore(tmp_path / "sh")
    cols = {"svcstate": (
        {"svcid": np.array(["aa", "bb"], object),
         "qps5s": np.array([1.0, 2.0])},
        np.array([True, False]))}
    e1 = store.add_shard(level="raw", tick0=0, tick1=2, t0=10.0,
                         t1=20.0, state_leaves=[np.arange(4)],
                         dep_leaves=[np.ones(2)], columns=cols,
                         wal_pos=(0, 100))
    store.add_shard(level="raw", tick0=2, tick1=4, t0=20.0, t1=30.0,
                    state_leaves=[np.arange(4) + 1],
                    dep_leaves=[np.ones(2)], columns=cols,
                    wal_pos=(0, 200))
    assert store.position() == (0, 200)
    assert store.tick() == 4
    # round trip: strings come back as object arrays, values intact
    data = store.load(e1)
    assert data["columns"]["svcstate"][0]["svcid"].dtype == object
    assert list(data["columns"]["svcstate"][0]["svcid"]) == ["aa", "bb"]
    assert np.array_equal(data["state"][0], np.arange(4))
    # at= resolution: newest window END <= ts; too-early ts → earliest
    assert store.resolve_at(25.0)["tick1"] == 2
    assert store.resolve_at(30.0)["tick1"] == 4
    assert store.resolve_at(5.0)["tick1"] == 2
    assert store.resolve_at(("tick", 3))["tick1"] == 2
    assert store.resolve_at(("tick", 4))["tick1"] == 4
    # window resolution: shards SAMPLING [t0, t1]
    assert [e["tick1"] for e in store.resolve_window(15.0, 35.0)] \
        == [2, 4]
    assert [e["tick1"] for e in store.resolve_window(25.0, 35.0)] \
        == [4]


def test_shard_store_sweeps_orphans(tmp_path):
    store = ShardStore(tmp_path / "sh")
    store.add_shard(level="raw", tick0=0, tick1=2, t0=1.0, t1=2.0,
                    state_leaves=[np.arange(2)], dep_leaves=[],
                    columns={}, wal_pos=(0, 50))
    # a crash mid-write strands a tmp; a crash between shard rename
    # and manifest rewrite strands an unreferenced shard file
    (store.dir / "gyt_shard_raw_00000099_00000100.tmp.npz").write_bytes(
        b"torn")
    (store.dir / "gyt_shard_raw_00000004_00000006.npz").write_bytes(
        b"orphan - manifest never saw it")
    store2 = ShardStore(store.dir)
    assert store2.sweep_stale_tmp() == 2
    files = {p.name for p in store.dir.glob("*.npz")}
    assert files == {"gyt_shard_raw_00000000_00000002.npz"}
    assert len(store2.shards()) == 1      # manifest untouched


# -------------------------------------------------------- replay parity
def test_compactor_replay_parity_bit_identical(tmp_path):
    """The flagship contract: compacted shard state at tick T ==
    live engine state at T, bit for bit (state AND dep), and at=
    queries serve rows identical to the live query at that instant."""
    rt = Runtime(CFG, _opts(tmp_path))
    sim = ParthaSim(n_hosts=8, n_svcs=4, seed=7)
    rt.feed(sim.name_frames())
    _drive(rt, sim, 4)
    live_state = _leaves(rt.state)
    live_dep = _leaves(rt.dep)
    live_rows = rt.query({"subsys": "svcstate", "maxrecs": 100,
                          "sortcol": "qps5s"})["recs"]
    live_topk = rt.query({"subsys": "topk", "maxrecs": 50})["recs"]

    c = Compactor(CFG, rt.opts, journal=rt.journal, stats=rt.stats)
    rep = c.compact_once(seal=True, upto_tick=rt._tick_no)
    assert rep["windows"] == 2
    assert rep["records"] > 0
    ent = [e for e in c.store.shards("raw") if e["tick1"] == 4][0]
    data = c.store.load(ent)
    _assert_leaves_equal(data["state"], live_state, "state")
    _assert_leaves_equal(data["dep"], live_dep, "dep")

    # at=-pinned queries equal the live snapshot taken at the same tick
    at_rows = rt.query({"subsys": "svcstate", "at": "tick:4",
                        "maxrecs": 100, "sortcol": "qps5s"})
    assert at_rows["recs"] == live_rows
    assert at_rows["tick"] == 4
    at_topk = rt.query({"subsys": "topk", "at": "tick:4",
                        "maxrecs": 50})["recs"]
    assert at_topk == live_topk
    assert at_topk and all("errbound" in r for r in at_topk)
    # the flagship metric landed in the live registry
    assert rt.stats.counters["compact_shards"] >= 2
    assert "compact_replay_ev_per_sec" in rt.stats.gauges
    c.close()
    rt.close()


@pytest.mark.slow
def test_compactor_restart_resume(tmp_path):
    """A fresh Compactor (process restart) re-seeds its replay engine
    from the newest raw shard and continues from the shard's recorded
    WAL position — parity still holds at the final tick.

    Slow tier for its wall clock only (two compactors, four driven
    ticks); the resumed numpy-leaf state goes through cache-reloaded
    donating programs, cold and warm alike."""
    rt = Runtime(CFG, _opts(tmp_path))
    sim = ParthaSim(n_hosts=8, n_svcs=4, seed=11)
    rt.feed(sim.name_frames())
    _drive(rt, sim, 2)
    c1 = Compactor(CFG, rt.opts, journal=rt.journal, stats=rt.stats)
    rep1 = c1.compact_once(seal=True, upto_tick=rt._tick_no)
    assert rep1["windows"] == 1
    c1.close()

    _drive(rt, sim, 2)
    live_state = _leaves(rt.state)
    # NEW instance: resume path (shard-as-checkpoint)
    c2 = Compactor(CFG, rt.opts, journal=rt.journal, stats=rt.stats)
    rep2 = c2.compact_once(seal=True, upto_tick=rt._tick_no)
    assert rep2["windows"] == 1
    ent = [e for e in c2.store.shards("raw") if e["tick1"] == 4][0]
    _assert_leaves_equal(c2.store.load(ent)["state"], live_state,
                         "state after resume")
    # journal handoff: the compactor's floor holds segments back from
    # checkpoint truncation until consumed
    pos = c2.store.position()
    assert pos is not None and rt.journal._truncate_floor == pos[0]
    c2.close()
    rt.close()


def test_sigkill_mid_compaction_manifest_consistent(tmp_path):
    """Kill the compactor at EVERY window boundary (exception injected
    inside the shard-write sequence = the process dying there): the
    manifest stays consistent (never names a missing/torn file), and a
    fresh compactor sweeps the debris and converges to the same final
    state."""
    rt = Runtime(CFG, _opts(tmp_path))
    sim = ParthaSim(n_hosts=8, n_svcs=4, seed=13)
    rt.feed(sim.name_frames())
    _drive(rt, sim, 4)
    live_state = _leaves(rt.state)

    class Boom(RuntimeError):
        pass

    crashes = 0
    while True:
        c = Compactor(CFG, rt.opts, journal=rt.journal)
        orig = c.store.add_shard
        calls = {"n": 0}

        def dying_add(*a, **kw):
            calls["n"] += 1
            if calls["n"] == 1 and crashes < 2:
                # die mid-sequence: tmp file written, manifest not —
                # exactly what a SIGKILL between fsync and rename (or
                # rename and manifest rewrite) leaves behind
                tmp = c.store.dir / "gyt_shard_raw_99999998_99999999" \
                    ".tmp.npz"
                tmp.write_bytes(b"partial write")
                raise Boom()
            return orig(*a, **kw)

        c.store.add_shard = dying_add
        try:
            c.compact_once(seal=True, upto_tick=rt._tick_no)
        except Boom:
            crashes += 1
            # manifest must be readable and name only existing files
            m = c.store.manifest()
            for e in m["shards"]:
                assert (c.store.dir / e["file"]).exists()
            c.close()
            continue
        c.close()
        break
    assert crashes == 2
    store = ShardStore(rt.opts.hist_shard_dir)
    assert not list(store.dir.glob("*.tmp.npz"))   # swept on start
    ent = [e for e in store.shards("raw") if e["tick1"] == 4][0]
    _assert_leaves_equal(store.load(ent)["state"], live_state,
                         "state after crash-recompaction")
    rt.close()


# ------------------------------------------------- retention / downsample
def test_retention_downsamples_raw_to_mid(tmp_path):
    opts = _opts(tmp_path, hist_window_ticks=1, hist_mid_every=2,
                 hist_retain_raw=2, hist_hour_every=2,
                 hist_retain_mid=50, hist_retain_hour=10)
    rt = Runtime(CFG, opts)
    sim = ParthaSim(n_hosts=8, n_svcs=4, seed=17)
    rt.feed(sim.name_frames())
    _drive(rt, sim, 6)
    c = Compactor(CFG, opts, journal=rt.journal, stats=rt.stats)
    c.compact_once(seal=True, upto_tick=rt._tick_no)
    store = c.store
    raws = store.shards("raw")
    mids = store.shards("mid")
    assert mids, "old raw shards must downsample into mid shards"
    assert len(raws) <= 4                    # retention bounded raws
    assert rt.stats.counters["compact_downsampled"] >= 1
    # every manifest entry exists on disk; no unreferenced shards
    named = {e["file"] for e in store.shards()}
    on_disk = {p.name for p in store.dir.glob("gyt_shard_*.npz")}
    assert named == on_disk
    # merged shard: tick range spans its members, columns aggregated
    m0 = mids[0]
    assert m0["tick1"] - m0["tick0"] == 2
    cols, mask = store.load(m0)["columns"]["svcstate"]
    assert mask.any() and len(cols["svcid"]) == int(mask.sum())
    # downsampled state still materializes for at= (sketch-merge = the
    # newest member's monotone sketch state)
    out = rt.query({"subsys": "topk", "at": f"tick:{m0['tick1']}"})
    assert out["nrecs"] > 0
    c.close()
    rt.close()


# --------------------------------------------------------------- windows
def test_windowed_queries_and_alertdef(tmp_path):
    rt = Runtime(CFG, _opts(tmp_path))
    sim = ParthaSim(n_hosts=8, n_svcs=4, seed=19)
    rt.feed(sim.name_frames())
    _drive(rt, sim, 4)
    c = Compactor(CFG, rt.opts, journal=rt.journal, stats=rt.stats)
    c.compact_once(seal=True, upto_tick=rt._tick_no)

    # windowed svcstate: per-entity aggregate across both shards
    out = rt.query({"subsys": "svcstate", "window": "1h",
                    "maxrecs": 100})
    assert out["shards"] == 2
    assert out["nrecs"] == 32                  # 8 hosts × 4 svcs
    # hand-check the mean: qps5s of one svc across the two snapshots
    s1, s2 = [c.store.load(e)["columns"]["svcstate"]
              for e in c.store.shards("raw")]
    svcid = s2[0]["svcid"][np.nonzero(s2[1])[0][0]]
    want = np.mean([float(s[0]["qps5s"][list(s[0]["svcid"]).index(
        svcid)]) for s in (s1, s2)])
    got = [r for r in out["recs"] if r["svcid"] == svcid][0]["qps5s"]
    assert got == pytest.approx(want, abs=5e-4)   # row_to_json rounds

    # windowed topk: bound-annotated rows, value within ±errbound of a
    # diff of two upper bounds by construction
    tk = rt.query({"subsys": "topk", "window": "1h", "maxrecs": 50})
    assert tk["nrecs"] > 0
    assert all("errbound" in r and r["value"] > 0 for r in tk["recs"])

    # filters and sorts run on the windowed columns through the same
    # engine (criteria on aggregated values)
    f = rt.query({"subsys": "svcstate", "window": "1h",
                  "filter": "{ svcstate.qps5s > 0 }",
                  "sortcol": "qps5s", "maxrecs": 5})
    assert 0 < f["nrecs"] <= 5

    # windowed alertdef: evaluates against the aggregate and fires
    rt.alerts.add_def({"alertname": "win-qps", "subsys": "svcstate",
                       "filter": "{ svcstate.qps5s >= 0 }",
                       "window": "1h"})
    fired = rt.alerts.check(rt.state, columns_fn=rt._alert_columns)
    assert any(a.alertname == "win-qps" for a in fired)
    c.close()
    rt.close()


def _capture_leaf(rt, name):
    from gyeeta_tpu.history import winquant as WQ
    return WQ.leaf_of(rt.state, name).astype(np.float32).copy()


def test_windowed_quantiles_match_offline_exact_merge(tmp_path):
    """ISSUE 14 flagship: ``window=`` p50/p95/p99 equal the quantile
    of the OFFLINE EXACT MERGE over the same event stream — the
    monotone resp loghist captured live at every window boundary is
    that exact merge (per-window delta sums telescope to boundary
    differences). Checked on svcstate (per-svc resp), tracereq
    (per-API latency) and taskstate (cpup95), full range AND a
    single-window partial range."""
    from gyeeta_tpu.history import winquant as WQ
    from gyeeta_tpu.query.api import _hex_id

    rt = Runtime(CFG, _opts(tmp_path))
    sim = ParthaSim(n_hosts=8, n_svcs=4, seed=41)
    rt.feed(sim.name_frames())
    caps = {0: {n: _capture_leaf(rt, n) for n in WQ.DELTA_SPECS}}
    for _ in range(4):
        rt.feed(sim.conn_frames(256) + sim.resp_frames(512)
                + sim.listener_frames() + sim.task_frames()
                + sim.trace_frames(128))
        rt.run_tick()
        if rt._tick_no % 2 == 0:
            caps[rt._tick_no] = {n: _capture_leaf(rt, n)
                                 for n in WQ.DELTA_SPECS}
    svcids = _hex_id(np.asarray(rt.state.tbl.key_hi),
                     np.asarray(rt.state.tbl.key_lo))
    c = Compactor(CFG, rt.opts, journal=rt.journal, stats=rt.stats)
    c.compact_once(seal=True, upto_tick=rt._tick_no)

    def quant(hist, spec, q, scale):
        # float() before the scale division — the serving path divides
        # in float64 (np.asarray(vals, float64) / scale)
        return float(WQ.np_hist_quantiles(
            np.asarray(hist, np.float32)[None, :], spec,
            [q])[0, 0]) / scale

    # --- svcstate: per-svc p50/p95/p99 over the full range
    win = rt.query({"subsys": "svcstate", "window": "1h",
                    "maxrecs": 100})
    exp = caps[4]["svc_resp"] - caps[0]["svc_resp"]
    by_id = {svcids[i]: i for i in range(len(svcids))}
    checked = 0
    for r in win["recs"]:
        i = by_id.get(r["svcid"])
        if i is None or exp[i].sum() == 0:
            continue
        for field, q in (("p99resp5s", 0.99), ("p95resp5s", 0.95),
                         ("p50resp5d", 0.50)):
            assert r[field] == pytest.approx(
                quant(exp[i], CFG.resp_spec, q, 1e3), abs=5e-4), field
        # p99 >= p95 >= p50: a real quantile set, not a mean
        assert r["p99resp5s"] >= r["p95resp5s"] >= r["p50resp5d"]
        checked += 1
    assert checked >= 8

    # --- partial range (second window only): per-window attribution
    ents = c.store.shards("raw")
    mid = (max(ents[0]["t1"], ents[1]["t0"]) + ents[1]["t1"]) / 2.0 \
        if ents[1]["t0"] > ents[0]["t1"] \
        else (ents[0]["t1"] + ents[1]["t1"]) / 2.0
    win2 = rt.query({"subsys": "svcstate", "tstart": mid,
                     "tend": ents[-1]["t1"] + 1.0, "maxrecs": 100})
    assert win2["shards"] == 1
    exp2 = caps[4]["svc_resp"] - caps[2]["svc_resp"]
    checked = 0
    for r in win2["recs"]:
        i = by_id.get(r["svcid"])
        if i is None or exp2[i].sum() == 0:
            continue
        assert r["p99resp5s"] == pytest.approx(
            quant(exp2[i], CFG.resp_spec, 0.99, 1e3), abs=5e-4)
        checked += 1
    assert checked >= 4

    # --- tracereq p99resp: multiset of per-API quantiles must match
    tr = rt.query({"subsys": "tracereq", "window": "1h",
                   "maxrecs": 200, "filter": "{ tracereq.nreq > 0 }"})
    expt = caps[4]["api_resp"] - caps[0]["api_resp"]
    want = sorted(round(quant(h, CFG.apiresp_spec, 0.99, 1e3), 3)
                  for h in expt if h.sum() > 0)
    got = sorted(r["p99resp"] for r in tr["recs"])
    assert got == pytest.approx(want, abs=5e-4)

    # --- taskstate cpup95 from the task_cpu delta panel
    tk = rt.query({"subsys": "taskstate", "window": "1h",
                   "maxrecs": 200})
    expc = caps[4]["task_cpu"] - caps[0]["task_cpu"]
    wantc = sorted(round(quant(h, CFG.taskcpu_spec, 0.95, 1.0), 3)
                   for h in expc if h.sum() > 0)
    gotc = sorted(r["cpup95"] for r in tk["recs"]
                  if r["cpup95"] > 0)
    assert gotc == pytest.approx(
        [w for w in wantc if w > 0], abs=5e-4)

    # windowed QUANTILE alertdef: p99 criteria over the window fire
    rt.alerts.add_def({"alertname": "win-p99", "subsys": "svcstate",
                       "filter": "{ svcstate.p99resp5s > 0 }",
                       "window": "1h"})
    fired = rt.alerts.check(rt.state, columns_fn=rt._alert_columns)
    assert any(a.alertname == "win-p99" for a in fired)
    c.close()
    rt.close()


def test_windowed_quantile_unsupported_rejected_counted(tmp_path):
    """Satellite: shards WITHOUT delta panels (pre-ISSUE-14 stores)
    must REJECT windowed quantile references at validation time —
    counted — and omit the fields from implicit projections; never
    serve the old silent mean-of-snapshots."""
    opts = _opts(tmp_path)
    store = ShardStore(opts.hist_shard_dir)
    cols = {"svcid": np.array(["aa", "bb"], object),
            "svcname": np.array(["s1", "s2"], object),
            "qps5s": np.array([1.0, 2.0]),
            "p99resp5s": np.array([10.0, 20.0]),
            "hostid": np.array([0.0, 1.0])}
    for k, (t0, t1) in enumerate(((10.0, 20.0), (20.0, 30.0))):
        store.add_shard(level="raw", tick0=k * 2, tick1=k * 2 + 2,
                        t0=t0, t1=t1, state_leaves=[], dep_leaves=[],
                        columns={"svcstate":
                                 (cols, np.ones(2, bool))},
                        wal_pos=(0, 100 * (k + 1)))
    rt = Runtime(CFG, opts)
    # explicit reference (projection / sort / filter / aggr) → reject
    for req in (
            {"columns": ["svcid", "p99resp5s"]},
            {"sortcol": "p99resp5s"},
            {"filter": "{ svcstate.p99resp5s > 5 }"},
            {"aggr": ["max(p99resp5s)"]}):
        with pytest.raises(ValueError, match="windowed quantile"):
            rt.query({"subsys": "svcstate", "window": "1h", **req})
    assert rt.stats.counters["windowed_quant_rejected"] == 4
    # implicit full projection: field OMITTED (counted), row served
    out = rt.query({"subsys": "svcstate", "window": "1h",
                    "maxrecs": 10})
    assert out["nrecs"] == 2
    assert all("p99resp5s" not in r for r in out["recs"])
    assert all(r["qps5s"] > 0 for r in out["recs"])
    assert rt.stats.counters["windowed_quant_fields_omitted"] > 0
    # non-quantile references still work
    f = rt.query({"subsys": "svcstate", "window": "1h",
                  "sortcol": "qps5s", "maxrecs": 10})
    assert f["nrecs"] == 2
    # a windowed QUANTILE alertdef over the delta-less store skips
    # COUNTED instead of breaking the whole alert pass
    rt.alerts.add_def({"alertname": "stale-p99", "subsys": "svcstate",
                       "filter": "{ svcstate.p99resp5s > 1 }",
                       "window": "1h"})
    skipped0 = rt.alerts.stats["nwindow_skipped"]
    fired = rt.alerts.check(rt.state, columns_fn=rt._alert_columns)
    assert not any(a.alertname == "stale-p99" for a in fired)
    assert rt.alerts.stats["nwindow_skipped"] == skipped0 + 1
    rt.close()


def test_delta_panel_roundtrip_and_downsample_merge(tmp_path):
    """Delta panels survive the npz roundtrip (keys, histograms, the
    derived t-digest) and the raw→mid downsample SUMS them (additive
    partial aggregates — windowed quantiles keep full fidelity over
    downsampled shards)."""
    from gyeeta_tpu.history import winquant as WQ

    opts = _opts(tmp_path, hist_window_ticks=1, hist_mid_every=2,
                 hist_retain_raw=2, hist_retain_mid=50,
                 hist_retain_hour=10)
    rt = Runtime(CFG, opts)
    sim = ParthaSim(n_hosts=8, n_svcs=4, seed=43)
    rt.feed(sim.name_frames())
    _drive(rt, sim, 6)
    final = _capture_leaf(rt, "svc_resp")
    c = Compactor(CFG, opts, journal=rt.journal, stats=rt.stats)
    c.compact_once(seal=True, upto_tick=rt._tick_no)
    mids = c.store.shards("mid")
    raws = c.store.shards("raw")
    assert mids
    d = c.store.load(mids[0])["deltas"]
    assert "svc_resp" in d and "td" in d["svc_resp"]
    assert len(d["svc_resp"]["key"]) == len(d["svc_resp"]["hist"])
    # td panel: per-row weights equal the histogram mass
    td = d["svc_resp"]["td"]
    assert np.allclose(td["weights"].sum(axis=1),
                       d["svc_resp"]["hist"].sum(axis=1), rtol=1e-5)
    # sum of EVERY surviving delta panel == the final monotone state
    # (nothing lost through downsampling)
    parts = [(c.store.load(e)["deltas"]["svc_resp"]["key"],
              c.store.load(e)["deltas"]["svc_resp"]["hist"])
             for e in mids + raws]
    keys, merged = WQ.merge_delta_rows(parts)
    assert float(merged.sum()) == pytest.approx(float(final.sum()),
                                                rel=1e-6)
    c.close()
    rt.close()


def test_timeview_errors_without_shards(tmp_path):
    rt = Runtime(CFG, RuntimeOpts(dep_pair_capacity=1024,
                                  dep_edge_capacity=512))
    with pytest.raises(ValueError, match="time-travel"):
        rt.query({"subsys": "svcstate", "at": "tick:1"})
    rt.close()
    rt2 = Runtime(CFG, _opts(tmp_path))
    with pytest.raises(ValueError, match="no history shards"):
        rt2.query({"subsys": "svcstate", "at": "tick:1"})
    # registry-backed views have no historical source → clean error
    sim = ParthaSim(n_hosts=8, n_svcs=4, seed=23)
    _drive(rt2, sim, 2)
    c = Compactor(CFG, rt2.opts, journal=rt2.journal)
    c.compact_once(seal=True, upto_tick=rt2._tick_no)
    with pytest.raises(ValueError, match="not available historically"):
        rt2.query({"subsys": "svcinfo", "at": "tick:2"})
    c.close()
    rt2.close()


# --------------------------------------------------------- history writer
class _SlowStore:
    """write() blocks until released — the 'stalled DB' the satellite
    moves off the fold thread."""

    def __init__(self):
        import threading
        self.gate = threading.Event()
        self.writes = []

    def write(self, subsys, t, rows):
        self.gate.wait(timeout=10.0)
        self.writes.append((subsys, t, len(rows)))
        return len(rows)


def test_history_writer_bounded_queue_and_barrier():
    from gyeeta_tpu.history.histwriter import HistoryWriter
    from gyeeta_tpu.utils.selfstats import Stats

    store = _SlowStore()
    stats = Stats()
    hw = HistoryWriter(store, stats=stats, max_queue=2)
    import time as _t
    # first sweep is picked up by the worker and BLOCKS in the store;
    # the queue then holds at most max_queue sweeps, dropping oldest
    hw.write_sweep([("svcstate", 1.0, [{"a": 1}] * 3)])
    deadline = _t.monotonic() + 5.0
    while not hw._busy and _t.monotonic() < deadline:
        _t.sleep(0.005)
    for i in range(4):
        hw.write_sweep([("svcstate", 2.0 + i, [{"a": 1}] * 2)])
    assert stats.counters["history_write_dropped"] == 2
    assert stats.counters["history_write_dropped_rows"] == 4
    assert stats.gauges["history_write_queue_depth"] == 2.0
    store.gate.set()                       # DB unstalls
    assert hw.barrier(timeout=10.0)
    assert stats.counters["history_write_sweeps"] == 3   # 1 + kept 2
    hw.close()
    # enqueue after close is a silent no-op (shutdown path)
    hw.write_sweep([("svcstate", 9.0, [])])


def test_run_tick_history_is_async_but_queries_read_their_writes(
        tmp_path):
    """run_tick no longer blocks on SQL; a historical query right after
    the tick still sees the tick's sweep (barrier read-your-writes)."""
    opts = RuntimeOpts(history_db=str(tmp_path / "h.db"),
                       history_every_ticks=1,
                       dep_pair_capacity=1024, dep_edge_capacity=512)
    rt = Runtime(CFG, opts)
    sim = ParthaSim(n_hosts=8, n_svcs=4, seed=29)
    _drive(rt, sim, 2)
    assert rt.stats.counters.get("history_write_sweeps", 0) >= 0
    hist = rt.query({"subsys": "svcstate", "tstart": 0,
                     "tend": 4e9})
    assert len(hist["recs"]) == 64            # 2 sweeps × 32 services
    rt.close()
    assert rt.stats.counters["history_write_sweeps"] == 2


# --------------------------------------------------------- sharded (slow)
@pytest.mark.slow
def test_sharded_replay_parity_and_time_travel(tmp_path):
    """The same replay-parity + at=/window= contract on the mesh tier:
    the compactor replays through a ShardedRuntime factory and the
    shard-materialized stacked state is bit-identical; historical
    queries ride the parameterized merged-columns path."""
    from gyeeta_tpu.parallel.mesh import make_mesh
    from gyeeta_tpu.parallel.shardedrt import ShardedRuntime

    from gyeeta_tpu.history import winquant as WQ
    from gyeeta_tpu.query.api import _hex_id

    opts = _opts(tmp_path)
    srt = ShardedRuntime(CFG, make_mesh(8), opts)
    sim = ParthaSim(n_hosts=8, n_svcs=4, seed=31)
    srt.feed(sim.name_frames())
    base_resp = WQ.leaf_of(srt.state, "svc_resp").copy()
    _drive(srt, sim, 4)
    live_state = _leaves(srt.state)
    live_resp = WQ.leaf_of(srt.state, "svc_resp").copy()
    live_rows = srt.query({"subsys": "svcstate", "maxrecs": 100,
                           "sortcol": "qps5s"})["recs"]

    c = Compactor(CFG, opts, journal=srt.journal, stats=srt.stats,
                  runtime_factory=lambda cfg, o: ShardedRuntime(
                      cfg, make_mesh(8), o))
    rep = c.compact_once(seal=True, upto_tick=srt._tick_no)
    assert rep["windows"] == 2
    ent = [e for e in c.store.shards("raw") if e["tick1"] == 4][0]
    _assert_leaves_equal(c.store.load(ent)["state"], live_state,
                         "sharded state")
    at_rows = srt.query({"subsys": "svcstate", "at": "tick:4",
                         "maxrecs": 100, "sortcol": "qps5s"})["recs"]
    assert at_rows == live_rows
    tk = srt.query({"subsys": "topk", "window": "1h", "maxrecs": 20})
    assert tk["nrecs"] > 0
    assert all("errbound" in r for r in tk["recs"])

    # windowed quantiles on the MESH tier equal the offline exact
    # merge (the stacked monotone leaf captured live, shard-major)
    win = srt.query({"subsys": "svcstate", "window": "1h",
                     "maxrecs": 100})
    exp = (live_resp - base_resp).astype(np.float32)
    key_hi = np.asarray(srt.state.tbl.key_hi).reshape(-1)
    key_lo = np.asarray(srt.state.tbl.key_lo).reshape(-1)
    by_id = {s: i for i, s in enumerate(_hex_id(key_hi, key_lo))}
    checked = 0
    for r in win["recs"]:
        i = by_id.get(r["svcid"])
        if i is None or exp[i].sum() == 0:
            continue
        want = float(WQ.np_hist_quantiles(
            exp[i][None, :], CFG.resp_spec, [0.99])[0, 0]) / 1e3
        assert r["p99resp5s"] == pytest.approx(want, abs=5e-4)
        assert r["p99resp5s"] >= r["p95resp5s"] >= r["p50resp5d"]
        checked += 1
    assert checked >= 8
    c.close()
    srt.close()


def test_cli_compact_offline(tmp_path):
    """`gyeeta_tpu compact` batch form: journal dir in, shards out,
    manifest listable — no serving process required."""
    opts = _opts(tmp_path)
    rt = Runtime(CFG, opts)
    sim = ParthaSim(n_hosts=8, n_svcs=4, seed=37)
    _drive(rt, sim, 2)
    rt.close()                    # journal closed → all segments sealed

    from gyeeta_tpu import cli
    cfg_file = tmp_path / "cfg.json"
    cfg_file.write_text(json.dumps({"engine": {
        "n_hosts": 8, "svc_capacity": 64, "task_capacity": 64,
        "conn_batch": 128, "resp_batch": 256, "fold_k": 2}}))
    import io
    import contextlib
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        cli.main(["compact", "--journal-dir", str(tmp_path / "wal"),
                  "--shard-dir", str(tmp_path / "shards"),
                  "--config", str(cfg_file), "--window-ticks", "2",
                  "--upto-tick", "2"])
    rep = json.loads(buf.getvalue())
    assert rep["windows"] == 1 and rep["records"] > 0
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        cli.main(["compact", "list",
                  "--shard-dir", str(tmp_path / "shards")])
    listing = json.loads(buf.getvalue())
    assert len(listing["shards"]) == 1
    assert os.path.exists(tmp_path / "shards"
                          / listing["shards"][0]["file"])


# -------------------------------------- windowed-aggregation vectorization
def _synth_parts(n_entities, n_parts, seed=0, subsys="svcstate"):
    """Randomized (cols, mask) parts shaped like stored svcstate
    panels: str identity cols + numeric cols + churn in the mask."""
    rng = np.random.default_rng(seed)
    parts = []
    ids = np.array([f"{i:016x}" for i in range(n_entities)], object)
    names = np.array([f"svc-{i % 97}" for i in range(n_entities)],
                     object)
    for p in range(n_parts):
        cols = {
            "svcid": ids,
            "svcname": names,
            "qps5s": rng.uniform(0, 100, n_entities),
            "nconns": rng.integers(0, 50, n_entities).astype(
                np.float64),
            "state": rng.integers(0, 5, n_entities).astype(np.int32),
            "hostid": (np.arange(n_entities) % 8).astype(np.float64),
        }
        mask = rng.uniform(size=n_entities) > 0.3
        parts.append((cols, mask))
    return parts


def test_window_aggregation_vectorized_parity():
    """ROADMAP history item (a): the np.unique/segment-sum window
    aggregator is bit-identical to the reference keyed loop —
    including first-appearance row order, per-entity means, and
    last-observation semantics — plus the key-less positional path."""
    from gyeeta_tpu.history import timeview as TV

    parts = _synth_parts(500, 4, seed=3)
    # entity churn: a part with rows the others never see
    extra = _synth_parts(520, 1, seed=9)[0]
    parts.insert(2, extra)
    got, gmask = TV.aggregate_window_columns("svcstate", parts)
    ref, rmask = TV.aggregate_window_columns_ref("svcstate", parts)
    assert list(got) == list(ref)
    assert np.array_equal(gmask, rmask)
    for c in ref:
        if ref[c].dtype == object:
            assert got[c].tolist() == ref[c].tolist(), c
        else:
            assert np.array_equal(got[c], ref[c]), c

    # multi-key subsystem (tracereq: svcid+svcname+api identity)
    rng = np.random.default_rng(5)
    tparts = []
    for p in range(3):
        n = 200
        cols = {
            "svcid": np.array([f"{i % 40:016x}" for i in range(n)],
                              object),
            "svcname": np.array([f"s{i % 40}" for i in range(n)],
                                object),
            "api": np.array([f"GET /api/{i % 13}" for i in range(n)],
                            object),
            "nreq": rng.uniform(0, 1e6, n),
            "p99resp": rng.uniform(0, 1e3, n),
            "hostid": (np.arange(n) % 8).astype(np.float64),
        }
        tparts.append((cols, rng.uniform(size=n) > 0.2))
    got, _ = TV.aggregate_window_columns("tracereq", tparts)
    ref, _ = TV.aggregate_window_columns_ref("tracereq", tparts)
    for c in ref:
        if ref[c].dtype == object:
            assert got[c].tolist() == ref[c].tolist(), c
        else:
            assert np.array_equal(got[c], ref[c]), c

    # key-less positional path (clusterstate) + all-masked-out parts
    cparts = [({"nhosts": np.arange(4.0), "state": np.ones(4, np.int32)},
               np.zeros(4, bool)),
              ({"nhosts": np.arange(4.0) * 2,
                "state": np.full(4, 2, np.int32)},
               np.ones(4, bool))]
    got, gmask = TV.aggregate_window_columns("clusterstate", cparts)
    ref, rmask = TV.aggregate_window_columns_ref("clusterstate", cparts)
    assert np.array_equal(gmask, rmask)
    for c in ref:
        assert np.array_equal(got[c], ref[c]), c

    # empty window (every row masked out on a keyed subsystem)
    eparts = [(parts[0][0], np.zeros(500, bool))]
    got, gmask = TV.aggregate_window_columns("svcstate", eparts)
    ref, rmask = TV.aggregate_window_columns_ref("svcstate", eparts)
    assert len(gmask) == len(rmask) == 0
    for c in ref:
        assert got[c].dtype == ref[c].dtype and len(got[c]) == 0, c
