"""Distributed history compaction (ISSUE 14): parallel per-shard WAL
replay workers + the parted shard store they produce.

Contracts exercised here:
- BIT IDENTITY: ``--compact-procs N`` output equals ``--compact-procs
  1`` for every N (per-part state/dep/column/delta arrays and the root
  manifest's window structure) — the per-shard decomposition is the
  canonical unit of work, worker count only moves the wall clock;
- QUERY PARITY: at=/window= queries over the parted store match a
  single-runtime control fold of the same event stream (per-entity
  values exactly; windowed quantiles equal the offline exact
  delta-merge);
- CRASH SAFETY: a worker killed (os._exit — no cleanup, the SIGKILL
  shape) at EVERY worker boundary leaves the root manifest consistent
  (old view, never a window some part lacks) and recompaction
  converges bit-identically;
- GUARDS: flat WALs and procs > shard count are rejected at
  construction.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from gyeeta_tpu.engine.aggstate import EngineCfg
from gyeeta_tpu.history import shards as SH, winquant as WQ
from gyeeta_tpu.history.compactproc import ParallelCompactor
from gyeeta_tpu.runtime import Runtime
from gyeeta_tpu.sim.partha import ParthaSim
from gyeeta_tpu.utils import journal as J
from gyeeta_tpu.utils.config import RuntimeOpts
from gyeeta_tpu.utils.selfstats import Stats

CFG = EngineCfg(n_hosts=8, svc_capacity=64, task_capacity=64,
                conn_batch=128, resp_batch=256, fold_k=2)
NSHARDS = 2
TICKS = 4
WINDOW_TICKS = 2


def _sims():
    return [ParthaSim(n_hosts=4, n_svcs=2, seed=100 + s,
                      host_base=s * 4) for s in range(NSHARDS)]


def _tick_frames(sim):
    return (sim.conn_frames(128) + sim.resp_frames(256)
            + sim.listener_frames() + sim.task_frames())


def _make_sharded_wal(wal: str) -> None:
    """A sharded WAL without a serving process: per-shard journals,
    host-disjoint sims, chunk tick stamps advancing on the shared
    global cadence — exactly the layout ``serve --shards`` writes."""
    for s, sim in enumerate(_sims()):
        j = J.Journal(os.path.join(wal, f"shard_{s:02d}"))
        j.append(sim.name_frames(), hid=s * 4, tick=0)
        for t in range(TICKS):
            j.append(_tick_frames(sim), hid=s * 4, tick=t)
        j.close()


def _opts(shard_dir) -> RuntimeOpts:
    return RuntimeOpts(hist_shard_dir=str(shard_dir),
                       hist_window_ticks=WINDOW_TICKS,
                       dep_pair_capacity=1024, dep_edge_capacity=512)


@pytest.fixture(scope="module")
def parted(tmp_path_factory):
    """One WAL, compacted twice (procs=1 and procs=2) + a control
    single-runtime fold of the SAME stream with monotone-leaf
    snapshots captured at every window boundary (the offline exact
    merge the windowed quantiles must equal)."""
    base = tmp_path_factory.mktemp("compactproc")
    wal = str(base / "wal")
    _make_sharded_wal(wal)

    reps = {}
    for procs, name in ((1, "sh1"), (2, "sh2")):
        pc = ParallelCompactor(CFG, _opts(base / name), procs,
                               journal_dir=wal,
                               shard_dir=str(base / name),
                               stats=Stats())
        reps[procs] = pc.compact_once(upto_tick=TICKS)
        pc.close()

    # control: ONE runtime folds the union in tick order (chunk
    # sub-order per shard preserved); capture the monotone resp leaf
    # at every window boundary
    rt = Runtime(CFG, RuntimeOpts(dep_pair_capacity=1024,
                                  dep_edge_capacity=512))
    sims = _sims()
    for sim in sims:
        rt.feed(sim.name_frames())
    captures = {0: np.asarray(rt.state.resp_win.alltime).copy()}
    for t in range(TICKS):
        for sim in sims:
            rt.feed(_tick_frames(sim))
        rt.run_tick()
        if rt._tick_no % WINDOW_TICKS == 0:
            captures[rt._tick_no] = np.asarray(
                rt.state.resp_win.alltime).copy()
    from gyeeta_tpu.query.api import _hex_id
    svcids = _hex_id(np.asarray(rt.state.tbl.key_hi),
                     np.asarray(rt.state.tbl.key_lo))
    live = np.asarray(
        (rt.state.tbl.key_hi != np.uint32(0xFFFFFFFF))
        | (rt.state.tbl.key_lo != np.uint32(0xFFFFFFFF)))
    control_rows = rt.query({"subsys": "svcstate", "maxrecs": 100,
                             "sortcol": "svcid",
                             "consistency": "strong"})["recs"]
    rt.close()
    return {"base": base, "wal": wal, "reps": reps,
            "captures": captures, "svcids": svcids, "live": live,
            "control_rows": control_rows}


def test_parallel_bit_identical_any_worker_count(parted):
    s1 = SH.open_shard_store(parted["base"] / "sh1")
    s2 = SH.open_shard_store(parted["base"] / "sh2")
    assert isinstance(s1, SH.PartedShardStore)
    assert isinstance(s2, SH.PartedShardStore)
    e1, e2 = s1.shards(), s2.shards()
    assert [(e["level"], e["tick0"], e["tick1"]) for e in e1] \
        == [(e["level"], e["tick0"], e["tick1"]) for e in e2]
    assert len(e1) == TICKS // WINDOW_TICKS
    for a, b in zip(e1, e2):
        assert len(a["parts"]) == len(b["parts"]) == NSHARDS
        for p in range(NSHARDS):
            da = s1.load_part(p, a["parts"][p])
            db = s2.load_part(p, b["parts"][p])
            for i, (x, y) in enumerate(zip(da["state"], db["state"])):
                assert np.array_equal(x, y), f"state leaf {i} part {p}"
            for i, (x, y) in enumerate(zip(da["dep"], db["dep"])):
                assert np.array_equal(x, y), f"dep leaf {i} part {p}"
            assert set(da["columns"]) == set(db["columns"])
            for sub in da["columns"]:
                ca, ma = da["columns"][sub]
                cb, mb = db["columns"][sub]
                assert np.array_equal(ma, mb)
                for c in ca:
                    if ca[c].dtype == object:
                        assert ca[c].tolist() == cb[c].tolist()
                    else:
                        assert np.array_equal(ca[c], cb[c]), (sub, c)
            assert set(da["deltas"]) == set(db["deltas"]) != set()
            for n in da["deltas"]:
                assert np.array_equal(da["deltas"][n]["hist"],
                                      db["deltas"][n]["hist"])
                assert da["deltas"][n]["key"].tolist() \
                    == db["deltas"][n]["key"].tolist()
    # per-shard resume positions recorded as [shard, seg, off] triples
    pos = s1.position()
    assert pos and all(len(p) == 3 for p in pos)
    assert parted["reps"][2]["workers"] == 2
    assert parted["reps"][1]["records"] \
        == parted["reps"][2]["records"] > 0


def test_parted_store_queries_match_control_fold(parted):
    """at= rows over the parted store equal the live control fold's
    rows (per-entity values are per-shard-replay invariant), and
    windowed quantiles equal the offline exact merge of the SAME
    event stream — full range AND a partial (single-window) range."""
    rt = Runtime(CFG, _opts(parted["base"] / "sh1"))
    out = rt.query({"subsys": "svcstate", "at": f"tick:{TICKS}",
                    "maxrecs": 100, "sortcol": "svcid"})
    assert out["recs"] == parted["control_rows"]

    spec = CFG.resp_spec
    svcids, live = parted["svcids"], parted["live"]
    caps = parted["captures"]

    def expect_p(hist_f32, q):
        return WQ.np_hist_quantiles(
            np.asarray(hist_f32, np.float32)[None, :],
            spec, [q])[0, 0] / 1e3

    # full range: merged deltas telescope to the final monotone state
    win = rt.query({"subsys": "svcstate", "window": "1h",
                    "maxrecs": 100})
    assert win["shards"] == TICKS // WINDOW_TICKS
    exp_full = (caps[TICKS] - caps[0]).astype(np.float32)
    by_id = {svcids[i]: i for i in np.nonzero(live)[0]}
    checked = 0
    for r in win["recs"]:
        i = by_id.get(r["svcid"])
        if i is None:
            continue
        assert r["p99resp5s"] == pytest.approx(
            expect_p(exp_full[i], 0.99), abs=5e-4)
        assert r["p95resp5s"] == pytest.approx(
            expect_p(exp_full[i], 0.95), abs=5e-4)
        checked += 1
    assert checked >= 4

    # partial range: only the LAST window's shards sample it — the
    # per-window attribution must be right, not just the telescoped sum
    store = SH.open_shard_store(parted["base"] / "sh1")
    ents = store.shards("raw")
    mid = (ents[0]["t1"] + ents[1]["t0"]) / 2.0 \
        if ents[1]["t0"] > ents[0]["t1"] \
        else (ents[0]["t1"] + ents[1]["t1"]) / 2.0
    win2 = rt.query({"subsys": "svcstate", "tstart": mid,
                     "tend": ents[-1]["t1"] + 1.0, "maxrecs": 100})
    assert win2["shards"] == 1
    exp_last = (caps[TICKS] - caps[WINDOW_TICKS]).astype(np.float32)
    checked = 0
    for r in win2["recs"]:
        i = by_id.get(r["svcid"])
        if i is None or exp_last[i].sum() == 0:
            continue
        assert r["p99resp5s"] == pytest.approx(
            expect_p(exp_last[i], 0.99), abs=5e-4)
        checked += 1
    assert checked >= 4

    # topk over the parted store: bound-annotated merged rows
    tk = rt.query({"subsys": "topk", "window": "1h", "maxrecs": 20})
    assert tk["nrecs"] > 0
    assert all("errbound" in r for r in tk["recs"])
    rt.close()


@pytest.mark.slow
def test_parallel_sigkill_at_every_worker_boundary(parted,
                                                   tmp_path,
                                                   monkeypatch):
    """Kill a worker (os._exit(9) — no cleanup) right after each
    shard's part lands but before the supervisor publishes: the pass
    FAILS LOUDLY, the root manifest never names a window every part
    has not emitted, and the retried pass converges bit-identically
    to the uninterrupted run."""
    sh = tmp_path / "shk"
    for die_shard in range(NSHARDS):
        monkeypatch.setenv("GYT_COMPACT_DIE_SHARD", str(die_shard))
        pc = ParallelCompactor(CFG, _opts(sh), 2,
                               journal_dir=parted["wal"],
                               shard_dir=str(sh), stats=Stats())
        with pytest.raises(RuntimeError, match="parallel compaction"):
            pc.compact_once(upto_tick=TICKS)
        pc.close()
        store = SH.PartedShardStore(sh)
        for ent in store.shards():       # consistency after the crash
            for p, pe in enumerate(ent["parts"]):
                assert (store.parts[p].dir / pe["file"]).exists()
        monkeypatch.delenv("GYT_COMPACT_DIE_SHARD")
        pc = ParallelCompactor(CFG, _opts(sh), 2,
                               journal_dir=parted["wal"],
                               shard_dir=str(sh), stats=Stats())
        rep = pc.compact_once(upto_tick=TICKS)
        pc.close()
        assert rep["windows"] >= 0       # retry completes
    # converged result == the uninterrupted run, array for array
    ref = SH.open_shard_store(parted["base"] / "sh1")
    got = SH.open_shard_store(sh)
    eref, egot = ref.shards(), got.shards()
    assert [(e["level"], e["tick0"], e["tick1"]) for e in eref] \
        == [(e["level"], e["tick0"], e["tick1"]) for e in egot]
    for a, b in zip(eref, egot):
        for p in range(NSHARDS):
            da = ref.load_part(p, a["parts"][p])
            db = got.load_part(p, b["parts"][p])
            for x, y in zip(da["state"], db["state"]):
                assert np.array_equal(x, y)


def test_guards_flat_wal_and_excess_procs(parted, tmp_path):
    flat = tmp_path / "flatwal"
    j = J.Journal(flat)
    j.append(b"x" * 64, tick=0)
    j.close()
    with pytest.raises(ValueError, match="SHARDED WAL"):
        ParallelCompactor(CFG, _opts(tmp_path / "s"), 2,
                          journal_dir=str(flat),
                          shard_dir=str(tmp_path / "s"))
    with pytest.raises(ValueError, match="compact-procs"):
        ParallelCompactor(CFG, _opts(tmp_path / "s2"), NSHARDS + 1,
                          journal_dir=parted["wal"],
                          shard_dir=str(tmp_path / "s2"))


@pytest.mark.slow
def test_cli_compact_parallel_and_list(parted, tmp_path):
    """`gyeeta_tpu compact --procs 2` offline + `compact list` on the
    parted manifest."""
    import contextlib
    import io

    from gyeeta_tpu import cli

    cfg_file = tmp_path / "cfg.json"
    cfg_file.write_text(json.dumps({"engine": {
        "n_hosts": 8, "svc_capacity": 64, "task_capacity": 64,
        "conn_batch": 128, "resp_batch": 256, "fold_k": 2}}))
    sh = tmp_path / "clish"
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        cli.main(["compact", "--journal-dir", parted["wal"],
                  "--shard-dir", str(sh), "--config", str(cfg_file),
                  "--window-ticks", str(WINDOW_TICKS),
                  "--upto-tick", str(TICKS), "--procs", "2"])
    rep = json.loads(buf.getvalue())
    assert rep["windows"] == TICKS // WINDOW_TICKS * NSHARDS
    assert rep["workers"] == 2
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        cli.main(["compact", "list", "--shard-dir", str(sh)])
    listing = json.loads(buf.getvalue())
    assert len(listing["shards"]) == TICKS // WINDOW_TICKS
    assert all(len(e["parts"]) == NSHARDS for e in listing["shards"])


# ------------------------------------------------------- one chip owner
def _report_backend(q) -> None:
    """Spawn target: by the time this runs, unpickling it has imported
    this module — and with it jax — under the parent's environment."""
    from gyeeta_tpu.history import compactproc
    compactproc._pin_cpu_backend()
    import jax
    q.put(jax.default_backend())


def test_replay_worker_never_opens_the_parents_accelerator(monkeypatch):
    """A serving process on a chip host exports ``JAX_PLATFORMS`` for
    the TPU; its spawned replay workers inherit that. The worker's pin
    must still land it on the CPU backend (here ``tpu`` cannot even
    initialize, so a worker that honoured it would raise)."""
    import multiprocessing as mp

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    ctx = mp.get_context("spawn")
    q = ctx.Queue()
    p = ctx.Process(target=_report_backend, args=(q,), daemon=True)
    p.start()
    try:
        assert q.get(timeout=120) == "cpu"
    finally:
        p.join(timeout=30)
    assert not p.is_alive()
