"""The one span primitive on the serving loop (obs/spans.py).

One toy run — a ``Runtime`` behind a ``GytServer`` with its tick loop on,
fed a mixed stream, queried over the socket — is shared by the file:

(a) every documented span name is in the ring, with a timing stage of the
    same name and the same count, under its documented parent, and the
    query spans of one request share ``req``;
(b) a ``jax.profiler`` trace of the run (Python tracer off) holds host
    events named after the annotated leaves and none after a parent;
(c) every fold variant's module is named by its sections, and the lowered
    fold carries every component's ``jax.named_scope``;
(d) every per-layer metric reader this tracing feeds, loaded from its file
    under ``benchmarks/metrics/`` and given the harness's own ``MetricCtx``
    over two ``selfstats`` readings of the run, returns a finite number.
"""

from __future__ import annotations

import asyncio
import collections
import glob
import importlib.util
import json
import math
import os
import time

import jax
import numpy as np
import pytest

from gyeeta_tpu import runtime as R
from gyeeta_tpu.engine import step
from gyeeta_tpu.engine.aggstate import EngineCfg
from gyeeta_tpu.ingest import wire
from gyeeta_tpu.net import GytServer, QueryClient
from gyeeta_tpu.net.agent import register
from gyeeta_tpu.runtime import Runtime
from gyeeta_tpu.sim.partha import ParthaSim

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = EngineCfg(n_hosts=8, svc_capacity=64, conn_batch=64, resp_batch=64,
                fold_k=2)

# span → the parents it may sit under (None: top level). A dispatch's
# spans sit under whoever dispatched: a feed, or the tick's flush.
_DISPATCH = ("feed", "tick.flush")
SPANS = {
    "edge_rx": (None,),     # a socket read's own work; never around feed
    "feed": (None,),
    "deframe": ("feed",),
    "slab_wait": _DISPATCH,
    "slab_decode": _DISPATCH,
    "td_flush": _DISPATCH,
    "fold_dispatch": _DISPATCH,
    "fold_h2d": ("fold_dispatch",),
    "fold_enqueue": ("fold_dispatch",),
    "tick": (None,),
    "tick.flush": ("tick",),
    "tick.td_drain": ("tick",),
    "tick.classify": ("tick",),
    "snapshot_publish": ("tick", None),     # None: the bootstrap publish
    "tick.hh_recover": ("tick",),
    "topk_recover": ("tick.hh_recover",),
    "tick.alerts": ("tick",),
    "tick.roll": ("tick",),
    "tick.history": ("tick",),
    "tick.health": ("tick",),
    "tick.close": ("tick",),
    "tick_visible": (None,),
    "tick_push": (None,),
    "query_queue": (None,),
    "query": (None,),
    "query_prewarm": (None,),
    "query_render": ("query", "query_prewarm"),
    # the service map: one view build a snapshot, by whoever asks first;
    # rows → answer for each svcdependency render
    "dep_view": ("query_render",),
    "dep_render": ("query_render",),
    "query_reply": (None,),
    "query_encode": (None,),
}
# spans that also enter a TraceAnnotation: the leaves. Parents never do.
LEAVES = {"edge_rx", "deframe", "slab_wait", "slab_decode", "td_flush",
          "fold_h2d", "fold_enqueue", "tick.flush", "tick.td_drain",
          "tick.classify", "snapshot_publish", "tick.hh_recover", "tick.alerts",
          "tick.roll", "tick.history", "tick.health", "tick.close",
          "tick_push", "query", "query_render", "query_encode"}
QUERY_SPANS = ("query_queue", "query", "query_prewarm", "query_render",
               "dep_view", "dep_render", "query_reply", "query_encode")
# what the dependency view counts beside its two spans (gauges and
# counters of ``selfstats``)
DEP_COUNTERS = ("dep_view_builds", "dep_view_edges", "dep_merge_dropped",
                "dep_rows_materialised")
NEW_METRICS = (
    "slab_wait_ms", "slab_decode_ms_per_mev", "h2d_ms", "loop_busy_share",
    "slab_fold_device_ms", "section_fold_device_ms", "section_fold_share",
    "tick_visible_ms", "tick_flush_ms", "tick_drain_ms", "tick_roll_ms",
    "query_queue_ms", "query_reply_ms", "query_render_ms",
    "query_cache_hit_share", "h2d_arrays_per_dispatch", "edge_ms_per_mev",
    "feeds_per_slab", "dep_view_ms", "dep_render_ms", "dep_builds_per_tick",
    "dep_rows_per_query")
VARIANTS = (
    ("connresp",), ("listener",), ("host",), ("listener", "host"),
    ("listener", "connresp"), ("host", "connresp"),
    ("listener", "host", "connresp"), step.FOLD_ALL_ORDER)
SCOPES = (
    "conn.upsert", "conn.ctr", "conn.svc_hll", "conn.glob_hll", "conn.cms",
    "conn.topk_select", "conn.topk", "conn.inv", "resp.lookup",
    "resp.loghist", "resp.td_stage", "dep.fold", "sect.listener",
    "sect.host", "sect.task", "sect.cpumem", "sect.trace", "sect.ping",
    "sect.delta")


def _reading(selfstats: dict) -> dict:
    """A ``selfstats`` answer as ``benchmarks/run.py:Run.stats`` keeps it."""
    c = dict(selfstats["counters"])
    c["_t"] = time.monotonic()
    c["_timings"] = {r["stage"]: (r["count"], r["totalms"])
                     for r in selfstats["timings"]}
    return c


async def _phase(rt, qc, sim, agent) -> None:
    """Two feeds of two slabs each with a listener sweep, a few conns
    over an event conn's socket, the same query twice (a result-cache
    miss, then a hit), one whole tick."""
    tick = rt.stats.gauges.get("tick", 0)
    for _ in range(2):
        rt.feed(sim.listener_frames() + sim.conn_frames(256)
                + sim.resp_frames(256))
    # through the serving edge; staged: the tick's flush folds it
    want = rt.stats.counters.get("conn_events", 0) + 16
    agent.write(sim.conn_frames(16))
    await agent.drain()
    while rt.stats.counters.get("conn_events", 0) < want:
        await asyncio.sleep(0.005)
    for _ in range(2):
        await qc.query({"subsys": "svcstate", "maxrecs": 4})
        await qc.query({"subsys": "svcdependency", "maxrecs": 4,
                        "sortcol": "nconn"})
    t0 = time.monotonic()
    while rt.stats.gauges.get("tick", 0) < tick + 2:
        assert time.monotonic() - t0 < 120.0, "the tick loop stopped"
        await asyncio.sleep(0.02)


async def _toy_run(trace_dir: str) -> dict:
    rt = Runtime(CFG)
    # the first feeds compile on the loop's own thread: on a loaded
    # machine that outlasts the default 30 s idle deadline of the query
    # conn opened before them
    srv = GytServer(rt, tick_interval=0.2, idle_timeout=600.0)
    host, port = await srv.start()
    sim = ParthaSim(n_hosts=8, n_svcs=2, seed=3)
    qc = QueryClient()
    await qc.connect(host, port)
    _r, agent, status, _hid = await register(host, port, 0x59A9,
                                             wire.CONN_EVENT)
    assert status == wire.REG_OK
    rt.feed(sim.name_frames())
    await _phase(rt, qc, sim, agent)        # every shape compiles here
    c0 = _reading(await qc.query({"subsys": "selfstats"}))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        await _phase(rt, qc, sim, agent)
    finally:
        jax.profiler.stop_trace()
    c1 = _reading(await qc.query({"subsys": "selfstats"}))
    rows = rt.spans.rows(last=1 << 20)
    stages = {r["stage"]: r["count"] for r in rt.stats.timing_rows()}
    total, cap = rt.spans.total, len(rt.spans)
    agent.close()
    await qc.close()
    await srv.stop()
    return {"rows": rows, "stages": stages, "c0": c0, "c1": c1,
            "ring_whole": total == cap, "trace_dir": trace_dir}


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    return asyncio.run(_toy_run(str(tmp_path_factory.mktemp("trace"))))


# ------------------------------------------------------------- (a) spans
@pytest.mark.parametrize("name", sorted(SPANS))
def test_span_row_stage_and_parent(toy, name):
    rows = [r for r in toy["rows"] if r["name"] == name]
    assert rows, f"no {name} span in the ring"
    # one primitive: the ring row and the histogram stage cannot drift
    assert toy["ring_whole"]
    assert toy["stages"].get(name) == len(rows)
    by_id = {r["id"]: r for r in toy["rows"]}
    for r in rows:
        assert r["id"] > 0 and r["wallms"] >= 0.0
        parent = by_id[r["parent"]]["name"] if r["parent"] else None
        assert parent in SPANS[name], (name, parent)


def test_every_span_is_documented(toy):
    assert {r["name"] for r in toy["rows"]} == set(SPANS)
    assert "decode_fold" not in toy["stages"]


def test_query_spans_share_req(toy):
    by_req = collections.defaultdict(set)
    for r in toy["rows"]:
        if r["name"] in QUERY_SPANS:
            assert r["req"] > 0, r
            by_req[r["req"]].add(r["name"])
        else:
            assert r["req"] == 0, r
    # a render ahead of the first ask (the tick after a repeated query)
    # is a request of the server's own: nothing but the render under it
    ahead = [names for names in by_req.values()
             if "query_prewarm" in names]
    assert ahead and all(
        names - {"dep_view", "dep_render"}
        == {"query_prewarm", "query_render"} for names in ahead), by_req
    asked = [names for names in by_req.values()
             if "query_prewarm" not in names]
    # every request asked waits, is encoded and replied to; a snapshot
    # query (not the two selfstats readings) runs under ``query``, and
    # renders on a result-cache miss only
    assert all(names >= {"query_queue", "query_encode", "query_reply"}
               for names in asked), by_req
    ran = [names for names in asked if "query" in names]
    assert len(ran) == 8
    assert 2 <= sum("query_render" in names for names in ran) < 8
    # the service map renders under ``dep_render``, never without it
    assert all(("dep_render" in names) == ("query_render" in names)
               for names in by_req.values() if "dep_view" in names)


def test_dep_view_counters(toy):
    """The four counters beside ``dep_view`` / ``dep_render``: one build
    a snapshot asked, its live edges, nothing a one-shard read could
    leave out, strings for the result rows only."""
    c0, c1 = toy["c0"], toy["c1"]
    assert all(k in c1 for k in DEP_COUNTERS), DEP_COUNTERS
    builds = c1["dep_view_builds"] - c0["dep_view_builds"]
    views = c1["_timings"]["dep_view"][0] - c0["_timings"]["dep_view"][0]
    renders = (c1["_timings"]["dep_render"][0]
               - c0["_timings"]["dep_render"][0])
    assert builds == views >= 1 and renders >= builds
    assert c1["dep_view_edges"] > 0 and c1["dep_merge_dropped"] == 0
    rows = c1["dep_rows_materialised"] - c0["dep_rows_materialised"]
    assert 0 < rows <= 4 * renders          # ``maxrecs`` 4


def test_tick_children_in_program_order(toy):
    """The tick's leaves tile it: every step of ``_run_tick`` runs inside
    one, in program order (the two conditional ones where they apply)."""
    order = ["tick.flush", "tick.td_drain", "tick.classify",
             "snapshot_publish", "tick.hh_recover", "tick.alerts",
             "tick.roll", "tick.history", "tick.health", "tick.close"]
    ticks = [r for r in toy["rows"] if r["name"] == "tick"]
    assert len(ticks) >= 4
    for t in ticks:
        kids = sorted((r for r in toy["rows"] if r["parent"] == t["id"]),
                      key=lambda r: r["id"])
        names = [r["name"] for r in kids]
        assert names == [n for n in order if n in names], names
        assert set(order) - set(names) <= {"tick.td_drain",
                                           "tick.hh_recover"}


# ----------------------------------------------------- (b) profiler trace
def test_profiler_trace_holds_the_leaves(toy):
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(toy["trace_dir"], "plugins", "profile",
                                   "*", "*.xplane.pb"))
    assert files, "the trace bracket wrote no .xplane.pb"
    pd = ProfileData.from_file(files[0])
    names = collections.Counter()
    stats = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                names[ev.name] += 1
                if ev.name == "query_render":
                    stats = dict(ev.stats)
    assert LEAVES <= set(names), LEAVES - set(names)
    # a parent would win every idle gap it encloses and say nothing
    assert not (set(SPANS) - LEAVES) & set(names)
    # the request id rides the annotation
    assert int(stats.get("req", 0)) > 0, stats


# ------------------------------------------------ (c) device-side naming
def _lowered(names: tuple):
    rt = Runtime(CFG)
    try:
        K = CFG.fold_k
        secs = []
        for k in names:
            if k == "connresp":
                secs.append((R.decode.conn_slab([], K, CFG.conn_batch),
                             R.decode.resp_slab([], K, CFG.resp_batch)))
            else:
                empty = np.zeros(0, wire.DTYPE_OF_SUBTYPE[
                    R._SECTION_SUBTYPES[k]])
                secs.append(rt._sect_builders[k](
                    empty, rt._slab_lanes_cfg[k], rt.stats))
        leaves, treedef = jax.tree.flatten(tuple(secs))
        return rt._get_fold_all(names).lower(
            rt.state, rt.dep, np.int32(0), R.pack.pack(leaves),
            (treedef, R.pack.layout_of(leaves)))
    finally:
        rt.close()


@pytest.mark.parametrize("names", VARIANTS, ids="_".join)
def test_fold_variant_module_name(names):
    text = _lowered(names).as_text()
    module = text.split("module @", 1)[1].split()[0]
    assert module.startswith("jit_fn")
    rest = "_".join(k for k in names if k != "connresp")
    if "connresp" in names:
        assert module == "_".join(x for x in ("jit_fn_connresp", rest)
                                  if x)
    else:
        assert module == "jit_fn_sections_" + rest


def test_fold_carries_every_scope():
    text = _lowered(step.FOLD_ALL_ORDER).as_text(debug_info=True)
    missing = [s for s in SCOPES if f"/{s}/" not in text]
    assert not missing, missing


# ------------------------------------------------- (d) per-layer readers
def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def metric_ctx(toy):
    run = _load(os.path.join(ROOT, "benchmarks", "run.py"), "_bench_run")
    c0, c1 = toy["c0"], toy["c1"]
    # no device plane on the CPU backend: the traced programs are the
    # toy run's own fold variants under their lowered names
    modules = [["jit_" + R.fold_all_name(v) + "(1)", 3, 0.03]
               for v in (("connresp",), ("listener", "connresp"),
                         ("listener",))]
    trace = {"window_s": 1.0, "busy_s": 0.5, "modules": modules}
    cfg = {"engine": {"fold_k": CFG.fold_k, "conn_batch": CFG.conn_batch,
                      "resp_batch": CFG.resp_batch}}
    return run, run.MetricCtx(c0, c1, c1["_t"] - c0["_t"], {}, trace, cfg,
                              {"platform": "cpu", "kind": "cpu",
                               "count": 1}, 0)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_metric_reader_reads_the_toy_run(metric_ctx, name):
    run, ctx = metric_ctx
    v = run.read_metric(name, ctx)
    assert isinstance(v, float) and math.isfinite(v) and v >= 0.0, (name, v)
    # what it reads is absent from a program without these spans: the
    # reader returns nothing there, and does not raise
    bare = run.MetricCtx({"_timings": {}, "_t": 0.0},
                         {"_timings": {}, "_t": 1.0}, 1.0, {},
                         {"window_s": 1.0, "modules": [["jit_fn(1)", 3,
                                                        0.03]]},
                         ctx.cfg, ctx.device, 0)
    assert run.read_metric(name, bare) is None


def test_benchmark_json_lists_the_new_readers():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"] for m in json.load(f)["per_layer"]}
    assert set(NEW_METRICS) <= listed


# ------------------------------------------------- the mesh runtime's twin
@pytest.mark.slow   # 8-device mesh programs stay out of the fast tier
def test_sharded_runtime_same_span_names():
    """``ShardedRuntime`` times the same places under the same names (plus
    ``rollup``; it has no double-buffered slab and no history sweep)."""
    from gyeeta_tpu.parallel.mesh import make_mesh
    from gyeeta_tpu.parallel.shardedrt import ShardedRuntime

    srt = ShardedRuntime(CFG._replace(n_hosts=16), make_mesh())
    try:
        sim = ParthaSim(n_hosts=16, n_svcs=2, seed=3)
        for _ in range(3):
            srt.feed(sim.conn_frames(256) + sim.resp_frames(256))
        srt.run_tick()
        srt.query({"subsys": "svcstate", "maxrecs": 4,
                   "consistency": "snapshot"})
        rows = srt.spans.rows(last=1 << 20)
        stages = {r["stage"]: r["count"] for r in srt.stats.timing_rows()}
    finally:
        srt.close()
    names = collections.Counter(r["name"] for r in rows)
    assert set(names) <= set(SPANS) | {"rollup"}, set(names) - set(SPANS)
    assert set(names) >= {
        "feed", "deframe", "slab_decode", "td_flush", "fold_dispatch",
        "fold_h2d", "fold_enqueue", "tick", "tick.flush", "tick.td_drain",
        "tick.classify", "snapshot_publish", "rollup", "tick.alerts",
        "tick.health", "tick.roll", "tick.close", "tick_visible", "query",
        "query_render"}
    assert dict(names) == {k: stages[k] for k in names}
