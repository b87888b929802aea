"""Observability tier: /metrics exposition, engine-health gauges,
span tracer, and single-node vs sharded parity.

Covers the ISSUE-2 acceptance surface: valid Prometheus text format
(counters + cumulative timing histograms + ``_sum``/``_count`` + ≥6
engine-health gauges), identical metric names over the binary-protocol
``metrics`` subsystem from both runtimes, the span ring riding
``selfstats.spans``, and the exact-boundary quantile fix in
``Stats.timing_rows``.
"""

from __future__ import annotations

import asyncio
import math
import re

import numpy as np
import pytest

from gyeeta_tpu.engine.aggstate import EngineCfg
from gyeeta_tpu.obs import format_top, prom
from gyeeta_tpu.obs.spans import SpanTracer
from gyeeta_tpu.runtime import Runtime
from gyeeta_tpu.sim.partha import ParthaSim
from gyeeta_tpu.utils.selfstats import Stats

CFG = EngineCfg(n_hosts=8, svc_capacity=64, conn_batch=64, resp_batch=64,
                fold_k=2)

_SAMPLE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})? (\+Inf|-?[0-9.e+-]+)$')


def _parse_exposition(text: str) -> dict:
    """Minimal exposition parser: {name: [(labels, value)]}; raises on
    any malformed line (the ci smoke step uses the same grammar)."""
    out: dict = {}
    for ln in text.splitlines():
        if not ln or ln.startswith("#"):
            continue
        m = _SAMPLE.match(ln)
        assert m, f"malformed exposition line: {ln!r}"
        name, labels, value = m.group(1), m.group(2) or "", m.group(3)
        v = math.inf if value == "+Inf" else float(value)
        out.setdefault(name, []).append((labels, v))
    return out


def _fed_runtime() -> Runtime:
    rt = Runtime(CFG)
    sim = ParthaSim(n_hosts=8, n_svcs=2, seed=3)
    rt.feed(sim.conn_frames(256) + sim.resp_frames(256))
    rt.run_tick()
    return rt


# ------------------------------------------------------------ exposition
def test_metrics_exposition_valid_and_complete():
    rt = _fed_runtime()
    out = rt.query({"subsys": "metrics"})
    assert out["content_type"].startswith("text/plain")
    series = _parse_exposition(out["text"])

    # counters: the ingest event counters ride as _total
    assert series["gyt_conn_events_total"][0][1] == 256.0
    assert series["gyt_resp_events_total"][0][1] == 256.0
    # PR-1 decode-path counters are scrapeable (satellite: a degraded
    # native extension is visible without a query client)
    assert ("gyt_ref_native_decoded_total" in series
            or "gyt_ref_fallback_decoded_total" in series)

    # ≥6 engine-health gauges from the batched device readback
    eng = [n for n in series if n.startswith("gyt_engine_")]
    assert len(eng) >= 6, eng
    occ = series["gyt_engine_svc_occupancy_ratio"][0][1]
    assert 0.0 < occ <= 1.0

    # timing histogram: cumulative le buckets + _sum/_count per stage
    buckets = series["gyt_stage_duration_seconds_bucket"]
    stages = {lb for lb, _ in buckets}
    assert any('stage="deframe"' in lb for lb in stages)
    for stage_lb in {re.search(r'stage="([^"]+)"', lb).group(1)
                     for lb, _ in buckets}:
        vals = [v for lb, v in buckets if f'stage="{stage_lb}"' in lb]
        assert vals == sorted(vals), f"{stage_lb}: non-cumulative"
        count = [v for lb, v in
                 series["gyt_stage_duration_seconds_count"]
                 if f'stage="{stage_lb}"' in lb]
        assert count and count[0] == vals[-1]   # +Inf bucket == count
        s = [v for lb, v in series["gyt_stage_duration_seconds_sum"]
             if f'stage="{stage_lb}"' in lb]
        assert s and s[0] >= 0.0
    rt.close()


def test_metrics_over_binary_protocol_and_webgw():
    """GET /metrics through the gateway == the metrics subsystem over
    the binary query protocol (one rendering for both faces)."""
    from gyeeta_tpu.net import GytServer, QueryClient
    from gyeeta_tpu.net.webgw import WebGateway

    async def scenario():
        rt = _fed_runtime()
        srv = GytServer(rt, tick_interval=None)
        host, port = await srv.start()
        qc = QueryClient()
        await qc.connect(host, port)
        over_wire = await qc.query({"subsys": "metrics"})
        await qc.close()
        gw = WebGateway(host, port)
        gh, gp = await gw.start()
        r, w = await asyncio.open_connection(gh, gp)
        w.write(b"GET /metrics HTTP/1.1\r\nConnection: close\r\n\r\n")
        await w.drain()
        raw = await r.read(-1)
        w.close()
        await gw.stop()
        await srv.stop()
        return over_wire, raw

    over_wire, raw = asyncio.run(scenario())
    head, _, body = raw.partition(b"\r\n\r\n")
    assert b"200 OK" in head.splitlines()[0]
    assert b"text/plain" in head
    http_names = set(_parse_exposition(body.decode()))
    wire_names = set(_parse_exposition(over_wire["text"]))
    # same rendering: every metric family visible on one face is
    # visible on the other (values may differ — queries bump counters)
    assert http_names == wire_names
    assert any(n.startswith("gyt_engine_") for n in http_names)


@pytest.mark.slow   # 8-device mesh program: shard_map executables must
#                     stay out of the fast tier's compile cache (conftest)
def test_metrics_parity_single_vs_sharded():
    """The metric-name surface is identical from Runtime and
    ShardedRuntime (acceptance: one registry surface, no drift)."""
    from gyeeta_tpu.parallel.mesh import make_mesh
    from gyeeta_tpu.parallel.shardedrt import ShardedRuntime

    rt = _fed_runtime()
    single = rt.query({"subsys": "metrics"})["text"]
    rt.close()

    srt = ShardedRuntime(CFG._replace(n_hosts=16), make_mesh())
    sim = ParthaSim(n_hosts=16, n_svcs=2, seed=3)
    srt.feed(sim.conn_frames(256) + sim.resp_frames(256))
    srt.run_tick()
    shard = srt.query({"subsys": "metrics"})["text"]
    srt.close()

    def surface(text):
        # engine health gauges + the stage histograms; the single-node
        # runtime's double-buffered staging slab (gyt_engine_stage_slab_*)
        # has no twin on the mesh
        return {n for n in _parse_exposition(text)
                if (n.startswith("gyt_engine_")
                    and not n.startswith("gyt_engine_stage_slab_"))
                or n.startswith("gyt_stage_duration_")}

    assert surface(single) == surface(shard)
    # and the engine gauges carry real readbacks on both
    for text in (single, shard):
        s = _parse_exposition(text)
        assert s["gyt_engine_conn_folded"][0][1] > 0


# ------------------------------------------------------------ engine health
def test_engine_health_single_batched_readback():
    rt = _fed_runtime()
    g = rt.engine_health()
    assert g["engine_svc_rows_live"] > 0
    assert 0 < g["engine_svc_occupancy_ratio"] <= 1.0
    assert g["engine_conn_folded"] == 256.0
    assert g["engine_resp_folded"] == 256.0
    # gauges landed in the Stats registry (selfstats + /metrics ride it)
    assert rt.stats.gauges["engine_svc_rows_live"] == \
        g["engine_svc_rows_live"]
    # the readback is ONE device vector — engine_health_vec packs every
    # key, so length and key-order are locked by HEALTH_KEYS
    from gyeeta_tpu.engine import step
    vec = np.asarray(rt._engine_health(rt.state, rt.dep))
    assert vec.shape == (len(step.HEALTH_KEYS),)
    rt.close()


def test_probe_gauges_in_health_and_selfstats():
    """The staged table probe's two device counters ride the one health
    readback into ``engine_health()`` and ``selfstats``. The first slabs
    carry keys no table holds yet (every edge is new) and overflow into
    the full-width path; the same traffic again is all-hit: a small
    residue and no further fallback."""
    cfg = CFG._replace(conn_batch=1024, resp_batch=2048)
    rt = Runtime(cfg)
    sim = ParthaSim(n_hosts=8, n_svcs=2, seed=3)
    frames = sim.conn_frames(4096) + sim.resp_frames(8192)
    rt.feed(sim.listener_frames() + frames)
    rt.run_tick()
    g0 = rt.engine_health()
    assert g0["engine_probe_fallbacks"] >= 1
    rt.feed(frames)
    rt.run_tick()
    g = rt.engine_health()
    assert g["engine_probe_fallbacks"] == g0["engine_probe_fallbacks"]
    lanes = g["engine_probe_residue_lanes"] - g0["engine_probe_residue_lanes"]
    assert 0 <= lanes < 0.1 * (4096 + 8192)
    counters = rt.query({"subsys": "selfstats"})["counters"]
    for k in ("engine_probe_residue_lanes", "engine_probe_fallbacks"):
        assert counters[k] == g[k]
    assert str(rt.state.n_probe.dtype) == "int32"
    rt.close()


def test_probe_failures_surface_in_health():
    """Overflowing a tiny svc slab shows up as probe failures +
    occupancy ~1.0 (the PSketch silent-saturation lesson)."""
    from gyeeta_tpu.ingest import wire
    from gyeeta_tpu.sketch import loghist

    cfg = EngineCfg(
        svc_capacity=32, n_hosts=4,
        resp_spec=loghist.LogHistSpec(vmin=1.0, vmax=1e8, nbuckets=32),
        hll_p_svc=4, hll_p_global=8, cms_depth=2, cms_width=1 << 8,
        topk_capacity=16, td_capacity=16,
        conn_batch=256, resp_batch=64, listener_batch=32)
    rt = Runtime(cfg)
    recs = np.zeros(2048, wire.TCP_CONN_DT)
    recs["ser_glob_id"] = np.arange(1, 2049, dtype=np.uint64)
    recs["flags"] = 2
    for i in range(0, 2048, 256):
        rt.feed(wire.encode_frame(wire.NOTIFY_TCP_CONN, recs[i:i + 256]))
    rt.flush()
    g = rt.engine_health()
    assert g["engine_svc_probe_failures"] > 0
    assert g["engine_svc_occupancy_ratio"] > 0.9
    rt.close()


# ------------------------------------------------------------------ spans
def test_span_tracer_ring_and_rows():
    tr = SpanTracer(capacity=4)
    for i in range(6):
        tr.record(f"s{i}", 1000.0 + i, float(i), nrec=i, path="native")
    assert len(tr) == 4 and tr.total == 6
    rows = tr.rows()
    assert [r["name"] for r in rows] == ["s5", "s4", "s3", "s2"]
    assert rows[0]["path"] == "native" and rows[0]["nrec"] == 5
    with tr.span("timed", nrec=7):
        pass
    assert tr.rows()[0]["name"] == "timed"
    assert tr.rows()[0]["wallms"] >= 0.0
    tr.clear()
    assert len(tr) == 0 and tr.rows() == []


def test_runtime_spans_ride_selfstats():
    rt = _fed_runtime()
    ss = rt.query({"subsys": "selfstats"})
    names = {s["name"] for s in ss["spans"]}
    assert {"deframe", "fold_dispatch", "tick"} <= names
    assert "decode_fold" not in names
    folds = [s for s in ss["spans"] if s["name"] == "fold_dispatch"]
    assert folds and folds[0]["nrec"] > 0
    assert folds[0]["path"] in ("native", "python")
    # the top renderer consumes the same payload
    frame = format_top(ss)
    assert "recent spans" in frame and "engine health" in frame
    rt.close()


def test_format_top_relay_ledger_section():
    """relay_* counters render in their own section with the derived
    ledger_open invariant (published − consumed − dropped, all
    relays), and never duplicate into the plain-counters tail."""
    ss = {"counters": {
        "uptime_sec": 3,
        "relay_published_records|relay=rb": 100,
        "relay_consumed_records|relay=rb": 90,
        "relay_dropped_records|relay=rb,shard=0": 6,
        "relay_dropped_records|relay=rb,shard=1": 4,
        "relay_epochs|relay=rb": 1,
        "gw_region_events": 5,
        "conn_events": 7}}
    frame = format_top(ss)
    assert "remote ingest relay:" in frame
    m = re.search(r"ledger_open\s+(\S+)", frame)
    assert m and float(m.group(1)) == 0.0       # books closed
    assert "relay_" not in frame.split("counters:")[1]
    # an open ledger surfaces as a nonzero derived row
    ss["counters"]["relay_published_records|relay=rb"] = 110
    m = re.search(r"ledger_open\s+(\S+)", format_top(ss))
    assert m and float(m.group(1)) == 10.0


# ------------------------------------------- timing quantile regression
def test_timing_quantile_exact_boundary_rank():
    """Satellite: rank semantics at exact cumulative boundaries.
    0.99*100 is 99.000…01 in binary; the old searchsorted on the float
    product skipped a bucket whose cumulative count is exactly 99 and
    reported the NEXT (slower) bucket."""
    s = Stats()
    for ms in (1.0,) * 99 + (100.0,):
        s.observe_ms("st", ms)
    (row,) = s.timing_rows()
    # rank ceil(0.99*100)=99 of 100 is still a 1ms sample
    assert row["p99ms"] <= 2.0, row
    assert row["p50ms"] <= 2.0

    s2 = Stats()
    for ms in (1.0,) * 50 + (100.0,) * 50:
        s2.observe_ms("st", ms)
    (r2,) = s2.timing_rows()
    assert r2["p50ms"] <= 2.0, r2      # rank 50 of 100: the 1ms bucket
    assert r2["p99ms"] >= 60.0


def test_prom_render_name_sanitization():
    s = Stats()
    s.bump("ref_evt_0x2", 3)
    s.bump("weird name-with.bad/chars", 1)
    s.gauge("tick", 7)
    text = prom.render(s)
    series = _parse_exposition(text)     # raises on malformed names
    assert series["gyt_ref_evt_0x2_total"][0][1] == 3.0
    assert series["gyt_weird_name_with_bad_chars_total"][0][1] == 1.0
    assert series["gyt_tick"][0][1] == 7.0
