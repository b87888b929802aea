#!/bin/sh
# CI entry: test suite on the 8-device virtual CPU platform.
# (tests/conftest.py defaults JAX_PLATFORMS=cpu + forces the device
# count; the chip is reached through the chip tool with chip_smoke.py.)
#
#   ./ci.sh            full suite (slow tier included)
#   ./ci.sh fast       unit tier only (-m "not slow", a few minutes) —
#                      run this on every change; the full suite at least
#                      once before shipping
set -e
cd "$(dirname "$0")"

# Build the native ingest extension from source — never trust a
# checked-in libgytdeframe.so (a stale binary would silently fall back
# or, worse, pass tests the current deframe.cpp wouldn't). A broken
# compile fails CI loudly; a host without a C++ toolchain skips with a
# reason and the suite runs on the pure-Python decode path.
if command -v g++ >/dev/null 2>&1; then
    rm -f gyeeta_tpu/ingest/native/libgytdeframe.so
    if ! python -m gyeeta_tpu.ingest.native.build; then
        echo "ci: FATAL — native ingest extension failed to compile" >&2
        exit 1
    fi
else
    echo "ci: SKIP native build (no C++ toolchain on this host);" \
         "tests run on the pure-Python decode path" >&2
fi

# ABI compile probe: prove the stock-struct transcriptions against the
# host C++ compiler's layout (offsetof/sizeof for every adapted struct).
# Skips itself with a reason when no toolchain; any drift fails CI.
echo "ci: ABI compile probe" >&2
if ! JAX_PLATFORMS=cpu python -m gyeeta_tpu.ingest.native.abiprobe; then
    echo "ci: FATAL — ABI probe found layout drift" >&2
    exit 1
fi

# /metrics exposition smoke: boot server + gateway, scrape, validate
# the Prometheus text contract with the built-in minimal parser (no
# external deps). Catches a broken scraper surface before the suite.
echo "ci: /metrics exposition smoke" >&2
if ! JAX_PLATFORMS=cpu python _metrics_smoke.py; then
    echo "ci: FATAL — /metrics smoke failed" >&2
    exit 1
fi

# NM query-edge smoke: boot a server, open a STOCK node-webserver conn
# (sim/nodeweb.py — zero GYT frames on the wire), run one
# QUERY_WEB_JSON and one CRUD_ALERT_JSON create→list→delete round trip,
# and query the `topk` heavy-hitter subsystem over BOTH the NM conn and
# the REST gateway — non-empty, bound-annotated, byte-equal renderings.
echo "ci: NM query-edge smoke" >&2
if ! JAX_PLATFORMS=cpu python _nm_smoke.py; then
    echo "ci: FATAL — NM smoke failed" >&2
    exit 1
fi

# History / time-travel smoke: feed → seal WAL → compact into columnar
# snapshot shards (retention downsample demonstrated) → RESTART a fresh
# runtime over the shard dir → query svcstate?at= + topk?window= over
# REST and a stock NM conn, asserting non-empty bound-annotated rows
# rendered byte-equal on both edges.
echo "ci: history time-travel smoke" >&2
if ! JAX_PLATFORMS=cpu python _hist_smoke.py; then
    echo "ci: FATAL — history smoke failed" >&2
    exit 1
fi

# Snapshot-serving QPS smoke: boot a TICKING server + REST gateway,
# feed from a NetAgent while 8 concurrent clients hammer svcstate/
# topk/hoststate — asserts non-empty single-tick-consistent rows,
# nonzero result-cache hits, and zero sheds at smoke load.
echo "ci: snapshot query-serving QPS smoke" >&2
if ! JAX_PLATFORMS=cpu python _qps_smoke.py; then
    echo "ci: FATAL — QPS smoke failed" >&2
    exit 1
fi

# Edge pre-aggregation smoke: a GYT_PREAGG=1 server negotiates delta
# mode with a default agent while an opted-out agent feeds raw sweeps;
# svcstate/hoststate agree byte-equal on REST and stock NM, the delta
# host's counters match the agent's own exact partials, and
# gyt_preagg_* counters render in /metrics.
echo "ci: edge pre-aggregation smoke" >&2
if ! JAX_PLATFORMS=cpu python _preagg_smoke.py; then
    echo "ci: FATAL — preagg smoke failed" >&2
    exit 1
fi

# Query-fabric gateway smoke: 2 serve replicas + 1 gateway — a query
# rendered once upstream serves every later client from the shared
# (snaptick, request-hash) edge cache (replica render counters prove
# the single render), an SSE subscriber receives a pushed event after
# a fed tick that reassembles byte-equal to a fresh full query (and a
# stable-row subscription pushes a REAL delta), and the gateway's
# /metrics exposes the gyt_gw_* families.
echo "ci: query-fabric gateway smoke" >&2
if ! JAX_PLATFORMS=cpu python _gw_smoke.py; then
    echo "ci: FATAL — gateway smoke failed" >&2
    exit 1
fi

# Multichip smoke: a REAL `serve --shards 8` subprocess on the
# simulated 8-device mesh — per-shard ingest + WAL subdirs + collective
# roll-up; 2 agents on different shards; asserts the MERGED
# svcstate/topk rows are non-empty and byte-equal on REST and stock NM,
# chunks routed to their layout shards, per-shard gauges exposed.
echo "ci: multichip --shards smoke" >&2
if ! JAX_PLATFORMS=cpu python _multichip_smoke.py; then
    echo "ci: FATAL — multichip smoke failed" >&2
    exit 1
fi

# Multi-process ingest smoke: a REAL `serve --shards 8
# --ingest-procs 2` subprocess — registration + fd handoff to sticky
# shard-group workers, worker-side deframe/decode + WAL append,
# shared-memory rings into the fold; 2 agents on different shard
# groups; asserts merged svcstate byte-equal on REST and stock NM,
# per-worker heartbeat gauges + ledger counters in /metrics, and the
# worker-owned per-shard WAL in the stock layout.
echo "ci: multi-process ingest smoke" >&2
if ! JAX_PLATFORMS=cpu python _mproc_smoke.py; then
    echo "ci: FATAL — mproc smoke failed" >&2
    exit 1
fi

# Chaos smoke: a REAL `serve` subprocess behind the seeded chaos proxy
# (sim/chaos.py) — corruption/disconnect faults, a slow-loris conn,
# one SIGTERM kill + --restore-latest restart. Fails on agent exit,
# non-convergence, an unreaped loris, or unaccounted record loss.
echo "ci: chaos / fault-injection smoke" >&2
if ! JAX_PLATFORMS=cpu python _chaos_smoke.py; then
    echo "ci: FATAL — chaos smoke failed" >&2
    exit 1
fi

# Fabric fault-domain smoke (ISSUE 15): phase A — 2 replicas + 2 REAL
# gateway subprocesses with a wedge-capable chaos proxy (gateway
# SIGKILL mid-subscription → counted resync + byte-equal continuation
# on the peer, restart resumes from the persisted ring with a DELTA,
# wedged replica bounded by hedged reads, killed replica opens the
# circuit breaker — zero surfaced upstream errors throughout); phase
# B — `serve --shards 2 --ingest-procs 2` subprocess (fresh scoped
# XLA cache): ingest worker SIGKILL under subscription load with the
# ring ledger closing EXACTLY, and a compaction-worker death at a
# shard boundary failing loudly then converging on rerun.
echo "ci: fabric fault-domain smoke" >&2
if ! JAX_PLATFORMS=cpu python _fabric_chaos_smoke.py; then
    echo "ci: FATAL — fabric fault-domain smoke failed" >&2
    exit 1
fi

# Continuous-query smoke (ISSUE 18): 2 replicas + gateway, 104
# standing filters (96 hub + 8 real SSE) spelled 8 ways over 4
# canonical criteria groups on churning svcstate. Asserts the
# amortization contract off /metrics (gyt_cq_group_evals_total ==
# groups*ticks, gyt_cq_panel_renders_total == ticks — ≤1 render and
# one predicate pass per group per tick no matter how many
# subscribers), SSE-held membership byte-exact vs a brute-force
# predicate pass over the full panel, /v1/topology on REST + a stock
# NM conn, alertdef CQ evaluation byte-identical to degenerate per-def
# groups (fewer predicate passes, same fires/astate), the zero-def
# alert short-circuit counter, and enter/leave continuity across a
# gateway restart (persisted ring resumes with the missed deltas —
# counted as a resume, zero resyncs).
echo "ci: continuous-query smoke" >&2
if ! JAX_PLATFORMS=cpu python _cq_smoke.py; then
    echo "ci: FATAL — continuous-query smoke failed" >&2
    exit 1
fi

# Two-region WAN smoke (ISSUE 19): region A = hub Runtime + REAL
# gateway subprocess; region B = REAL `relay` + hub-mode `gateway`
# subprocesses with 3 agents, BOTH WAN hops through chaos proxies
# carrying asymmetric latency. Asserts: steady-state inter-region
# bytes ∝ delta churn (not panel size) with one WAN stream per key;
# relay-worker SIGKILL → respawn = a NEW counted epoch with the
# published == consumed + dropped ledger closing EXACTLY across TCP;
# full inter-region partition → bytes LOST (not parked) → heal
# resumes with a counted in-band resync/reconnect and byte-equal
# convergence; region-B wipeout (gateway + relay SIGKILL) → region A
# keeps serving, restarted region B converges byte-equal to the
# fault-free control. Never silent divergence.
echo "ci: two-region WAN smoke" >&2
if ! JAX_PLATFORMS=cpu python _region_smoke.py; then
    echo "ci: FATAL — two-region WAN smoke failed" >&2
    exit 1
fi

# Remote compaction region smoke (ISSUE 20): sealed WAL segments ship
# from a 2-shard source region over the supervised segship protocol to
# a compaction region's staging dir, under the full crash campaign —
# shipper SIGKILL at EVERY ship boundary (one death per landed
# segment, exit code enforced), receiver self-kill alternating between
# the post-rename and post-ledger crash points, and a WAN partition
# dropped mid-segment (stream hole → counted reconnect → per-segment
# offset resume). Asserts: the staging dir converges BYTE-IDENTICAL to
# the source WAL, the content-hash ledger closes EXACTLY
# (sealed == landed + counted drops, zero drops here), and a parallel
# replay of the SHIPPED staging dir through the serve daemon's staging
# loop is array-for-array identical to a local parallel replay of the
# original WAL. Never silent divergence.
echo "ci: remote compaction region smoke" >&2
if ! JAX_PLATFORMS=cpu python _rcompact_smoke.py; then
    echo "ci: FATAL — remote compaction smoke failed" >&2
    exit 1
fi

# Fold-path smoke: a default Runtime folds a fed stream in fold_all
# dispatches.
echo "ci: fused fold-path smoke" >&2
if ! JAX_PLATFORMS=cpu python - <<'PYEOF'
from gyeeta_tpu.runtime import Runtime
from gyeeta_tpu.sim.partha import ParthaSim

rt = Runtime()
sim = ParthaSim(n_hosts=4, n_svcs=4, seed=3)
rt.feed(sim.listener_frames())
rt.feed(sim.conn_frames(4096))
rt.feed(sim.resp_frames(4096))
rt.flush()
assert rt.stats.counters.get("fold_dispatches", 0) > 0
rt.close()
print("ci: fused fold OK")
PYEOF
then
    echo "ci: FATAL — fused fold-path smoke failed" >&2
    exit 1
fi

if [ "$1" = "fast" ]; then
    shift
    exec python -m pytest tests/ -q -m "not slow" "$@"
fi
python -m pytest tests/ -q "$@"
