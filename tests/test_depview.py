"""The service-dependency view (``svcdependency``) of a ``Runtime``
snapshot held against the plain reference (``sketch/exactdep.py``).

One seeded stream — task→svc flows of unknown and of known process
groups, svc→svc flows known whole, svc→svc flows reported as two halves —
is fed to a small ``Runtime`` and replayed into the reference; every
answer below comes from the published snapshot, the way ``serve``
answers. ``nconn`` is exact; ``bytes`` is a float32 sum of n addends and
is held to n·2^-23 (the limit ``benchmarks/lib/recount.py`` uses).

The view is a lazy column set read straight from the edge slab: the
last tests count what a query makes it render and build.
"""

from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gyeeta_tpu.engine import table
from gyeeta_tpu.engine.aggstate import EngineCfg
from gyeeta_tpu.ingest import wire
from gyeeta_tpu.parallel import depgraph as dg
from gyeeta_tpu.query import aggr as A
from gyeeta_tpu.query import api, fieldmaps
from gyeeta_tpu.query.lazycols import LazyCols
from gyeeta_tpu.runtime import Runtime
from gyeeta_tpu.sim.partha import ParthaSim
from gyeeta_tpu.sketch.exactdep import DepViewRef
from gyeeta_tpu.utils.config import RuntimeOpts

H, S = 8, 6
SNAP = {"subsys": "svcdependency", "consistency": "snapshot"}


def _frames(recs) -> bytes:
    return wire.encode_frames_chunked(wire.NOTIFY_TCP_CONN, recs)


def _stream(sim) -> list:
    """The seeded conn batches, in feed order."""
    rng = np.random.default_rng(7)
    task = sim.conn_records(1500)             # callers: unknown groups
    known = sim.svc_conn_records(400)         # callers: known groups,
    known["cli_related_listen_id"] = 0        # not services
    mesh = sim.svc_conn_records(700)          # svc → svc, both ends known
    cli_half, ser_half = sim.svc_conn_records(300, split_halves=True)
    order = rng.permutation(len(ser_half))    # halves meet out of order
    return [task[:700], known, mesh[:350], cli_half, task[700:],
            ser_half[order], mesh[350:]]


@pytest.fixture(scope="module")
def world():
    sim = ParthaSim(n_hosts=H, n_svcs=S, seed=11, cli_groups_per_svc=5)
    rt = Runtime(EngineCfg(n_hosts=H, svc_capacity=128, task_capacity=128,
                           conn_batch=256, resp_batch=256, fold_k=2),
                 RuntimeOpts(dep_pair_capacity=1024,
                             dep_edge_capacity=2048))
    ref = DepViewRef()
    rt.feed(sim.name_frames() + sim.listener_frames() + sim.task_frames())
    for recs in _stream(sim):
        rt.feed(_frames(recs))
        ref.add(recs)
    rt.run_tick()
    names = {(int(r["kind"]), int(r["name_id"])):
             bytes(r["name"][:int(r["nlen"])]).decode()
             for r in sim.name_records()}
    comm_of = {int(r["aggr_task_id"]): int(r["comm_id"])
               for r in sim.aggr_task_records()}
    yield rt, ref, names, comm_of
    rt.close()


def _bytes_ok(got: float, want: int, n: int) -> bool:
    # n float32 additions of addends each rounded to float32 once
    return abs(got - want) <= want * ((n + 2) * 2.0 ** -23) + 1e-6


def _name(names, kind, id_hex):
    return names.get((kind, int(id_hex, 16)), id_hex)


def test_all_rows_match_the_reference(world):
    rt, ref, names, comm_of = world
    out = rt.query({**SNAP, "maxrecs": 100000})
    want = {(r["cliid"], r["serid"]): r for r in ref.rows()}
    assert len(want) > 150 and out["nrecs"] == out["ntotal"] == len(want)
    assert any(r["clisvc"] for r in want.values())
    assert not all(r["clisvc"] for r in want.values())
    seen = set()
    for r in out["recs"]:
        w = want[(r["cliid"], r["serid"])]
        seen.add((r["cliid"], r["serid"]))
        assert r["nconn"] == w["nconn"] and r["clisvc"] == w["clisvc"]
        assert _bytes_ok(r["bytes"], w["bytes"], w["nconn"]), (r, w)
        assert r["sername"] == _name(names, wire.NAME_KIND_SVC, r["serid"])
        if r["clisvc"]:
            cliname = _name(names, wire.NAME_KIND_SVC, r["cliid"])
        else:
            comm = comm_of.get(int(r["cliid"], 16))
            cliname = names[(wire.NAME_KIND_COMM, comm)] if comm \
                else r["cliid"]
        assert r["cliname"] == cliname
    assert len(seen) == len(want)
    # every kind of caller name was there to be resolved
    kinds = collections.Counter(
        "svc" if r["clisvc"] else "comm" if r["cliname"] != r["cliid"]
        else "hex" for r in out["recs"])
    assert min(kinds[k] for k in ("svc", "comm", "hex")) > 10, kinds


@pytest.mark.parametrize("col", ["nconn", "bytes"])
def test_sorted_top100(world, col):
    rt, ref, _names, _comm = world
    out = rt.query({**SNAP, "maxrecs": 100, "sortcol": col,
                    "sortdesc": True})
    got = [r[col] for r in out["recs"]]
    want = ref.top(col, 100)
    assert len(got) == 100
    if col == "nconn":
        assert got == want
    else:
        assert got == sorted(got, reverse=True)
        by_edge = {(r["cliid"], r["serid"]): r for r in ref.rows()}
        for r in out["recs"]:
            w = by_edge[(r["cliid"], r["serid"])]
            assert _bytes_ok(r["bytes"], w["bytes"], w["nconn"])
        # float32 rounding may swap near-equal neighbours, no more
        assert all(_bytes_ok(g, w, 64) for g, w in zip(got, want))


def test_groupby_service(world):
    rt, ref, _names, _comm = world
    out = rt.query({**SNAP, "maxrecs": 100000,
                    "aggr": ["sum(nconn) as nconn", "sum(bytes) as bytes",
                             "count(*) as ncallers"],
                    "groupby": ["serid"], "sortcol": "nconn",
                    "sortdesc": True})
    want = ref.by_service()
    assert out["ngroups"] == out["nrecs"] == len(want)
    assert [r["nconn"] for r in out["recs"]] == sorted(
        (w["nconn"] for w in want.values()), reverse=True)
    for r in out["recs"]:
        w = want[r["serid"]]
        assert r["nconn"] == w["nconn"] and r["ncallers"] == w["ncallers"]
        assert _bytes_ok(r["bytes"], w["bytes"], w["nconn"])
    top = rt.query({**SNAP, "maxrecs": 3, "aggr": ["sum(nconn) as nconn"],
                    "groupby": ["serid"], "sortcol": "nconn"})
    assert top["recs"] == [{"serid": r["serid"], "nconn": r["nconn"]}
                           for r in out["recs"][:3]]
    assert top["ngroups"] == len(want)


def test_numeric_filter(world):
    rt, ref, _names, _comm = world
    out = rt.query({**SNAP, "maxrecs": 100000, "filter":
                    "{ svcdependency.nconn >= 8 } and "
                    "{ svcdependency.clisvc = true }"})
    want = {(r["cliid"], r["serid"]): r["nconn"] for r in ref.rows()
            if r["nconn"] >= 8 and r["clisvc"]}
    assert want and {(r["cliid"], r["serid"]): r["nconn"]
                     for r in out["recs"]} == want


def test_name_filter_and_name_sort(world):
    rt, ref, names, _comm = world
    ser = next(r["serid"] for r in ref.rows() if r["clisvc"])
    sername = names[(wire.NAME_KIND_SVC, int(ser, 16))]
    out = rt.query({**SNAP, "maxrecs": 100000, "filter":
                    f"{{ svcdependency.sername = '{sername}' }}",
                    "sortcol": "cliname", "sortdesc": False})
    want = {r["cliid"] for r in ref.rows() if r["serid"] == ser}
    assert {r["cliid"] for r in out["recs"]} == want
    assert all(r["serid"] == ser for r in out["recs"])
    got = [r["cliname"] for r in out["recs"]]
    assert got == sorted(got)
    one = rt.query({**SNAP, "filter":
                    f"{{ svcdependency.serid = '{ser}' }}"})
    assert {r["cliid"] for r in one["recs"]} == want


def test_mesh_follows_the_slab(world):
    """``svcmesh`` labels the same svc→svc edges the view holds."""
    rt, ref, _names, _comm = world
    out = rt.query({"subsys": "svcmesh", "consistency": "snapshot",
                    "maxrecs": 100000})
    nodes = {x for r in ref.rows() if r["clisvc"]
             for x in (r["cliid"], r["serid"])}
    assert {r["svcid"] for r in out["recs"]} == nodes


# ------------------------------------------- what a query renders, builds
def _count_rendered(monkeypatch) -> list:
    """Every id or name rendered from here on adds its row count."""
    calls = []
    real_hex, real_names = api._hex_id, api._names_of

    def hex_id(hi, lo):
        calls.append(len(hi))
        return real_hex(hi, lo)

    def names_of(names, kind, hi, lo):
        calls.append(len(hi))
        return real_names(names, kind, hi, lo)

    monkeypatch.setattr(api, "_hex_id", hex_id)
    monkeypatch.setattr(api, "_names_of", names_of)
    return calls


def test_maxrecs_100_renders_strings_for_100_rows(world, monkeypatch):
    rt, ref, _names, _comm = world
    rt.run_tick()                             # a snapshot nobody has read
    calls = _count_rendered(monkeypatch)
    before = rt.stats.counters["dep_rows_materialised"]
    out = rt.query({**SNAP, "maxrecs": 100, "sortcol": "nconn"})
    assert out["nrecs"] == 100 and out["ntotal"] == len(ref.edges)
    assert calls and max(calls) <= 100, calls
    assert rt.stats.counters["dep_rows_materialised"] - before == 100
    cols, mask = rt.snapshot.columns("svcdependency")
    assert isinstance(cols, LazyCols) and mask.all()
    assert len(mask) == len(ref.edges)        # the live edges, no more
    assert not {"cliid", "serid", "cliname", "sername"} & set(
        dict.keys(cols))                      # nothing at view width
    # a groupby on an id groups on its key words: labels for 5 groups
    del calls[:]
    rt.query({**SNAP, "maxrecs": 5, "aggr": ["count(*) as n"],
              "groupby": ["serid"], "sortcol": "n"})
    assert calls and max(calls) <= 5, calls


def test_three_requests_one_build(world):
    rt, _ref, _names, _comm = world
    rt.run_tick()
    c0 = dict(rt.stats.counters)
    stage = lambda name: {r["stage"]: r["count"]          # noqa: E731
                          for r in rt.stats.timing_rows()}.get(name, 0)
    views, renders = stage("dep_view"), stage("dep_render")
    for q in ({"maxrecs": 100, "sortcol": "nconn"},
              {"maxrecs": 100, "sortcol": "bytes"},
              {"maxrecs": 100, "aggr": ["sum(nconn) as nconn"],
               "groupby": ["serid"], "sortcol": "nconn"},
              {"maxrecs": 100, "sortcol": "nconn"}):     # a cache hit
        rt.query({**SNAP, **q})
    assert rt.stats.counters["dep_view_builds"] - c0["dep_view_builds"] == 1
    assert stage("dep_view") - views == 1
    assert stage("dep_render") - renders == 3
    assert rt.stats.gauges["dep_view_edges"] == len(_ref.edges)
    assert rt.stats.gauges["dep_merge_dropped"] == 0.0


# ----------------------------------------------------- the slab, the merge
def test_one_shard_reads_the_slab_itself(world):
    rt, _ref, _names, _comm = world
    dep = rt.snapshot.dep
    es = dg.edges_local(dep)
    assert es.tbl is dep.edge_tbl and es.cli_hi is dep.e_cli_hi
    assert int(es.n_dropped) == 0
    text = jax.jit(dg.edges_local).lower(dep).as_text()
    assert "scatter" not in text and "while" not in text   # no re-hash


def test_edge_merge_counts_what_it_cannot_place():
    n, cap = 64, 16
    ids = np.arange(1, n + 1, dtype=np.uint32)
    one = jnp.ones(n, jnp.float32)
    es = jax.jit(dg._edge_merge, static_argnums=0)(
        cap, jnp.asarray(ids), jnp.asarray(ids), jnp.ones(n, bool),
        jnp.asarray(ids * 7), jnp.asarray(ids * 13), one, one,
        jnp.ones(n, bool))
    placed = int(np.asarray(table.live_mask(es.tbl)).sum())
    assert placed <= cap and int(es.n_dropped) == n - placed > 0
    assert float(es.nconn.sum()) == placed
    roomy = jax.jit(dg._edge_merge, static_argnums=0)(
        1024, jnp.asarray(ids), jnp.asarray(ids), jnp.ones(n, bool),
        jnp.asarray(ids * 7), jnp.asarray(ids * 13), one, one,
        jnp.ones(n, bool))
    assert int(roomy.n_dropped) == 0 and float(roomy.nconn.sum()) == n


# ------------------------------------- the aggregator against its old form
def _aggregate_by_dict(cols, idx, specs, groupby, fmap) -> list:
    """``aggregate_columns`` as it was before it sorted: a dict of key
    tuples, one numpy call a group and spec."""
    keycols = [np.asarray(cols[fmap[g].col])[idx] for g in groupby]
    keys = list(zip(*[k.tolist() for k in keycols])) if groupby \
        else [()] * len(idx)
    groups = collections.defaultdict(list)
    for pos, k in enumerate(keys):
        groups[k].append(pos)
    if not groups and not groupby:
        groups[()] = []
    out = []
    for key, members in groups.items():
        rec = {g: (fmap[g].to_json(kv) if fmap[g].to_json else kv)
               for g, kv in zip(groupby, key)}
        sel = idx[np.asarray(members, np.int64)]
        for s in specs:
            rec[s.alias] = float(len(sel)) if s.field == "*" else A._apply(
                s, np.asarray(cols[fmap[s.field].col])[sel].astype(
                    np.float64))
        out.append(rec)
    return out


@pytest.mark.parametrize("groupby", [
    (), ("hostid",), ("state", "hostid"), ("svcname",),
    ("svcname", "state"), ("nqry5s",)], ids="_".join)
def test_aggregate_columns_bit_equal_to_dict_form(groupby):
    rng = np.random.default_rng(len(groupby) + 3)
    fmap = fieldmaps.field_map("svcstate")
    n = 4000
    cols = {
        "hostid": rng.integers(0, 40, n).astype(np.float32),
        "state": rng.integers(0, 5, n).astype(np.int8),
        "nconns": (rng.standard_normal(n)
                   * 10 ** rng.uniform(-3, 6, n)).astype(np.float32),
        "svcname": np.array([f"s{v}" for v in rng.integers(0, 60, n)],
                            object),
        "kbin15s": rng.standard_normal(n),
        "nqry5s": rng.integers(0, 1500, n).astype(np.float64)}
    specs = [A.parse_aggr(x, "svcstate") for x in (
        "sum(nconns) as s", "avg(kbin15s) as a", "count(*) as c",
        "min(nconns)", "max(kbin15s)", "p95(nconns) as p",
        "count(state) as cs")]
    for share in (0.0, 0.01, 0.5, 1.0):
        idx = np.nonzero(rng.random(n) < share)[0]
        want = _aggregate_by_dict(cols, idx, specs, groupby, fmap)
        for sortcol, desc in ((None, True), ("s", True), ("c", False),
                              (groupby[0] if groupby else None, True)):
            w = list(want)
            if sortcol:
                w.sort(key=lambda r: r[sortcol], reverse=desc)
            for maxrecs in (3, 10 ** 6):
                got, ngroups = A.aggregate_columns(
                    cols, idx, specs, groupby, fmap, sortcol=sortcol,
                    sortdesc=desc, maxrecs=maxrecs)
                assert ngroups == len(want)
                assert got == w[:maxrecs]
                assert [list(r.items()) for r in got] == [
                    list(r.items()) for r in w[:maxrecs]]
