"""``fleet-50k.depview`` rehearsed on the CPU backend at the tiny size:
the cell is its workload file and one entry, its six-request dashboard
cycle (half of it ``svcdependency``) runs beside the open-loop traffic,
and the harness's own comparison decides. A sound run ends ``correct``
with every ``dep100`` answer of the window judged
(``recount.window_answers``) and the whole dependency view recounted after
it; an edge's ``nconn`` altered in the answer turns ``correct`` false, in
the window and after it."""

import json
import os
import shutil

import pytest

from conftest import HERE, ROOT
from test_rehearse import TINY, _copy_benchmark, _run

CELL, TINY_CELL = "fleet-50k.depview", "tiny.depview"
STEADY = "fleet-50k.relay-steady"


@pytest.fixture()
def tree(tmp_path):
    """A checkout of the benchmark with the tiny configuration and the
    tiny form of the cell in it."""
    _copy_benchmark(tmp_path)
    os.symlink(os.path.join(ROOT, "gyeeta_tpu"), tmp_path / "gyeeta_tpu")
    shutil.copy(os.path.join(TINY, "config.tiny.json"),
                tmp_path / "benchmarks" / "configs" / "tiny.json")
    shutil.copy(os.path.join(TINY, TINY_CELL + ".json"),
                tmp_path / "benchmarks" / "workloads")
    with open(tmp_path / "BENCHMARK.json") as f:
        b = json.load(f)
    assert CELL in {w["name"] for w in b["workloads"]}
    b["configs"] = [{"name": "tiny", "source": "tests",
                     "file": "benchmarks/configs/tiny.json",
                     "reduced": [], "why": "tests"}]
    b["workloads"] = [{"name": TINY_CELL, "config": "tiny",
                       "traffic": "depview", "chips": 1, "why": "tests"}]
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [TINY_CELL] if CELL in m["workloads"] else []
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(b, f)
    return tmp_path


def test_the_cell_is_its_steady_twin_but_for_the_dashboards():
    """Parameter for parameter relay-steady, apart from what the
    dashboards ask (and the line that says why)."""
    def load(cell):
        with open(os.path.join(ROOT, "benchmarks", "workloads",
                               cell + ".json")) as f:
            return json.load(f)
    dep, steady = load(CELL), load(STEADY)
    queries = dep["dashboards"].pop("queries")
    kept = {q["name"]: q for q in steady["dashboards"].pop("queries")}
    dep.pop("why"), steady.pop("why")
    assert dep == steady
    assert [q["name"] for q in queries] == [
        "dep100", "depbytes", "depsvc", "top100", "clusterstate", "topk"]
    assert all(q == kept[q["name"]] for q in queries[3:])
    assert all(q["req"]["subsys"] == "svcdependency"
               and q["req"]["maxrecs"] == 100 and q["req"]["sortdesc"]
               for q in queries[:3])
    assert [q["req"]["sortcol"] for q in queries[:3]] == [
        "nconn", "bytes", "nconn"]
    assert queries[2]["req"]["groupby"] == ["serid"]
    with open(os.path.join(TINY, TINY_CELL + ".json")) as f:
        assert json.load(f)["dashboards"]["queries"][:3] == queries[:3]


def test_the_configuration_is_fleet_50k_with_the_service_map_served():
    """``fleet-50k-svcmap`` is its own deployment (source, what is served,
    the guarantees stated for the dependency view) at ``fleet-50k``'s
    sizes, number for number: the two steady cells differ by the
    dashboards alone, and the folds compile once for both."""
    def load(name):
        with open(os.path.join(ROOT, "benchmarks", "configs",
                               name + ".json")) as f:
            return json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    cell = {w["name"]: w for w in b["workloads"]}[CELL]
    entry = {c["name"]: c for c in b["configs"]}[cell["config"]]
    assert (cell["config"], cell["traffic"]) == ("fleet-50k-svcmap",
                                                 "depview")
    assert entry["file"] == "benchmarks/configs/fleet-50k-svcmap.json"
    base_entry = {c["name"]: c for c in b["configs"]}["fleet-50k"]
    assert entry["source"] != base_entry["source"]
    svcmap, base = load("fleet-50k-svcmap"), load("fleet-50k")
    for block in ("engine", "runtime", "fleet", "published", "reduced"):
        assert svcmap[block] == base[block], block
    assert svcmap["name"] == "fleet-50k-svcmap"
    # every guarantee of fleet-50k holds, and the view's own is stated
    stated = dict(svcmap["guarantees"])
    assert "dep_merge_dropped 0" in stated.pop("dependency_view")
    assert stated == base["guarantees"]


def test_depview_cell_correct(tree):
    out = _run(tree, TINY_CELL, 2147483659, seconds=11)
    assert out["correct"] is True, out["checks"]
    checks = out["checks"]
    for name in ("window_answers_bad", "dep_top100_off", "dep_svcs_wrong",
                 "dep_svcs_short", "programs_in_window"):
        assert checks[name][0] == 0.0, (name, checks[name])
    assert checks["dep_bytes_rel"][0] <= checks["dep_bytes_rel"][1]
    # six requests a cycle, four clients, a quarter second between asks
    assert out["rehearsal"]["queries"] >= 24
    assert out["rehearsal"]["fresh_ticks"] >= 1 and out["failed"] == 0


def test_altered_edge_is_not_correct(tree):
    """The planted fault: an edge's ``nconn`` altered in the answer."""
    shutil.copy(os.path.join(HERE, "data", "faults_depview.py"),
                tree / "benchmarks" / "lib" / "faults.py")
    out = _run(tree, TINY_CELL, 2147483660, "--fault", "alter_dep_answer",
               seconds=11)
    assert out["correct"] is False
    checks = out["checks"]
    # the window's own dep100 answers, by what they say ...
    assert checks["window_answers_bad"][0] > 0, checks
    # ... and the exact recount after it: the top-100 and the roll-up
    assert checks["dep_top100_off"][0] == 1.0, checks
    assert checks["dep_svcs_wrong"][0] > 0, checks
    # nothing else is blamed
    assert all(v <= lim for name, (v, lim) in checks.items()
               if name not in ("window_answers_bad", "dep_top100_off",
                               "dep_svcs_wrong", "dep_svcs_short",
                               "dep_bytes_rel")), checks
