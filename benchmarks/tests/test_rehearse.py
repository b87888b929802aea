"""The whole harness on the CPU backend at a tiny size: the rest of a run
with the look for a chip skipped. Every test drives its own copy of the
benchmark (``benchmarks/`` copied beside a link to the program, with a
BENCHMARK.json cut from the real one to the tiny configuration), so no two
share a work directory. Shows that a cell is data (a throw-away workload
file and one entry), that the rehearsal prints no device metric, and that
the comparison comes out FALSE when the timed path is broken underneath or
the program runs sketches narrower than the configuration states (the
control)."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, HERE, ROOT

TINY = os.path.join(HERE, "data", "tiny")
CELL_OF = {"fleet-50k.relay-flood": "tiny.flood",
           "fleet-50k.relay-steady": "tiny.steady"}


def _copy_benchmark(dst):
    shutil.copytree(BENCH, dst / "benchmarks", ignore=shutil.ignore_patterns(
        ".work", "__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)


@pytest.fixture()
def tree(tmp_path):
    """A checkout of the benchmark with the tiny configuration in it."""
    _copy_benchmark(tmp_path)
    os.symlink(os.path.join(ROOT, "gyeeta_tpu"), tmp_path / "gyeeta_tpu")
    shutil.copy(os.path.join(TINY, "config.tiny.json"),
                tmp_path / "benchmarks" / "configs" / "tiny.json")
    for cell in CELL_OF.values():
        shutil.copy(os.path.join(TINY, cell + ".json"),
                    tmp_path / "benchmarks" / "workloads")
    with open(tmp_path / "BENCHMARK.json") as f:
        b = json.load(f)
    b["configs"] = [{"name": "tiny", "source": "tests",
                     "file": "benchmarks/configs/tiny.json",
                     "reduced": [], "why": "tests"}]
    b["workloads"] = [{"name": cell, "config": "tiny",
                       "traffic": cell.split(".")[1], "chips": 1,
                       "why": "tests"} for cell in CELL_OF.values()]
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL_OF[c] for c in m["workloads"]
                              if c in CELL_OF]
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(b, f)
    return tmp_path


def _run(tree, cell, seed, *extra, seconds=4):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--rehearse-cpu",
         "--workload", cell, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", "0", *extra],
        cwd=tree, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_a_cell_is_data(tree):
    """A new cell: one workload file, one entry. No harness edit."""
    with open(tree / "benchmarks" / "workloads" / "tiny.steady.json") as f:
        wl = json.load(f)
    wl["rate_events_per_s"] = 12000
    wl["dashboards"]["clients"] = 2
    with open(tree / "benchmarks" / "workloads" / "tiny.throwaway.json",
              "w") as f:
        json.dump(wl, f)
    with open(tree / "BENCHMARK.json") as f:
        b = json.load(f)
    b["workloads"].append({"name": "tiny.throwaway", "config": "tiny",
                           "traffic": "throwaway", "chips": 1,
                           "why": "tests"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m and "tiny.steady" in m["workloads"]:
            m["workloads"].append("tiny.throwaway")
    with open(tree / "BENCHMARK.json", "w") as f:
        json.dump(b, f)
    out = _run(tree, "tiny.throwaway", 3000000019, seconds=11)
    assert out["correct"] is True, out["checks"]
    assert out["metrics"] == {}                  # no device metric on a CPU
    assert out["device"]["platform"] == "cpu"
    assert out["rehearsal"]["queries"] > 0 and out["rehearsal"]["polls"] > 0
    assert out["rehearsal"]["fresh_ticks"] >= 1
    assert list(out)[-1] == "checks"
    assert all(v <= lim for v, lim in out["checks"].values())


def test_flood_cell_correct(tree):
    out = _run(tree, "tiny.flood", 11)
    assert out["correct"] is True, out["checks"]
    assert out["checks"]["programs_in_window"] == [0.0, 0.0]


@pytest.mark.parametrize("fault,number", [
    ("drop_half", "ledger_off"),        # half of each batch left out
    ("drop_resp_batch", "folded_rel_gap"),  # accepted, never folded
    ("alter_answer", "svc_exact_off"),  # an answer altered where produced
])
def test_broken_timed_path_is_not_correct(tree, fault, number):
    out = _run(tree, "tiny.flood", 12, "--fault", fault)
    assert out["correct"] is False
    v, lim = out["checks"][number]
    assert v > lim, out["checks"]


@pytest.mark.parametrize("key,narrow,number", [
    ("resp_nbuckets", 128, "loghist_5d_err"),
    ("hll_p_svc", 6, "hll_fleet_err"),
])
def test_control_narrower_sketch_is_not_correct(tree, key, narrow, number):
    """The control: the program run with a sketch narrower than the
    configuration states (half the response histogram; a smaller HLL
    register file) — the step that would tempt a later PR."""
    path = tree / "benchmarks" / "configs" / "tiny.json"
    with open(path) as f:
        cfg = json.load(f)
    cfg["engine"][key] = narrow
    with open(path, "w") as f:
        json.dump(cfg, f)
    out = _run(tree, "tiny.flood", 13)
    assert out["correct"] is False
    assert out["checks"][number][0] > out["checks"][number][1]


def test_no_result_without_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and the benchmark's
    own files: non-zero, and no result line."""
    _copy_benchmark(tmp_path)
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "fleet-50k.relay-flood", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
