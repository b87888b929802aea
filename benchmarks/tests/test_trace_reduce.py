"""The trace reduction on a small trace recorded on a TPU v5e
(``data/tiny.xplane.pb``, written by ``data/record_tiny_trace.py``: three
runs each of a jitted ``fn`` and a jitted sort, with 10 ms sleeps)."""

import json
import os
import subprocess
import sys

from conftest import BENCH, HERE

TRACE = os.path.join(HERE, "data", "tiny.xplane.pb")


def _reduce():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "lib", "trace_reduce.py"),
         TRACE], capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_reduction_of_the_recorded_trace():
    r = _reduce()
    assert r["devices"] == 1 and "/device:TPU:0" in r["planes"]
    mods = {m[0].split("(")[0]: m for m in r["modules"]}
    # three executions of each program; the sort takes 11.2 us a run, fn 2.2-2.5 us
    assert mods["jit_fn"][1] == 3 and mods["jit__lambda"][1] == 3
    assert abs(mods["jit_fn"][2] - 6.897e-6) < 1e-8
    assert abs(mods["jit__lambda"][2] - 3 * 11.205e-6) < 0.5e-6
    # busy is the union of the op intervals: no more than the programs'
    # own time, and most of it
    total = mods["jit_fn"][2] + mods["jit__lambda"][2]
    assert 0.5 * total < r["busy_s"] <= total * 1.001
    assert r["busy_s"] < r["window_s"]          # the sleeps are idle
    assert 1 <= len(r["device_ops"]) <= 10
    assert r["device_ops"][0][1] >= r["device_ops"][-1][1] > 0
    # the ten longest gaps, each named by the host event that overlaps
    # it most: the three 10 ms sleeps lead
    assert len(r["idle_gaps"]) == 10
    assert all(g[1] > 0 and isinstance(g[0], str) for g in r["idle_gaps"])
    assert [g[0] for g in r["idle_gaps"][:3]] == ["python3: $time sleep"] * 3
    assert all(0.010 < g[1] < 0.013 for g in r["idle_gaps"][:3])
    assert r["idle_gaps"][0][1] >= r["idle_gaps"][-1][1]
    gap = sum(g[1] for g in r["idle_gaps"])
    assert gap + r["busy_s"] <= r["traced_s"] + 1e-9
