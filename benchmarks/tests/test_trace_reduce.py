"""The trace reduction on a small trace recorded on a TPU v5e
(``data/tiny.xplane.pb``, written by ``data/record_tiny_trace.py``: three
runs each of a jitted ``fn`` and a jitted sort, with 10 ms sleeps), on
profiles built by hand, and the window marker the child writes."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from conftest import BENCH, HERE
from lib import trace_reduce

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


# ------------------------------------------------ the window on one clock
MS = 1_000_000      # ns


def _ev(name, start_ms, dur_ms):
    return SimpleNamespace(name=name, start_ns=start_ms * MS,
                           duration_ns=dur_ms * MS)


def _profile(ops, mods, host):
    """A profile as ``reduce_profile`` reads it: ``ops`` / ``mods`` the
    events of one TPU's ``XLA Ops`` / ``XLA Modules`` lines, ``host`` those
    of a ``python3`` line of ``/host:CPU``."""
    line = lambda name, evs: SimpleNamespace(name=name, events=evs)  # noqa
    return SimpleNamespace(planes=[
        SimpleNamespace(name="/device:TPU:0", lines=[
            line("XLA Modules", mods), line("XLA Ops", ops)]),
        SimpleNamespace(name="/host:CPU", lines=[line("python3", host)]),
        SimpleNamespace(name="/host:metadata", lines=[])])


def test_a_saturated_device_reads_busy_within_the_marker():
    """Back-to-back 1 ms folds from 0.5 ms before the 6 ms marker to 0.5 ms
    after it, as a chip-bound flood's trace holds them while stop_trace
    collects: busy 7 ms of the trace, 6 ms of the window."""
    starts = [0.5 + k for k in range(7)]
    ops = [_ev("fusion.763" if k % 2 else "fusion.517", s, 1.0)
           for k, s in enumerate(starts)]
    mods = [_ev("jit_fn_connresp(1)", s, 1.0) for s in starts]
    host = [_ev("slab_decode", 0.2, 0.3), _ev(trace_reduce.WINDOW_MARKER,
                                                 1.0, 6.0)]
    r = trace_reduce.reduce_profile(_profile(ops, mods, host))
    assert r["window_from"] == "marker"
    assert r["window_s"] == pytest.approx(6e-3, abs=1e-12)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["busy_s"] == pytest.approx(6e-3, abs=1e-12)
    # what lies outside: half a fold each side, 7 ms of busy in the trace
    assert r["busy_outside_s"] == pytest.approx([0.5e-3, 0.5e-3], abs=1e-12)
    # per op name, clipped: 0.5 + 1 + 1 + 0.5 (even k) and 1 + 1 + 1 (odd)
    assert dict(r["device_ops"]) == pytest.approx(
        {"fusion.517": 3e-3, "fusion.763": 3e-3}, abs=1e-12)
    # programs count where they START: the six from 1.5 ms on, each whole;
    # inside the window, every program's overlap with it: the half of
    # the first and of the last that lie in it, and the five between
    (name, n, sec), = r["modules"]
    assert name == "jit_fn_connresp(1)" and n == 6
    assert sec == pytest.approx(6e-3, abs=1e-12)
    assert r["module_window_s"] == pytest.approx({name: 6e-3}, abs=1e-12)
    assert r["idle_gaps"] == []


def test_shares_of_a_saturated_window_stay_within_it():
    """The readers over the reduction of a device saturated with section
    folds across the marker: idle 0, the folds' share 100 %, neither
    past its end, and the mean per fold whole."""
    import run
    starts = [0.5 + k for k in range(7)]
    ops = [_ev("fusion.1", s, 1.0) for s in starts]
    mods = [_ev("jit_fn_sections_listener(1)", s, 1.0) for s in starts]
    r = trace_reduce.reduce_profile(_profile(ops, mods, [
        _ev(trace_reduce.WINDOW_MARKER, 1.0, 6.0)]))
    ctx = run.MetricCtx({"_timings": {}, "_t": 0.0},
                        {"_timings": {}, "_t": 1.0}, 1.0, {}, r, {},
                        {"platform": "tpu", "kind": "TPU v5 lite",
                         "count": 1}, 0)
    assert run.read_metric("device_idle_share", ctx) == pytest.approx(
        0.0, abs=1e-9)
    assert run.read_metric("section_fold_share", ctx) == pytest.approx(
        100.0, abs=1e-9)
    assert run.read_metric("section_fold_device_ms", ctx) == pytest.approx(
        1.0, abs=1e-9)


def test_idle_inside_the_marker_counts_its_leading_and_trailing_stretch():
    ops = [_ev("sort", 2.5, 1.0), _ev("sort", 5.0, 1.0), _ev("sort", 11, 1)]
    mods = [_ev("jit_sort(2)", s, 1.0) for s in (2.5, 5.0, 11.0)]
    host = [_ev(trace_reduce.WINDOW_MARKER, 0.0, 10.0),
            _ev("tick", 6.2, 3.5), _ev("slab_decode", 0.5, 1.0)]
    r = trace_reduce.reduce_profile(_profile(ops, mods, host))
    assert r["window_from"] == "marker"
    assert r["busy_s"] == pytest.approx(2e-3, abs=1e-12)
    assert r["busy_outside_s"] == pytest.approx([0.0, 1e-3], abs=1e-12)
    assert r["modules"][0][:2] == ["jit_sort(2)", 2]
    # the trailing 4 ms, the leading 2.5 ms and the 1.5 ms between, longest
    # first, each named by the host event that overlaps it most (the
    # marker, which overlaps them all, names none)
    assert [g[0] for g in r["idle_gaps"]] == [
        "python3: tick", "python3: slab_decode", "host: no event"]
    assert [g[1] for g in r["idle_gaps"]] == pytest.approx(
        [4e-3, 2.5e-3, 1.5e-3], abs=1e-12)
    assert 1.0 - r["busy_s"] / r["window_s"] == pytest.approx(0.8)


def test_a_trace_without_the_marker_takes_the_device_span():
    ops = [_ev("sort", 2.0, 1.0), _ev("sort", 5.0, 1.0)]
    mods = [_ev("jit_sort(2)", 1.99, 1.02), _ev("jit_sort(2)", 4.99, 1.02)]
    r = trace_reduce.reduce_profile(_profile(ops, mods, [
        _ev("tick", 3.0, 2.0)]))
    assert r["window_from"] == "span"
    # the span opens with the first program's event, 10 us before its op
    assert r["window_s"] == r["traced_s"] == pytest.approx(4.02e-3,
                                                          abs=1e-12)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["busy_s"] == pytest.approx(2e-3, abs=1e-12)
    assert r["busy_outside_s"] == [0.0, 0.0]
    assert r["modules"][0][1] == 2
    assert r["idle_gaps"][0] == ["python3: tick", pytest.approx(2e-3)]


def test_the_child_marks_its_traced_window(tmp_path):
    """``child._trace`` on CPU jax with a 0.2 s window: exactly one
    ``bench_window`` event on a host plane, as long as the sleep."""
    code = (
        "import glob, json, sys\n"
        f"sys.path.insert(0, {BENCH!r})\n"
        "from lib import child\n"
        "from jax.profiler import ProfileData\n"
        "d = sys.argv[1]\n"
        "child._trace(d, 0.0, 0.2)\n"
        "pb, = glob.glob(d + '/plugins/profile/*/*.xplane.pb')\n"
        "print(json.dumps([[p.name, ev.duration_ns]\n"
        "    for p in ProfileData.from_file(pb).planes\n"
        "    for ln in p.lines for ev in ln.events\n"
        "    if ev.name == 'bench_window']))\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, "-c", code, str(tmp_path)],
                       capture_output=True, text=True, env=env,
                       timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    (plane, dur), = json.loads(p.stdout.strip().splitlines()[-1])
    assert plane.startswith("/host:")
    assert abs(dur * 1e-9 - 0.2) < 0.05
    done = json.loads((tmp_path / "trace_done.json").read_text())
    assert done["t_start"] < done["t_stop"] <= done["t_written"]
