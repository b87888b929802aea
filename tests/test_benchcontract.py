"""bench.py's no-fallback contract (ISSUE 21), checked without running a
phase: the orchestrator is driven with canned leaf results.

- a CPU device never prints a ``_per_chip`` metric name;
- a failed or timed-out phase makes the run exit non-zero (after the
  result line, so what completed is still on record);
- a leaf that found no accelerator ends the run at once;
- the orchestrator itself imports no jax (one process owns the chip).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import bench  # noqa: E402


def _leaf(phase: str, device: str) -> dict:
    metric = bench._PHASE_METRIC[phase]
    return {metric: 100.0, "device": device, "device_count": 1}


def _run(monkeypatch, tmp_path, capsys, leaf_of):
    monkeypatch.setenv("GYT_BENCH_PARTIAL", str(tmp_path / "p.jsonl"))
    monkeypatch.setenv("GYT_BENCH_RUNS", "1")
    monkeypatch.setattr(bench, "_phase_subproc",
                        lambda phase, platform: leaf_of(phase))
    try:
        bench._orchestrate(None)
        rc = 0
    except SystemExit as e:
        rc = e.code
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    return rc, (json.loads(lines[-1]) if lines else None)


@pytest.mark.parametrize("device,metric,has_baseline", [
    ("cpu:cpu", "flow_events_per_sec_cpu_backend", False),
    ("tpu:TPU v5 lite", "flow_events_per_sec_per_chip", True),
])
def test_metric_name_follows_the_device(monkeypatch, tmp_path, capsys,
                                        device, metric, has_baseline):
    rc, out = _run(monkeypatch, tmp_path, capsys,
                   lambda ph: _leaf(ph, device))
    assert rc == 0
    assert out["metric"] == metric and out["device"] == device
    assert ("vs_baseline" in out) == has_baseline
    assert "phases_failed" not in out


@pytest.mark.parametrize("marker", [{"failed": True, "rc": 1},
                                    {"timeout": True}])
def test_failed_phase_exits_nonzero(monkeypatch, tmp_path, capsys,
                                    marker):
    rc, out = _run(
        monkeypatch, tmp_path, capsys,
        lambda ph: dict(marker) if ph == "feed_ns"
        else _leaf(ph, "tpu:TPU v5 lite"))
    assert rc == 1
    assert out["phases_failed"] == ["feed_ns"]
    assert out["value"] == 100.0          # what completed is on record


def test_no_accelerator_leaf_ends_the_run(monkeypatch):
    class _R:
        returncode = bench.RC_NO_ACCELERATOR
        stdout = stderr = ""

    monkeypatch.setattr(subprocess, "run", lambda *a, **k: _R())
    with pytest.raises(SystemExit) as e:
        bench._phase_subproc("fold_toy", None)
    assert e.value.code == bench.RC_NO_ACCELERATOR


def test_orchestrator_imports_no_jax():
    """Importing bench and reaching the orchestrator must not import
    jax: the leaf of the moment is the one process that may hold the
    chip."""
    code = ("import sys; sys.path.insert(0, %r); import bench; "
            "from gyeeta_tpu.utils import xlacache; "
            "xlacache.configure({}); "
            "assert 'jax' not in sys.modules, 'jax imported'" % HERE)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
