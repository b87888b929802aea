"""Process start-up contracts (ISSUE 21): who may touch the accelerator,
where the compile cache lives, and what a serving process says about
the device it took.
"""

from __future__ import annotations

import os
import subprocess
import sys

import numpy as np
import pytest

from gyeeta_tpu.utils import xlacache

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _py(code: str, **env) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=180, cwd=HERE,
        env={**os.environ, "PYTHONPATH": HERE, **env})


# ------------------------------------------------------ one chip owner
def test_importing_the_package_opens_no_backend():
    """A module-level device array (a ``jnp`` constant) initializes the
    backend at import: on a chip host, importing the engine would then
    take the chip from the process that serves — and the CPU
    device-count flag ``serve --shards`` sets would come too late."""
    r = _py("import gyeeta_tpu.server_main, gyeeta_tpu.parallel.shardedrt,"
            " gyeeta_tpu.history.compactor, gyeeta_tpu.query.api\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()")
    assert r.returncode == 0, r.stderr[-2000:]


@pytest.mark.parametrize("module", [
    "gyeeta_tpu.cli", "gyeeta_tpu.net.agent", "gyeeta_tpu.sim.partha",
    "gyeeta_tpu.net.gateway", "gyeeta_tpu.net.relay",
    "gyeeta_tpu.ingest.decode", "gyeeta_tpu.utils.xlacache"])
def test_launchers_and_clients_import_no_jax(module):
    """Whatever starts or feeds a chip-owning child stays off jax."""
    r = _py(f"import sys, {module}\n"
            "assert 'jax' not in sys.modules, 'jax imported'")
    assert r.returncode == 0, r.stderr[-2000:]


def test_shards_force_virtual_devices_only_on_cpu(monkeypatch):
    """``serve --shards N`` forces N host devices under
    ``JAX_PLATFORMS=cpu`` and leaves every other backend's view of its
    devices alone."""
    import argparse

    from gyeeta_tpu import server_main as SM

    class _Stop(Exception):
        pass

    def boom(*a, **k):
        raise _Stop

    # stop before any backend work: only the environment is under test
    monkeypatch.setattr(SM, "Runtime", boom)
    monkeypatch.setattr("jax.devices", boom)
    args = argparse.Namespace(shards=4)
    for plat, forced in (("cpu", True), ("tpu", False), ("", False)):
        monkeypatch.setenv("JAX_PLATFORMS", plat)
        monkeypatch.setenv("XLA_FLAGS", "")
        with pytest.raises(_Stop):
            SM._make_runtime(args, None, None)
        assert ("xla_force_host_platform_device_count=4"
                in os.environ["XLA_FLAGS"]) == forced, plat


# ------------------------------------------------------- compile cache
def test_cache_dir_from_outside_is_left_alone():
    env = {"JAX_COMPILATION_CACHE_DIR": "/some/dir"}
    assert xlacache.configure(env) == "/some/dir"
    assert env["JAX_COMPILATION_CACHE_DIR"] == "/some/dir"


@pytest.mark.parametrize("env", [{}, {"JAX_COMPILATION_CACHE_DIR": ""}])
def test_cache_dir_default_is_fixed_and_in_the_checkout(env):
    got = xlacache.configure(env)
    assert got == os.path.join(HERE, ".jax_cache") == xlacache.default_dir()
    # one directory, whoever asks and whenever: nothing of the process,
    # the time, the version or the user is in the path
    assert xlacache.configure({}) == got
    with open(os.path.join(HERE, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
    # every program is cached, so hits + misses count every program
    assert env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] == "0"
    assert env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] == "-1"


def test_serving_process_compiles_into_the_given_cache(tmp_path):
    """``JAX_COMPILATION_CACHE_DIR=/some/dir`` reaches a fresh process
    through the CLI's placement call: its programs land there, and a
    second process finds them (hits, no miss)."""
    code = (
        "from gyeeta_tpu.utils import xlacache; xlacache.configure()\n"
        "from gyeeta_tpu.obs.xlamon import XlaMonitor\n"
        "m = XlaMonitor()\n"
        "import jax, jax.numpy as jnp\n"
        "jax.jit(lambda x: (x * 3 + 1).sum())(jnp.arange(7.0))"
        ".block_until_ready()\n"
        "c = m.stats.counters\n"
        "print(c['xla_programs'], c['xla_cache_hits'],"
        " c['xla_cache_misses'])")
    cache = tmp_path / "xla"
    first = _py(code, JAX_COMPILATION_CACHE_DIR=str(cache))
    assert first.returncode == 0, first.stderr[-2000:]
    progs, hits, misses = map(int, first.stdout.split())
    assert progs == misses > 0 and hits == 0
    assert len(list(cache.iterdir())) >= progs
    second = _py(code, JAX_COMPILATION_CACHE_DIR=str(cache))
    assert second.returncode == 0, second.stderr[-2000:]
    assert list(map(int, second.stdout.split())) == [progs, progs, 0]


# ------------------------------------------------- the device, stated
def test_serverstatus_states_the_device():
    import jax

    from gyeeta_tpu.engine.aggstate import EngineCfg
    from gyeeta_tpu.runtime import Runtime

    rt = Runtime(EngineCfg(svc_capacity=64, n_hosts=4, task_capacity=64,
                           conn_batch=64, resp_batch=64, fold_k=2))
    try:
        for consistency in ("strong", "snapshot"):
            row = rt.query({"subsys": "serverstatus",
                            "consistency": consistency})["recs"][0]
            assert row["platform"] == jax.devices()[0].platform == "cpu"
            assert row["devicekind"] == jax.devices()[0].device_kind
            assert row["ndevices"] == len(jax.devices())
    finally:
        rt.close()


def test_device_gauges_absent_where_the_backend_reports_none():
    """The CPU backend has no ``memory_stats``: no gauge, not a zero."""
    from gyeeta_tpu.obs import xlamon
    assert xlamon.device_gauges() == {}
    assert np.isfinite(xlamon.device_info()["ndevices"])


def test_chip_smoke_constants_follow_the_engine_defaults():
    """``chip_smoke.py`` cannot import the engine (its parent stays off
    jax), so it restates the defaults its recount depends on."""
    sys.path.insert(0, HERE)
    import chip_smoke as C

    from gyeeta_tpu.engine.aggstate import EngineCfg
    d = EngineCfg()
    assert C.ENGINE_DEFAULTS == {"conn_batch": d.conn_batch,
                                 "resp_batch": d.resp_batch,
                                 "fold_k": d.fold_k}
    assert C.RESP_SPEC == tuple(d.resp_spec)
    assert C.HLL_P_SVC == d.hll_p_svc
    # the cut is one halving of the published slab, at the same load
    assert C.FLEET_4["engine"]["svc_capacity"] == 2 * C.SVC_CAPACITY
    assert C.FLEET["hosts"] * C.FLEET["svcs"] * 2 == C.SVC_CAPACITY
