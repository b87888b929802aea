"""Fused fold_all megakernel vs the legacy per-subsystem dispatch
sequence: bit-identical state over a mixed-subsystem fuzz.

The fused path (``GYT_FUSED_FOLD=1``, the default) stages every drained
subsystem chunk and folds them in ONE ``step.fold_all`` dispatch per
feed batch; the legacy escape hatch (``GYT_FUSED_FOLD=0``) issues one
donated jit per subsystem. Both must produce the SAME ``AggState`` and
``DepGraph`` bit-for-bit — fold_all applies sub-folds in the drain
order (``step.FOLD_ALL_ORDER``), so fusion changes dispatch grouping,
never fold semantics. This is the PR-1 parity-fuzz pattern pointed at
the dispatch layer instead of the decoder.
"""

from __future__ import annotations

import numpy as np
import pytest

from gyeeta_tpu.engine.aggstate import EngineCfg
from gyeeta_tpu.ingest import wire
from gyeeta_tpu.sim.partha import ParthaSim
from gyeeta_tpu.sketch import loghist


def _small_cfg() -> EngineCfg:
    return EngineCfg(
        svc_capacity=64, n_hosts=8,
        resp_spec=loghist.LogHistSpec(vmin=1.0, vmax=1e8, nbuckets=32),
        hll_p_svc=4, hll_p_global=8, cms_depth=2, cms_width=1 << 8,
        topk_capacity=16, topk_budget=48, td_capacity=16,
        conn_batch=64, resp_batch=128, listener_batch=32, fold_k=4)


def _mixed_stream(seed: int, shuffle: bool = True) -> bytes:
    """One fuzz stream: every device-fold subsystem, random sizes,
    subsystem order shuffled per stream."""
    sim = ParthaSim(n_hosts=8, n_svcs=4, seed=seed)
    rng = np.random.default_rng(seed)
    parts = [
        sim.listener_frames(),
        sim.conn_frames(int(rng.integers(48, 260))),
        sim.resp_frames(int(rng.integers(48, 380))),
        sim.task_frames(),
        wire.encode_frames_chunked(wire.NOTIFY_CPU_MEM_STATE,
                                   sim.cpu_mem_records()),
        sim.trace_frames(int(rng.integers(8, 32))),
        wire.encode_frames_chunked(wire.NOTIFY_HOST_STATE,
                                   sim.host_state_records()),
    ]
    # keepalive pings for a few announced task groups (refresh-only)
    tasks = sim.aggr_task_records()
    pings = np.zeros(min(8, len(tasks)), wire.TASK_PING_DT)
    pings["aggr_task_id"] = tasks["aggr_task_id"][: len(pings)]
    pings["host_id"] = tasks["host_id"][: len(pings)]
    parts.append(wire.encode_frames_chunked(wire.NOTIFY_TASK_PING,
                                            pings))
    if shuffle:
        rng.shuffle(parts)
    return b"".join(parts)


def _digest(rt) -> tuple:
    import jax

    leaves = jax.tree.leaves(rt.state) + jax.tree.leaves(rt.dep)
    return tuple(np.asarray(x).tobytes() for x in leaves)


def _run(monkeypatch, fused: bool, streams, chunk_seed: int) -> tuple:
    from gyeeta_tpu import runtime as rtmod

    monkeypatch.setenv("GYT_FUSED_FOLD", "1" if fused else "0")
    rt = rtmod.Runtime(_small_cfg())
    assert rt._fused is fused     # the env hatch actually selects paths
    rng = np.random.default_rng(chunk_seed)
    for i, s in enumerate(streams):
        # a few streams land split at a random read boundary. Kept to a
        # handful on purpose: every distinct section-presence combo a
        # split produces compiles its own fold_all variant (seconds
        # each) — byte-granular chopping is
        # test_fused_chunking_invariance's job; here the fuzz mass is
        # 500 distinct streams
        if i < 4 and len(s) > 2:
            cut = int(rng.integers(1, len(s)))
            rt.feed(s[:cut])
            rt.feed(s[cut:])
        else:
            rt.feed(s)
    rt.flush()
    rt.td_drain()
    d = _digest(rt)
    counters = dict(rt.stats.counters)
    rt.close()
    return d, counters


@pytest.mark.slow   # ~3 min on 1 vCPU; the byte-chunked parity test
                    # below keeps a fused==legacy digest check in the
                    # fast tier, and ci.sh smokes the fused path too
def test_fused_vs_legacy_parity_fuzz(monkeypatch):
    """500-stream mixed-subsystem fuzz: fused == legacy, bit for bit."""
    streams = [_mixed_stream(seed) for seed in range(500)]
    d_fused, c_fused = _run(monkeypatch, True, streams, chunk_seed=99)
    d_legacy, c_legacy = _run(monkeypatch, False, streams, chunk_seed=99)
    assert d_fused == d_legacy, \
        "fused fold_all diverged from the per-subsystem dispatch sequence"
    # record accounting must agree too (staging never loses a record)
    for k in ("conn_events", "resp_events", "listener_records",
              "task_records", "cpumem_records", "trace_records",
              "task_pings", "host_records"):
        assert c_fused.get(k, 0) == c_legacy.get(k, 0), k
    # and the fused path actually fused: fold dispatches happened
    assert c_fused.get("fold_dispatches", 0) > 0
    assert c_legacy.get("fold_dispatches", 0) == 0


def test_fused_byte_chunked_parity(monkeypatch):
    """Byte-granular random read boundaries, SAME boundaries on both
    paths → bit-identical state. (Chunking itself is allowed to permute
    service-row assignment on BOTH paths — a read boundary decides
    whether a conn K-slab folds before or after a later sweep chunk,
    so whichever stream first claims a row differs; the parity contract
    is per-chunking, and the 500-stream fuzz covers many chunkings.)"""
    from gyeeta_tpu import runtime as rtmod

    streams = [_mixed_stream(seed) for seed in range(8)]

    def run(fused: bool, chunk_seed: int):
        monkeypatch.setenv("GYT_FUSED_FOLD", "1" if fused else "0")
        rt = rtmod.Runtime(_small_cfg())
        rng = np.random.default_rng(chunk_seed)
        for s in streams:
            off = 0
            while off < len(s):
                step = int(rng.integers(1, 4096))
                rt.feed(s[off: off + step])
                off += step
        rt.flush()
        rt.td_drain()
        d = _digest(rt)
        rt.close()
        return d

    assert run(True, 7) == run(False, 7)


@pytest.mark.slow
def test_sharded_fused_vs_legacy(monkeypatch):
    """ShardedRuntime: the fused fold+dep+pressure dispatch matches the
    legacy three-dispatch sequence bit-for-bit (simulated mesh)."""
    from gyeeta_tpu.parallel.shardedrt import ShardedRuntime

    streams = [_mixed_stream(seed) for seed in range(30)]

    def run(fused: bool):
        import jax

        monkeypatch.setenv("GYT_FUSED_FOLD", "1" if fused else "0")
        rt = ShardedRuntime(_small_cfg())
        assert rt._fused is fused
        for s in streams:
            rt.feed(s)
        rt.flush()
        leaves = jax.tree.leaves(rt.state) + jax.tree.leaves(rt.dep)
        d = tuple(np.asarray(x).tobytes() for x in leaves)
        rt.close()
        return d

    assert run(True) == run(False)


def test_staging_buffer_not_rewritten_under_a_fold_in_flight():
    """The double-buffered conn/resp staging slabs are reused every
    second dispatch, and ``device_put`` reads host memory asynchronously
    (it may alias it outright on the CPU backend). With a device two
    dispatches behind the host, the decode of slab N used to rewrite the
    buffer slab N-2's fold had not read yet: response samples were
    folded under other services' ids — counted as unknown, or worse,
    silently attributed. Here every fold is preceded by device work that
    keeps the whole pool busy, so the host always runs ahead; every
    sample must still land on its own service."""
    import jax
    import jax.numpy as jnp

    from gyeeta_tpu.runtime import Runtime

    # default lane widths: staging arrays big enough to be transferred
    # asynchronously; a small slab keeps the fold itself cheap
    cfg = EngineCfg(svc_capacity=1024, n_hosts=64, task_capacity=256)
    rt = Runtime(cfg)
    sim = ParthaSim(n_hosts=16, n_svcs=16, seed=5)
    rt.feed(sim.listener_frames())
    rt.flush()
    big = jnp.ones((3000, 3000), jnp.float32)

    @jax.jit
    def busy(x):
        m = jax.lax.fori_loop(0, 8, lambda i, a: (a @ big) * 1e-4, big)
        return x + m[0, 0] * 0.0

    real = rt._get_fold_all

    def behind(names):
        fold = real(names)
        return lambda st, dep, tick, *secs: fold(
            st._replace(n_conn=busy(st.n_conn)), dep, tick, *secs)

    rt._get_fold_all = behind
    lanes_r = cfg.fold_k * cfg.resp_batch
    lanes_c = cfg.fold_k * cfg.conn_batch
    sent = []
    try:
        for _ in range(8):
            resp = sim.resp_records(lanes_r)
            sent.append(resp)
            rt.feed(wire.encode_frames_chunked(wire.NOTIFY_RESP_SAMPLE,
                                               resp)
                    + sim.conn_frames(lanes_c))
        rt.flush()
        assert float(np.asarray(rt.state.n_resp_unknown)) == 0.0
        resp = np.concatenate(sent)
        ids, want = np.unique(resp["glob_id"], return_counts=True)
        key = (np.asarray(rt.state.tbl.key_hi).astype(np.uint64)
               << np.uint64(32)) | np.asarray(rt.state.tbl.key_lo)
        row_of = {int(k): r for r, k in enumerate(key)}
        got = np.asarray(rt.state.resp_win.cur).sum(axis=1)
        assert [got[row_of[int(i)]] for i in ids] == want.tolist()
    finally:
        rt.close()
