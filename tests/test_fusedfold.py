"""The fused ``fold_all`` dispatch against the composition it stands for.

A ``Runtime`` stages every drained subsystem chunk and folds the staged
sections, the conn/resp K-slab and the dependency graph in ONE
``step.fold_all`` dispatch. The reference here is no second runtime: it
is ``step.ingest_*`` composed in the test — every dispatch the runtime
makes is recorded (which sections, their lanes as they crossed to the
device, the tick) and applied to a second ``(state, dep)`` by one
separately jitted ``ingest_listener / ingest_host / ingest_task /
ingest_cpumem / ingest_trace / ping_tasks / ingest_delta / fold_many +
dep_fold_many`` per section, in ``step.FOLD_ALL_ORDER``. Both must hold
the SAME ``AggState`` and ``DepGraph`` bit for bit: fusion changes
dispatch grouping, never fold semantics. The mesh runtime's slab
dispatch is held to its three-dispatch composition the same way.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

from gyeeta_tpu.engine import aggstate, step
from gyeeta_tpu.engine.aggstate import EngineCfg
from gyeeta_tpu.ingest import pack, wire
from gyeeta_tpu.parallel import depgraph as dg
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


def _digest(state, dep) -> tuple:
    return tuple(np.asarray(x).tobytes()
                 for x in jax.tree.leaves(state) + jax.tree.leaves(dep))


# wire subtype → the runtime's record counter
_COUNTERS = {
    wire.NOTIFY_TCP_CONN: "conn_events",
    wire.NOTIFY_RESP_SAMPLE: "resp_events",
    wire.NOTIFY_LISTENER_STATE: "listener_records",
    wire.NOTIFY_AGGR_TASK_STATE: "task_records",
    wire.NOTIFY_CPU_MEM_STATE: "cpumem_records",
    wire.NOTIFY_REQ_TRACE: "trace_records",
    wire.NOTIFY_TASK_PING: "task_pings",
    wire.NOTIFY_HOST_STATE: "host_records",
}


def _records_sent(streams) -> dict:
    """Records per counter in the streams, from the frame decoder alone."""
    sent = dict.fromkeys(_COUNTERS.values(), 0)
    for s in streams:
        frames, used = wire.decode_frames(s)
        assert used == len(s)
        for subtype, recs in frames:
            sent[_COUNTERS[subtype]] += len(recs)
    return sent


class _Composition:
    """A second ``(state, dep)``, folded one section per dispatch."""

    def __init__(self, rt):
        cfg = rt.cfg
        self.state = aggstate.init(cfg)
        self.dep = dg.init(rt.opts.dep_pair_capacity,
                           rt.opts.dep_edge_capacity)
        self.dispatches = 0
        self.lanes = dict.fromkeys(step.FOLD_ALL_ORDER, 0)
        one = lambda f: jax.jit(lambda st, b: f(cfg, st, b))  # noqa: E731
        self._fold = {
            "listener": one(step.ingest_listener),
            "host": one(step.ingest_host),
            "task": one(step.ingest_task),
            "cpumem": one(step.ingest_cpumem),
            "trace": one(step.ingest_trace),
            "ping": one(step.ping_tasks),
        }
        self._delta = jax.jit(
            lambda st, dep, b, t: step.ingest_delta(cfg, st, dep, b, t))
        self._connresp = jax.jit(
            lambda st, cbs, rbs: step.fold_many(cfg, st, cbs, rbs))
        self._dep = jax.jit(dg.dep_fold_many)
        self._td_flush = jax.jit(
            lambda st: step.td_flush_partial(cfg, st))
        self._shadow(rt)

    def _shadow(self, rt) -> None:
        """Every ``fold_all`` dispatch and digest flush of ``rt`` is
        applied here too."""
        real_get, real_flush = rt._get_fold_all, rt._td_flush_partial

        def get(names):
            fold = real_get(names)

            def dispatch(st, dep, tick, block, layout):
                self.apply(names, tick, np.array(block), layout)
                return fold(st, dep, tick, block, layout)
            return dispatch

        def flush(st):
            self.state = self._td_flush(self.state)
            return real_flush(st)

        rt._get_fold_all, rt._td_flush_partial = get, flush

    def apply(self, names, tick, block, layout) -> None:
        # the sections as they lie in the block that crossed: host views
        # by pack's own offsets, not the traced unpack under test
        treedef, leaves = layout
        offs, _ = pack.offsets(leaves)
        secs = dict(zip(names, jax.tree.unflatten(treedef, [
            pack._slot(block, off, dt, shape)
            for off, (dt, shape) in zip(offs, leaves)])))
        self.dispatches += 1
        for kind in step.FOLD_ALL_ORDER:
            if kind not in secs:
                continue
            sec = secs[kind]
            if kind == "connresp":
                cbs, rbs = sec
                self.state = self._connresp(self.state, cbs, rbs)
                self.dep = self._dep(self.dep, cbs, tick)
                self.lanes["connresp"] += int(cbs.valid.sum()) \
                    + int(rbs.valid.sum())
                continue
            if kind == "delta":
                self.state, self.dep = self._delta(self.state, self.dep,
                                                   sec, tick)
            else:
                self.state = self._fold[kind](self.state, sec)
            self.lanes[kind] += int(sec.valid.sum())


def _check(rt, ref, streams) -> None:
    rt.flush()
    rt.td_drain()
    assert ref.dispatches == rt.stats.counters["fold_dispatches"] > 0
    assert _digest(rt.state, rt.dep) == _digest(ref.state, ref.dep), \
        "fold_all diverged from the per-section composition"
    # record accounting: staging never loses a record — what the frames
    # carried was counted, and every counted record crossed in a lane
    c = rt.stats.counters
    sent = _records_sent(streams)
    for k, n in sent.items():
        assert c.get(k, 0) == n, k
    assert ref.lanes["connresp"] == (
        sent["conn_events"] + sent["resp_events"]
        + c.get("resp_from_trace", 0))
    for kind, ctr in (("listener", "listener_records"),
                      ("host", "host_records"), ("task", "task_records"),
                      ("cpumem", "cpumem_records"),
                      ("trace", "trace_records"), ("ping", "task_pings")):
        assert ref.lanes[kind] == sent[ctr], kind


@pytest.mark.slow   # ~3 min on 1 vCPU; the byte-chunked parity test
                    # below keeps the same check in the fast tier
def test_fused_vs_legacy_parity_fuzz():
    """500-stream mixed-subsystem fuzz: every dispatch equals the
    per-section composition, bit for bit."""
    from gyeeta_tpu.runtime import Runtime

    streams = [_mixed_stream(seed) for seed in range(500)]
    rt = Runtime(_small_cfg())
    ref = _Composition(rt)
    rng = np.random.default_rng(99)
    for i, s in enumerate(streams):
        # a few streams land split at a random read boundary. Kept to a
        # handful on purpose: every distinct section-presence combo a
        # split produces compiles its own fold_all variant (seconds
        # each) — byte-granular chopping is
        # test_fused_byte_chunked_parity's job; here the fuzz mass is
        # 500 distinct streams
        if i < 4 and len(s) > 2:
            cut = int(rng.integers(1, len(s)))
            rt.feed(s[:cut])
            rt.feed(s[cut:])
        else:
            rt.feed(s)
    try:
        _check(rt, ref, streams)
    finally:
        rt.close()


def test_fused_byte_chunked_parity():
    """One long read, then byte-granular random read boundaries → the
    same state as the per-section composition of the same dispatches.
    (Chunking itself is
    allowed to permute service-row assignment — a read boundary decides
    whether a conn K-slab folds before or after a later sweep chunk, so
    whichever stream first claims a row differs; the parity contract is
    per-chunking, and the 500-stream fuzz covers many chunkings.)"""
    from gyeeta_tpu.runtime import Runtime

    streams = [_mixed_stream(seed) for seed in range(12)]
    rt = Runtime(_small_cfg())
    ref = _Composition(rt)
    rng = np.random.default_rng(7)
    # the first read is four streams long: every section and a full
    # slab in ONE dispatch onto an empty table, where the order of the
    # sub-folds decides who claims which row (reads of a few kB hold a
    # section or two and would pass a fold_all that folds out of order)
    rt.feed(b"".join(streams[:4]))
    assert rt.stats.counters["slab_dispatches"] > 0
    for s in streams[4:]:
        off = 0
        while off < len(s):
            n = int(rng.integers(1, 4096))
            rt.feed(s[off: off + n])
            off += n
    try:
        _check(rt, ref, streams)
    finally:
        rt.close()


@pytest.mark.slow
def test_sharded_fused_vs_legacy():
    """ShardedRuntime: the one fold+dep+pressure slab dispatch matches
    its three-dispatch composition bit for bit (simulated mesh)."""
    from gyeeta_tpu.parallel import sharded
    from gyeeta_tpu.parallel.shardedrt import ShardedRuntime

    streams = [_mixed_stream(seed) for seed in range(30)]
    rt = ShardedRuntime(_small_cfg())
    cfg, mesh = rt.cfg, rt.mesh
    copy = jax.jit(lambda t: jax.tree.map(lambda x: x.copy(), t))
    ref = dict(zip(("state", "dep"), copy((rt.state, rt.dep))))
    fold = sharded.fold_step_sharded(cfg, mesh)
    pressure = sharded.td_pressure_sharded(mesh)
    slabs = []

    def composed(real, cap_per_dest):
        dep_step = dg.dep_step_fn(mesh, cap_per_dest=cap_per_dest)

        def dispatch(st, dep, cbs, rbs, tick):
            ref["state"] = fold(ref["state"], cbs, rbs)
            ref["dep"] = dep_step(ref["dep"], cbs, tick)
            out = real(st, dep, cbs, rbs, tick)
            assert int(out[2]) == int(pressure(ref["state"]))
            slabs.append(cap_per_dest)
            return out
        return dispatch

    def mirrored(real, with_dep=False):
        """The sweep folds and the digest flush are not under test:
        the reference state takes the same ones."""
        def dispatch(st, *a):
            if with_dep:
                ref["state"], ref["dep"] = real(ref["state"], ref["dep"],
                                                *a[1:])
            else:
                ref["state"] = real(ref["state"], *a)
            return real(st, *a)
        return dispatch

    rt._fold_dep_slab = composed(rt._fold_dep_slab,
                                 cfg.conn_batch * cfg.fold_k)
    rt._fold_dep_chunk = composed(rt._fold_dep_chunk, cfg.conn_batch)
    rt._td_flush = mirrored(rt._td_flush)
    rt._sect_folds = {
        kind: (mirrored(f, with_dep=kind == "delta"), lanes)
        for kind, (f, lanes) in rt._sect_folds.items()}
    try:
        for s in streams:
            rt.feed(s)
        rt.flush()
        assert len(set(slabs)) == 2      # slab- and chunk-width both ran
        assert _digest(rt.state, rt.dep) == _digest(ref["state"],
                                                    ref["dep"])
    finally:
        rt.close()


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
