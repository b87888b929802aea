"""The packed host→device block (``ingest/pack.py``): whatever sections
ride a fused dispatch cross as ONE flat ``uint32`` array, and the
compiled fold's first ops take them apart again — bit for bit."""

from __future__ import annotations

import jax
import numpy as np
import pytest

from gyeeta_tpu import runtime as R
from gyeeta_tpu.engine import step
from gyeeta_tpu.engine.aggstate import EngineCfg
from gyeeta_tpu.ingest import decode, pack, wire
from gyeeta_tpu.sim.partha import ParthaSim
from gyeeta_tpu.sketch import loghist

CFG = EngineCfg(
    svc_capacity=64, n_hosts=8,
    resp_spec=loghist.LogHistSpec(vmin=1.0, vmax=1e8, nbuckets=32),
    hll_p_svc=4, hll_p_global=8, cms_depth=2, cms_width=1 << 8,
    topk_capacity=16, topk_budget=48, td_capacity=16,
    conn_batch=64, resp_batch=128, listener_batch=32, fold_k=4)
K, CB, RB = CFG.fold_k, CFG.conn_batch, CFG.resp_batch


def _hostile(tree, seed: int):
    """The same tree with every leaf's CONTENT replaced: random bit
    patterns (NaN payloads, negative int32, denormals), explicit -0.0 /
    NaN / INT32_MIN lanes, flags both set and clear."""
    rng = np.random.default_rng(seed)

    def leaf(a):
        a = np.asarray(a)
        if a.dtype == np.bool_:
            out = rng.integers(0, 2, a.shape).astype(np.bool_)
            edge = np.array([True, False])
            out.reshape(-1)[:2] = edge[: out.size]
            return out
        out = rng.integers(0, 2 ** 32, a.shape, dtype=np.uint64).astype(
            np.uint32).view(a.dtype)
        flat = out.reshape(-1)
        if a.dtype == np.float32:
            # 0x7FC00BAD: a NaN with a payload
            edge = np.array([0x7FC00BAD, 0x80000000, 0x7FC00000,
                             0xFF800000], np.uint32).view(np.float32)
        elif a.dtype == np.int32:
            edge = np.array([-1, np.iinfo(np.int32).min], np.int32)
        else:
            return out
        flat[: len(edge)] = edge[: flat.size]
        return out

    return jax.tree.map(leaf, tree)


def _roundtrip(secs: tuple, into=None):
    leaves, treedef = jax.tree.flatten(secs)
    layout = pack.layout_of(leaves)
    block = pack.pack(leaves, into=into)
    assert block.dtype == np.uint32 and block.ndim == 1
    assert block.size == pack.offsets(layout)[1]
    got = jax.jit(lambda b: jax.tree.unflatten(
        treedef, pack.unpack(b, layout)))(block)
    return block, got


def _assert_same(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()       # NaN payloads included


@pytest.fixture(scope="module")
def builders():
    rt = R.Runtime(CFG)
    yield rt._sect_builders, rt._slab_lanes_cfg
    rt.close()


def _section(kind: str, builders, k: int = K):
    if kind == "connresp":
        return (decode.conn_slab([], k, CB), decode.resp_slab([], k, RB))
    build, lanes = builders
    empty = np.zeros(0, wire.DTYPE_OF_SUBTYPE[R._SECTION_SUBTYPES[kind]])
    return build[kind](empty, lanes[kind], None)


def _slab_records(seed: int, nc: int, nr: int):
    """conn + resp records; every third conn still open, every fourth
    client-observed, so ``is_close`` / ``is_accept`` are set AND clear."""
    sim = ParthaSim(n_hosts=8, n_svcs=4, seed=seed)
    conn = sim.conn_records(nc)
    conn["tusec_close"][::3] = 0
    conn["flags"][::4] &= ~np.uint32(2)
    return conn, sim.resp_records(nr)


def _decode_into(buf, conn, resp, clear_c=0, clear_r=0):
    _, ccols, rcols = buf
    return (decode.conn_slab([conn], K, CB, out=ccols, clear_to=clear_c),
            decode.resp_slab([resp], K, RB, out=rcols, clear_to=clear_r))


def _reference(conn, resp):
    """The NumPy decoders into fresh columns: what a slab must equal."""
    cb = decode.conn_batch(conn, K * CB)
    rb = decode.resp_batch(resp, K * RB)
    return (decode.ConnBatch(*(x.reshape(K, CB) for x in cb)),
            decode.RespBatch(*(x.reshape(K, RB) for x in rb)))


CASES = tuple(step.FOLD_ALL_ORDER) + (
    "single", "everything", "slab_in_place", "slab_partly_filled",
    "slab_refilled_smaller")


@pytest.mark.parametrize("case", CASES)
def test_pack_unpack_bit_for_bit(case, builders):
    if case in step.FOLD_ALL_ORDER:
        want = (_hostile(_section(case, builders), 11),)
        _, got = _roundtrip(want)
    elif case == "single":          # the (1, B) flush / boundary shape
        want = (_hostile(_section("connresp", builders, k=1), 12),)
        _, got = _roundtrip(want)
    elif case == "everything":
        want = tuple(_hostile(_section(k, builders), 13 + i)
                     for i, k in enumerate(step.FOLD_ALL_ORDER))
        _, got = _roundtrip(want)
    else:
        # the staged slab: decoded through the block's own views, so
        # the pack has nothing to copy and hands the block back itself
        buf = decode.alloc_slab_cols(K * CB, K * RB)
        nc, nr = {"slab_in_place": (K * CB, K * RB),
                  "slab_partly_filled": (K * CB // 3, 5),
                  "slab_refilled_smaller": (7, K * RB // 2)}[case]
        clear_c = clear_r = 0
        if case == "slab_refilled_smaller":
            # an earlier, larger fill leaves stale lanes behind
            big = _slab_records(3, K * CB - 1, K * RB)
            _decode_into(buf, *big)
            clear_c, clear_r = len(big[0]), len(big[1])
        conn, resp = _slab_records(4, nc, nr)
        slab = _decode_into(buf, conn, resp, clear_c, clear_r)
        want = (_reference(conn, resp),)
        block, got = _roundtrip((slab,), into=buf[0])
        assert block is buf[0]
        if decode.native.available():
            before = block.copy()
            leaves = jax.tree.leaves((slab,))
            assert all(np.shares_memory(a, block) for a in leaves)
            assert pack.pack(leaves, into=block) is block
            assert np.array_equal(block, before)
        for flag in ("is_close", "is_accept"):
            col = getattr(want[0][0], flag)
            assert col.any() and not col.all(), flag
        assert want[0][0].valid.sum() == nc and want[0][1].valid.sum() == nr
    _assert_same(got, want)


def test_pack_refuses_a_leaf_it_has_no_place_for():
    with pytest.raises(TypeError, match="float64"):
        pack.pack([np.zeros(3, np.float64)])


def test_pack_copies_leaves_that_lie_elsewhere():
    """A block of the right length whose leaves were NOT decoded in
    place (the NumPy fallback decoders return fresh columns) is filled
    by copy; a block of another length is left alone."""
    conn, resp = _slab_records(5, 40, 90)
    want = _reference(conn, resp)
    leaves = jax.tree.leaves(want)
    home = decode.alloc_slab_cols(K * CB, K * RB)[0]
    assert pack.pack(leaves, into=home) is home
    other = np.full(5, 7, np.uint32)
    fresh = pack.pack(leaves, into=other)
    assert fresh is not other and (other == 7).all()
    assert np.array_equal(fresh, home)


# ------------------------------------------------ the runtime's counter
def _h2d_per_dispatch(rt, feed) -> tuple:
    c = rt.stats.counters
    a0, d0 = c.get("h2d_arrays", 0), c.get("fold_dispatches", 0)
    feed()
    return (c.get("h2d_arrays", 0) - a0,
            c.get("fold_dispatches", 0) - d0)


@pytest.mark.parametrize("what", ["slab", "listener", "flush"])
def test_runtime_puts_one_array_a_dispatch(what):
    rt = R.Runtime(CFG)
    try:
        sim = ParthaSim(n_hosts=8, n_svcs=4, seed=9)
        feed = {
            "slab": lambda: rt.feed(sim.conn_frames(K * CB)
                                    + sim.resp_frames(K * RB)),
            "listener": lambda: rt.feed(sim.listener_frames()),
            "flush": lambda: (rt.feed(sim.conn_frames(5)
                                      + sim.resp_frames(9)), rt.flush()),
        }[what]
        arrays, dispatches = _h2d_per_dispatch(rt, feed)
        assert dispatches >= 1
        assert arrays / dispatches <= 2
        assert rt.stats.counters["h2d_bytes"] > 0
    finally:
        rt.close()
