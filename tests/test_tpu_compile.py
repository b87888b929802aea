"""Compile-only checks against the chip this system is deployed on.

The TPU's compiler is installed here and compiles for a chip that is
described, not attached (``/opt/skills/guides/on-chip-measurement`` §2,
rehearsal 3). Nothing runs, so these say nothing about results or
times — they say that the main path's programs, at the ``fleet-50k``
widths ``chip_smoke.py`` serves (131,072-row service slab, 50,048
hosts, 16×2048 conn + 16×4096 resp lanes per dispatch), are programs
the chip's compiler accepts, and how much device memory each needs.

This is the ONLY file that describes a chip. The description is made
inside the module-scoped ``topo`` fixture and nowhere else: only one
process may load the TPU library, every xdist worker imports every test
file, and a worker that loaded it keeps it until it exits. So nothing
here touches the topology at import, in a ``skipif`` or in a
``parametrize`` argument, and every compile happens in the test's own
process.

Tier-1 compiles the fold's components (a few seconds each). The slow
tier is the compile rehearsal before a chip call: every program of the
served path whole, then the four-device sharded fold and roll-up, each
held under the chip's 16 GB::

    JAX_PLATFORMS=cpu python -m pytest tests/test_tpu_compile.py \\
        -m slow -s
"""

import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from gyeeta_tpu.engine import aggstate, step, table
from gyeeta_tpu.engine.aggstate import EngineCfg
from gyeeta_tpu.ingest import decode, pack, wire
from gyeeta_tpu.parallel import depgraph as dg
from gyeeta_tpu.parallel import rollup, sharded
from gyeeta_tpu.semantic import derive
from gyeeta_tpu.sketch import countmin, hyperloglog as hll, loghist, \
    tdigest, topk

# the fleet-50k geometry (BASELINE.json, ROADMAP R1, chip_smoke.py)
FLEET = EngineCfg(svc_capacity=131072, n_hosts=50048,
                  task_capacity=65536)
DEP_PAIRS, DEP_EDGES = 65536, 524288
# the same fleet over four shards: the slabs split, the host space and
# the edge capacity (which is also the roll-up's merge capacity) do not
FLEET_SHARD = FLEET._replace(svc_capacity=FLEET.svc_capacity // 4,
                             task_capacity=FLEET.task_capacity // 4)
# the benchmark's fleet-50k deployment: the service slab halved
FLEET_50K = FLEET._replace(svc_capacity=65536)
CONN_LANES = FLEET.fold_k * FLEET.conn_batch      # 32,768
RESP_LANES = FLEET.fold_k * FLEET.resp_batch      # 65,536
HBM_BYTES = 16e9                                  # one v5e chip
# a copy, reshape or transpose this large in a fold program is a
# relayout of per-service state: the fold scatters into flat targets
# (sketch/flat.py) so that the chip's tiling of the state never changes
RELAYOUT_BYTES = 8 << 20
# the slab fold's temporaries at fleet-50k (compile-only: 176.8 MiB
# with flat targets, 350.4 MiB with the 2-D scatter targets relaid out)
SLAB_TEMP_BYTES = 200 << 20


@pytest.fixture(scope="module")
def topo():
    """The described (not attached) chip. Persistent cache off around
    these compiles: an executable compiled for a described chip is
    written to the cache but cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — any failure means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.asarray(topo.devices).reshape(4), ("hosts",))


@pytest.fixture
def tpu_branches(monkeypatch):
    """Code that asks ``jax.default_backend()`` while it is traced sees
    the CPU here and would compile its CPU branch for the chip
    (``sketch/topk.py`` sorts differently per backend). Steer it from
    the test: inside that module alone, jax answers as on the chip."""
    class _AnswersAsTpu:
        def __getattr__(self, name):
            return getattr(jax, name)

        @staticmethod
        def default_backend():
            return "tpu"

    monkeypatch.setattr(topk, "jax", _AnswersAsTpu())


def _on(tree, sharding):
    """Shapes of ``tree`` (arrays or ShapeDtypeStructs) placed by
    ``sharding`` — there is no device to hold an array."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=sharding), tree)


def _stacked(tree, mesh):
    """``tree`` with a leading shard axis, split over ``mesh``."""
    shd = NamedSharding(mesh, P("hosts"))
    n = mesh.devices.size
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((n,) + x.shape, x.dtype,
                                       sharding=shd), tree)


def _state(cfg):
    return jax.eval_shape(lambda: aggstate.init(cfg))


def _dep(pairs=DEP_PAIRS, edges=DEP_EDGES):
    return jax.eval_shape(lambda: dg.init(pairs, edges))


def _conn(lanes, k=None):
    none = np.zeros(0, wire.TCP_CONN_DT)
    return decode.conn_batch(none, lanes) if k is None \
        else decode.conn_slab(none, k, lanes // k)


def _resp(lanes, k=None):
    none = np.zeros(0, wire.RESP_SAMPLE_DT)
    return decode.resp_batch(none, lanes) if k is None \
        else decode.resp_slab(none, k, lanes // k)


def _compile(fn, *args, donate=()):
    """Compile for the described chip → (device bytes the program needs
    beyond what it aliases, seconds, memory analysis, the executable)."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    secs = time.perf_counter() - t0
    m = compiled.memory_analysis()
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    return need, secs, m, compiled


_ITEM = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
         "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
         "f64": 8}
_LAYOUT_OP = re.compile(
    r"(\S+) = (\w+)\[([\d,]*)\]\S* (copy|reshape|transpose)\(")


def _relayouts(compiled):
    """Every copy, reshape or transpose of the optimized program whose
    result is ``RELAYOUT_BYTES`` or more, as "name dtype[shape]"."""
    big = []
    for name, dt, dims, _ in _LAYOUT_OP.findall(compiled.as_text()):
        n = _ITEM.get(dt, 4)
        for d in filter(None, dims.split(",")):
            n *= int(d)
        if n >= RELAYOUT_BYTES:
            big.append(f"{name} {dt}[{dims}]")
    return big


def _report(name, need, secs, m):
    print(f"\n[v5e compile] {name}: {secs:.1f}s  "
          f"args={m.argument_size_in_bytes / 1e9:.3f}GB "
          f"out={m.output_size_in_bytes / 1e9:.3f}GB "
          f"alias={m.alias_size_in_bytes / 1e9:.3f}GB "
          f"temp={m.temp_size_in_bytes / 1e9:.3f}GB "
          f"code={m.generated_code_size_in_bytes / 1e6:.1f}MB "
          f"-> device={need / 1e9:.3f}GB")


# ------------------------------------------------------------ components
def _c_upsert(one):
    tbl = _on(jax.eval_shape(lambda: table.init(FLEET.svc_capacity)), one)
    cb = _on(_conn(CONN_LANES), one)
    return (lambda t, hi, lo, v: table.upsert(t, hi, lo, v),
            (tbl, cb.svc_hi, cb.svc_lo, cb.valid), (0,))


def _c_loghist(one):
    st = _on(_state(FLEET), one)
    rb = _on(_resp(RESP_LANES), one)
    rows = jax.ShapeDtypeStruct((RESP_LANES,), jnp.int32, sharding=one)
    return (lambda h, r, v, ok: loghist.update_entities(
        h, FLEET.resp_spec, r, v, valid=ok),
        (st.resp_win.cur, rows, rb.resp_us, rb.valid), (0,))


def _c_svc_hll(one):
    st = _on(_state(FLEET), one)
    cb = _on(_conn(CONN_LANES), one)
    rows = jax.ShapeDtypeStruct((CONN_LANES,), jnp.int32, sharding=one)
    return (lambda s, r, hi, lo, ok: hll.update_entities(
        s, FLEET.hll_p_svc, r, hi, lo, valid=ok),
        (st.svc_hll, rows, cb.cli_hi, cb.cli_lo, cb.valid), (0,))


def _c_td_stage(one):
    # the digest's duty cycle stages one resp lane in td_sample_stride
    st = _on(_state(FLEET_50K), one)
    lanes = RESP_LANES // FLEET.td_sample_stride
    rows = jax.ShapeDtypeStruct((lanes,), jnp.int32, sharding=one)
    vals = jax.ShapeDtypeStruct((lanes,), jnp.float32, sharding=one)
    return (tdigest.stage_samples,
            (st.td_stage, st.td_stage_n, rows, vals), (0, 1))


def _c_cms(one):
    st = _on(_state(FLEET), one)
    cb = _on(_conn(CONN_LANES), one)
    return (lambda s, hi, lo, v, ok: countmin.update(s, hi, lo, v,
                                                     valid=ok),
            (st.cms, cb.flow_hi, cb.flow_lo, cb.bytes_sent, cb.valid),
            (0,))


def _c_topk(one):
    st = _on(_state(FLEET), one)
    cb = _on(_conn(FLEET.topk_budget), one)
    return (lambda s, hi, lo, v, ok: topk.update(s, hi, lo, v, valid=ok),
            (st.flow_topk, cb.flow_hi, cb.flow_lo, cb.bytes_sent,
             cb.valid), (0,))


def _c_td_flush(one):
    return (lambda s: step.td_flush_partial(FLEET, s),
            (_on(_state(FLEET), one),), (0,))


# the components that write per-service state the slab fold (or the
# flush after it) scatters into: none may relayout that state
_FLAT_TARGETS = (_c_loghist, _c_svc_hll, _c_td_stage, _c_td_flush)


@pytest.mark.parametrize("build", [
    _c_upsert, _c_loghist, _c_svc_hll, _c_cms, _c_topk, _c_td_flush,
    _c_td_stage], ids=lambda f: f.__name__[3:])
def test_fold_component_compiles_for_v5e(one_chip, tpu_branches, build):
    """The fold's components at fleet widths: the 131,072-row upsert,
    the loghist / per-service HLL / CMS scatters, the top-K grouping
    sort at ``topk_budget`` lanes (the accelerator branch), the partial
    t-digest flush, the digest stage at fleet-50k. The scatters into
    per-service state, and the flush, relayout none of it."""
    fn, args, donate = build(one_chip)
    need, secs, m, compiled = _compile(fn, *args, donate=donate)
    _report(build.__name__[3:], need, secs, m)
    assert need < HBM_BYTES
    if build in _FLAT_TARGETS:
        assert _relayouts(compiled) == []


# --------------------------------------------- the served path, whole
def _p_fold_slab(one, cfg=FLEET):
    # the production fused dispatch, as Runtime._get_fold_all builds it
    # for the conn/resp K-slab: the 22 columns arrive as one packed
    # word block and the fold's first ops take them apart
    leaves, treedef = jax.tree.flatten(
        (_conn(CONN_LANES, FLEET.fold_k), _resp(RESP_LANES, FLEET.fold_k)))
    layout = pack.layout_of(leaves)
    block = jax.ShapeDtypeStruct((pack.offsets(layout)[1],), np.uint32,
                                 sharding=one)
    return (lambda s, d, b: step.fold_all(
        cfg, s, d, 0, connresp=jax.tree.unflatten(
            treedef, pack.unpack(b, layout))),
        (_on(_state(cfg), one), _on(_dep(), one), block), (0, 1))


def _p_fold_sweep(one):
    # the 5 s sweep variant: listener + host state sections
    lst = decode.listener_batch(
        np.zeros(0, wire.LISTENER_STATE_DT),
        2 * wire.MAX_LISTENERS_PER_BATCH)
    hst = decode.host_batch(np.zeros(0, wire.HOST_STATE_DT),
                            wire.MAX_HOSTS_PER_BATCH)
    return (lambda s, d, lb, hb: step.fold_all(FLEET, s, d, 0,
                                               listener=lb, host=hb),
            (_on(_state(FLEET), one), _on(_dep(), one), _on(lst, one),
             _on(hst, one)), (0, 1))


def _p_tick(one):
    return (lambda s: step.tick_5s(FLEET, s),
            (_on(_state(FLEET), one),), (0,))


def _p_classify(one):
    return (lambda s: derive.classify_pass(FLEET, s),
            (_on(_state(FLEET), one),), (0,))


def _p_snapshot_copy(one):
    return (lambda t: jax.tree.map(jnp.copy, t),
            ((_on(_state(FLEET), one), _on(_dep(), one)),), ())


@pytest.mark.slow
@pytest.mark.parametrize("build", [
    _p_fold_slab, _p_fold_sweep, _p_tick, _p_classify, _p_snapshot_copy],
    ids=lambda f: f.__name__[3:])
def test_served_program_fits_one_v5e(one_chip, tpu_branches, build):
    """Every program of the one-chip served path at the fleet geometry,
    whole (the partial t-digest flush is among the tier-1 cases):
    arguments + outputs − aliased + temporaries under 16 GB.
    (One program at a time: what ELSE the process keeps on the device —
    the published snapshot — is not in this count; see PERF.md.)"""
    fn, args, donate = build(one_chip)
    need, secs, m, _ = _compile(fn, *args, donate=donate)
    _report(build.__name__[3:], need, secs, m)
    assert need < HBM_BYTES


@pytest.mark.slow
@pytest.mark.parametrize("cfg", [FLEET_50K, FLEET],
                         ids=lambda c: f"S{c.svc_capacity}")
def test_slab_fold_relayouts_no_state(one_chip, tpu_branches, cfg):
    """The slab fold scatters into the per-service HLL registers, the
    resp histogram's current slab and the digest stage at flat indices,
    so the program copies, reshapes or transposes none of them (a 2-D
    scatter target costs a relayout into the flat tiling and back on
    every slab), and its temporaries stay small."""
    fn, args, donate = _p_fold_slab(one_chip, cfg)
    need, secs, m, compiled = _compile(fn, *args, donate=donate)
    _report(f"fold_slab S={cfg.svc_capacity}", need, secs, m)
    assert _relayouts(compiled) == []
    assert m.temp_size_in_bytes < SLAB_TEMP_BYTES


# ------------------------------------------------ four chips, one program
@pytest.mark.slow
def test_sharded_fold_and_rollup_fit_four_v5e(four_chips, tpu_branches):
    """``serve --shards 4``: the sharded fused fold (per-shard fold +
    dep fold + the all_to_all pairing of one-sided conn halves) and the
    once-per-tick fleet roll-up collective, on a Mesh of the described
    2x2 devices. ``memory_analysis`` of an SPMD program is per device."""
    mesh = four_chips
    st = _stacked(_state(FLEET_SHARD), mesh)
    dep = _stacked(_dep(DEP_PAIRS // 4, DEP_EDGES), mesh)
    cb = _stacked(_conn(CONN_LANES), mesh)
    rb = _stacked(_resp(RESP_LANES), mesh)
    tick = jax.ShapeDtypeStruct((), jnp.int32,
                                sharding=NamedSharding(mesh, P()))
    # builders return jitted programs; __wrapped__ skips the process memo
    fold = sharded.fold_step_dep_sharded.__wrapped__(
        FLEET_SHARD, mesh, cap_per_dest=CONN_LANES)
    roll = rollup.fleet_rollup_fn.__wrapped__(FLEET_SHARD, mesh,
                                              DEP_EDGES)
    for name, prog, args in (("sharded_fold", fold,
                              (st, dep, cb, rb, tick)),
                             ("fleet_rollup", roll, (st, dep))):
        t0 = time.perf_counter()
        compiled = prog.lower(*args).compile()
        secs = time.perf_counter() - t0
        m = compiled.memory_analysis()
        need = (m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes)
        _report(name, need, secs, m)
        assert need < HBM_BYTES
        hlo = compiled.as_text()
        assert "all-to-all" in hlo or "all-gather" in hlo \
            or "all-reduce" in hlo, f"{name}: no collective in the HLO"
