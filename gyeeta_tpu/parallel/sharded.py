"""Sharded engine: per-shard AggState slabs + shard_map'd fold steps.

Each mesh shard owns an independent ``AggState`` (its own service slab and
sketches) for its slice of the host-id space — exactly a madhava's role
(per-host RCU tables, ``server/gy_mconnhdlr.h:1107``), but as one stacked
pytree with a leading shard axis laid out over the mesh. Ingest batches
arrive pre-routed ``(n_shards, B, ...)`` (see ``shard_batches``); the fold
runs embarrassingly parallel under ``shard_map`` with zero collectives —
collectives appear only in ``rollup.py``/``pairing.py``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from gyeeta_tpu.engine import aggstate, step
from gyeeta_tpu.parallel.mesh import HOST_AXIS, axes_of, \
    leading_sharding, shard_of_host


_MESH_MEMO: dict = {}


def mesh_key(mesh) -> tuple:
    """Hashable identity of a mesh's geometry (axis names + shape +
    device ids): two Mesh objects over the same devices compile the
    same programs, so they share memoized executables."""
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
            tuple(int(d.id) for d in mesh.devices.flat))


def memo_sharded(key: tuple, make):
    """Process-wide compiled-function memo for the mesh tier (the
    sharded twin of ``runtime._memo_jit``): a second ShardedRuntime
    with identical geometry shares the first one's traced and compiled
    programs instead of re-tracing the whole fold family."""
    fn = _MESH_MEMO.get(key)
    if fn is None:
        fn = _MESH_MEMO[key] = make()
    return fn


def _local(tree):
    """Strip the singleton shard axis inside shard_map."""
    return jax.tree.map(lambda x: x[0], tree)


def _relocal(tree):
    return jax.tree.map(lambda x: x[None], tree)


def init_sharded(cfg: aggstate.EngineCfg, mesh):
    """Stacked (n_shards, ...) AggState laid out over the mesh axis."""
    n = mesh.devices.size
    shd = leading_sharding(mesh)

    @partial(jax.jit, out_shardings=shd)
    def _init():
        one = aggstate.init(cfg)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), one)

    return _init()


def stack_prerouted(batch_fns, per_shard_records):
    """Stacked batches from records ALREADY routed per shard — the
    ingest edge hashes hosts to shards once at staging time
    (``ShardedRuntime._stage_raw``), so the dispatch path just builds
    each shard's lanes from its own bucket. Returns host-side numpy
    leaves ``(n_shards, lanes, ...)`` ready for ``put_sharded``."""
    builder, lanes = batch_fns
    return jax.tree.map(
        lambda *xs: np.stack(xs),
        *[builder(recs, lanes) for recs in per_shard_records])


def shard_batches(cfg: aggstate.EngineCfg, mesh, batch_fns, records,
                  host_ids):
    """Route host-side records to shards and build stacked batches.

    ``records``: structured record array; ``host_ids``: (N,) source host of
    each record; ``batch_fns``: (builder, lane_size) — e.g.
    ``(decode.conn_batch, cfg.conn_batch)``. Returns a batch pytree whose
    leaves are (n_shards, lane_size, ...) numpy arrays (ready for
    ``jax.device_put`` with the leading sharding).

    This is the host-side L1 role (validate + batch + route,
    ``server/gy_mconnhdlr.cc:2430``): pure numpy, no device work.
    """
    builder, lanes = batch_fns
    n = mesh.devices.size
    dest = shard_of_host(np.asarray(host_ids), n)
    shards = []
    for s in range(n):
        shards.append(builder(records[dest == s], lanes))
    return jax.tree.map(lambda *xs: np.stack(xs), *shards)


def put_sharded(mesh, batch):
    """Transfer a stacked host batch to devices, split on the shard axis."""
    shd = leading_sharding(mesh)
    return jax.tree.map(lambda x: jax.device_put(x, shd), batch)


def fold_step_sharded(cfg: aggstate.EngineCfg, mesh):
    """Compiled sharded flagship step: (state, conn, resp) → state.

    Uses the same staged-digest hot path as the single-chip
    ``fold_many``: conn fold + one flat resp pass + amortized digest
    compression per shard. Callers must apply ``td_flush_sharded``
    before reading digest quantiles (the sharded runtime does, at tick
    and query boundaries)."""

    @partial(jax.shard_map, mesh=mesh, in_specs=(P(axes_of(mesh)),) * 3,
             out_specs=P(axes_of(mesh)), check_vma=False)
    def _step(st, cb, rb):
        local = step.ingest_conn(cfg, _local(st), _local(cb))
        local = step.ingest_resp_flat(cfg, local, _local(rb))
        return _relocal(local)

    return jax.jit(_step, donate_argnums=(0,))


def fold_step_dep_sharded(cfg: aggstate.EngineCfg, mesh,
                          cap_per_dest: int):
    """The sharded slab dispatch: engine fold + dependency-graph fold
    (incl. the cross-shard pairing ``all_to_all``) + the global
    digest-stage pressure scalar in ONE shard_map'd jit with state AND
    dep donation — what ``fold_step_sharded`` + ``td_pressure_sharded``
    + ``dep_step_fn`` compute in three dispatches
    (tests/test_fusedfold.py holds it to that composition), for one
    jit-call overhead per slab. The pressure scalar is a graph OUTPUT
    (replicated ()), so the hot loop never issues a dispatch just to
    observe it. ``cap_per_dest`` is the pairing dispatch capacity —
    instantiate once per slab width (chunk vs fold_k-deep)."""
    from gyeeta_tpu.parallel import depgraph as dg

    n = mesh.devices.size
    axes = axes_of(mesh)
    sizes = tuple(mesh.shape[a] for a in axes)
    spec = P(axes)

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(spec, spec, spec, spec, P()),
             out_specs=(spec, spec, P()), check_vma=False)
    def _step(st, dep, cb, rb, tick):
        local = step.ingest_conn(cfg, _local(st), _local(cb))
        local = step.ingest_resp_flat(cfg, local, _local(rb))
        dloc = _local(dep)
        cbl = _local(cb)
        direct, hv = dg.halves_from_conn(cbl)
        dloc = dg.fold_edges(dloc, *direct, tick)
        routed, o_drop = dg._dispatch_halves(hv, axes, sizes, n,
                                             cap_per_dest)
        dloc = dloc._replace(n_dropped=dloc.n_dropped + o_drop)
        dloc = dg.pair_halves_cond(dloc, routed, tick)
        press = jnp.max(local.td_stage_n)
        for ax in axes:
            press = jax.lax.pmax(press, ax)
        return _relocal(local), _relocal(dloc), press

    return jax.jit(_step, donate_argnums=(0, 1))


def td_flush_sharded(cfg: aggstate.EngineCfg, mesh):
    """Per-shard partial digest-stage flush (query/tick readiness).

    Each shard compresses its ``td_flush_m`` fullest stages per call —
    O(m), not O(per-shard capacity); when m ≥ the per-shard slab this
    is exactly the full flush. The sharded runtime drains iteratively
    against ``td_pressure_sharded`` (same host-trigger design as the
    single-chip runtime; an in-graph cond flush cost 110 ms/dispatch
    untaken at 65k capacity)."""

    @partial(jax.shard_map, mesh=mesh, in_specs=P(axes_of(mesh)),
             out_specs=P(axes_of(mesh)), check_vma=False)
    def _flush(st):
        return _relocal(step.td_flush_partial(cfg, _local(st)))

    return jax.jit(_flush, donate_argnums=(0,))


def td_pressure_sharded(mesh):
    """Global max staged-sample count across shards — one () scalar."""

    @partial(jax.shard_map, mesh=mesh, in_specs=P(axes_of(mesh)),
             out_specs=P(), check_vma=False)
    def _pressure(st):
        local = jnp.max(_local(st).td_stage_n)
        for ax in axes_of(mesh):
            local = jax.lax.pmax(local, ax)
        return local

    return jax.jit(_pressure)


def tick_5s_sharded(cfg: aggstate.EngineCfg, mesh):
    @partial(jax.shard_map, mesh=mesh, in_specs=P(axes_of(mesh)),
             out_specs=P(axes_of(mesh)), check_vma=False)
    def _tick(st):
        return _relocal(step.tick_5s(cfg, _local(st)))

    return jax.jit(_tick, donate_argnums=(0,))


def ingest_listener_sharded(cfg: aggstate.EngineCfg, mesh):
    @partial(jax.shard_map, mesh=mesh, in_specs=(P(axes_of(mesh)),) * 2,
             out_specs=P(axes_of(mesh)), check_vma=False)
    def _fold(st, lb):
        return _relocal(step.ingest_listener(cfg, _local(st), _local(lb)))

    return jax.jit(_fold, donate_argnums=(0,))


def ingest_host_sharded(cfg: aggstate.EngineCfg, mesh):
    @partial(jax.shard_map, mesh=mesh, in_specs=(P(axes_of(mesh)),) * 2,
             out_specs=P(axes_of(mesh)), check_vma=False)
    def _fold(st, hb):
        return _relocal(step.ingest_host(cfg, _local(st), _local(hb)))

    return jax.jit(_fold, donate_argnums=(0,))


def ingest_cpumem_sharded(cfg: aggstate.EngineCfg, mesh):
    @partial(jax.shard_map, mesh=mesh, in_specs=(P(axes_of(mesh)),) * 2,
             out_specs=P(axes_of(mesh)), check_vma=False)
    def _fold(st, cm):
        return _relocal(step.ingest_cpumem(cfg, _local(st), _local(cm)))

    return jax.jit(_fold, donate_argnums=(0,))


def ingest_trace_sharded(cfg: aggstate.EngineCfg, mesh):
    @partial(jax.shard_map, mesh=mesh, in_specs=(P(axes_of(mesh)),) * 2,
             out_specs=P(axes_of(mesh)), check_vma=False)
    def _fold(st, tb):
        return _relocal(step.ingest_trace(cfg, _local(st), _local(tb)))

    return jax.jit(_fold, donate_argnums=(0,))


def ingest_task_sharded(cfg: aggstate.EngineCfg, mesh):
    @partial(jax.shard_map, mesh=mesh, in_specs=(P(axes_of(mesh)),) * 2,
             out_specs=P(axes_of(mesh)), check_vma=False)
    def _fold(st, tb):
        return _relocal(step.ingest_task(cfg, _local(st), _local(tb)))

    return jax.jit(_fold, donate_argnums=(0,))


def ingest_delta_sharded(cfg: aggstate.EngineCfg, mesh):
    """Sharded edge pre-aggregation fold: each shard folds the delta
    lanes of ITS hosts (records were routed by the layout's hid hash at
    staging time, like every raw stream) into its own state AND dep
    slice — pre-aggregated dep edges are direct edges (both endpoints
    known at the agent), so no pairing collective is needed."""

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(P(axes_of(mesh)),) * 3 + (P(),),
             out_specs=(P(axes_of(mesh)),) * 2, check_vma=False)
    def _fold(st, dep, db, tick):
        lst, ldep = step.ingest_delta(cfg, _local(st), _local(dep),
                                      _local(db), tick)
        return _relocal(lst), _relocal(ldep)

    return jax.jit(_fold, donate_argnums=(0, 1))


def ping_tasks_sharded(cfg: aggstate.EngineCfg, mesh):
    @partial(jax.shard_map, mesh=mesh, in_specs=(P(axes_of(mesh)),) * 2,
             out_specs=P(axes_of(mesh)), check_vma=False)
    def _fold(st, pb):
        return _relocal(step.ping_tasks(cfg, _local(st), _local(pb)))

    return jax.jit(_fold, donate_argnums=(0,))


def classify_sharded(cfg: aggstate.EngineCfg, mesh):
    """Per-shard 5s classify pass (embarrassingly parallel: each shard
    classifies its own services/hosts — the per-madhava sweep)."""
    from gyeeta_tpu.semantic import derive

    @partial(jax.shard_map, mesh=mesh, in_specs=P(axes_of(mesh)),
             out_specs=P(axes_of(mesh)), check_vma=False)
    def _cls(st):
        return _relocal(derive.classify_pass(cfg, _local(st)))

    return jax.jit(_cls, donate_argnums=(0,))


def age_tasks_sharded(cfg: aggstate.EngineCfg, mesh, max_age_ticks: int):
    @partial(jax.shard_map, mesh=mesh, in_specs=P(axes_of(mesh)),
             out_specs=P(axes_of(mesh)), check_vma=False)
    def _age(st):
        return _relocal(step.age_tasks(cfg, _local(st), max_age_ticks))

    return jax.jit(_age, donate_argnums=(0,))


def age_apis_sharded(cfg: aggstate.EngineCfg, mesh, max_age_ticks: int):
    @partial(jax.shard_map, mesh=mesh, in_specs=P(axes_of(mesh)),
             out_specs=P(axes_of(mesh)), check_vma=False)
    def _age(st):
        return _relocal(step.age_apis(cfg, _local(st), max_age_ticks))

    return jax.jit(_age, donate_argnums=(0,))


def memoize_builder(builder):
    """Route a compiled-program builder ``f(cfg?, mesh, extras...)``
    through the process-wide memo (every arg must be hashable; Mesh
    args key by geometry). Used below and by ``depgraph``/``rollup``."""
    from jax.sharding import Mesh

    def wrapper(*args, **kwargs):
        key = (builder.__module__, builder.__name__) + tuple(
            mesh_key(a) if isinstance(a, Mesh) else a for a in args) \
            + tuple(sorted(kwargs.items()))
        return memo_sharded(key, lambda: builder(*args, **kwargs))

    wrapper.__name__ = builder.__name__
    wrapper.__doc__ = builder.__doc__
    wrapper.__wrapped__ = builder
    return wrapper


# Memoize every pure compiled-program builder in this module (NOT
# init_sharded — it returns live state buffers that are later donated,
# so instances must never share them).
for _n in ("fold_step_sharded", "fold_step_dep_sharded",
           "td_flush_sharded", "td_pressure_sharded", "tick_5s_sharded",
           "ingest_listener_sharded", "ingest_host_sharded",
           "ingest_cpumem_sharded", "ingest_trace_sharded",
           "ingest_task_sharded", "ping_tasks_sharded",
           "ingest_delta_sharded",
           "classify_sharded", "age_tasks_sharded", "age_apis_sharded"):
    globals()[_n] = memoize_builder(globals()[_n])
del _n
