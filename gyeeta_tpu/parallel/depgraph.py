"""Service dependency graph: paired flows → svc→svc edge slab → clusters.

This is the product feature the pairing collective exists for. The
reference builds it in three stages: madhava records per-listener
``DEPENDS_LISTENER`` maps from locally-resolved conns
(``common/gy_socket_stat.h:721``), shyama pairs the cross-madhava halves in
``glob_tcp_conn_tbl_`` and notifies both sides
(``server/gy_shconnhdlr.cc:3790-3854``), and a periodic job coalesces
listeners that talk to each other into service-mesh clusters
(``coalesce_svc_mesh_clusters``, ``server/gy_shconnhdlr.cc:5198``).

TPU-native redesign — three fixed-shape device structures per shard:

- **half table**: flow-key-addressed slab holding unpaired conn halves
  *with payloads* (client entity id, server glob id, bytes). Halves arrive
  pre-routed to the flow-owner shard by the ``lax.all_to_all`` capacity
  dispatch (``pairing._dispatch``); a row whose both halves have landed is
  *drained the same step*: its edge is folded and the row tombstoned, so
  the table holds only in-flight halves (the reference's unresolved-conn
  cap, ``server/gy_mconnhdlr.h:94``, becomes the slab capacity + TTL).
- **edge slab**: (cli_entity, ser_listener)-keyed table accumulating
  nconn/bytes per dependency edge. The client entity is the caller's
  related-listener id when it has one (svc→svc edge — the mesh), else its
  process-group id (task→svc edge). Conn records that already carry both
  sides (local / same-agent flows, the non-shyama path of the reference)
  fold straight into the edge slab and skip pairing.
- **cluster labels**: vectorized min-label propagation over the svc→svc
  edges — the coalesce pass as a fixed-iteration jitted loop instead of
  shyama's pointer-chasing set merge. Runs on the merged (rolled-up) edge
  set, so every shard computes the same clusters ("every shard is shyama").

Shard-merge of edge slabs is an ``all_gather`` + re-upsert (edges for one
(cli,ser) key may accumulate on several shards; counts are additive).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from gyeeta_tpu.engine import table
from gyeeta_tpu.parallel.mesh import HOST_AXIS
from gyeeta_tpu.parallel.pairing import owner_shard
from gyeeta_tpu.utils import hashing as H

_EDGE_SALT = 0x5E1FD0


class DepGraph(NamedTuple):
    # ---- unpaired halves, keyed by flow key (per-shard slice) ----
    half_tbl: table.Table
    h_cli_hi: jnp.ndarray    # (P,) client entity id (payload of cli half)
    h_cli_lo: jnp.ndarray
    h_cli_svc: jnp.ndarray   # (P,) bool — client entity is a listener
    h_ser_hi: jnp.ndarray    # (P,) server glob id (payload of ser half)
    h_ser_lo: jnp.ndarray
    h_bytes: jnp.ndarray     # (P,) f32 — flow bytes (max of the two halves)
    h_cli_seen: jnp.ndarray  # (P,) bool
    h_ser_seen: jnp.ndarray  # (P,) bool
    h_last_tick: jnp.ndarray  # (P,) i32 — for TTL eviction
    # ---- dependency edges, keyed by mix(cli, ser) ----
    edge_tbl: table.Table
    e_cli_hi: jnp.ndarray    # (E,) endpoint ids (actual, not the hash key)
    e_cli_lo: jnp.ndarray
    e_cli_svc: jnp.ndarray   # (E,) bool — svc→svc edge (mesh member)
    e_ser_hi: jnp.ndarray
    e_ser_lo: jnp.ndarray
    e_ctr: jnp.ndarray       # (E, 2) f32 — [:, 0] nconn (flows folded
    #                           into this edge), [:, 1] bytes. ONE
    #                           column block so the per-dispatch
    #                           accumulate is ONE row scatter-add (two
    #                           per-column scatters pay the 32k-lane
    #                           index resolution twice — the ctr_win
    #                           lesson, engine/step.py:ingest_conn)
    e_last_tick: jnp.ndarray  # (E,) i32
    # ---- counters ----
    n_paired: jnp.ndarray    # () f32 — halves joined into an edge
    n_expired: jnp.ndarray   # () f32 — halves evicted unpaired (TTL)
    n_dropped: jnp.ndarray   # () f32 — dispatch/table overflow drops
    n_probe: jnp.ndarray     # (2,) i32 — edge-probe lanes sent to stage 2,
    #                          lookups that overflowed it

    @property
    def e_nconn(self):
        """(E,) flows-per-edge view of ``e_ctr`` (read path)."""
        return self.e_ctr[:, 0]

    @property
    def e_bytes(self):
        """(E,) bytes-per-edge view of ``e_ctr`` (read path)."""
        return self.e_ctr[:, 1]


def init(pair_capacity: int = 4096, edge_capacity: int = 2048) -> DepGraph:
    Pc, E = pair_capacity, edge_capacity
    z32 = lambda n: jnp.zeros((n,), jnp.uint32)        # noqa: E731
    return DepGraph(
        half_tbl=table.init(Pc),
        h_cli_hi=z32(Pc), h_cli_lo=z32(Pc),
        h_cli_svc=jnp.zeros((Pc,), bool),
        h_ser_hi=z32(Pc), h_ser_lo=z32(Pc),
        h_bytes=jnp.zeros((Pc,), jnp.float32),
        h_cli_seen=jnp.zeros((Pc,), bool),
        h_ser_seen=jnp.zeros((Pc,), bool),
        h_last_tick=jnp.full((Pc,), -1, jnp.int32),
        edge_tbl=table.init(E),
        e_cli_hi=z32(E), e_cli_lo=z32(E),
        e_cli_svc=jnp.zeros((E,), bool),
        e_ser_hi=z32(E), e_ser_lo=z32(E),
        e_ctr=jnp.zeros((E, 2), jnp.float32),
        e_last_tick=jnp.full((E,), -1, jnp.int32),
        n_paired=jnp.zeros((), jnp.float32),
        n_expired=jnp.zeros((), jnp.float32),
        n_dropped=jnp.zeros((), jnp.float32),
        n_probe=jnp.zeros((2,), jnp.int32),
    )


# ------------------------------------------------------------------ edges
def edge_key(cli_hi, cli_lo, ser_hi, ser_lo):
    """(cli, ser) → 64-bit edge table key as (hi, lo) u32 pair."""
    khi = H.mix64(cli_hi, cli_lo, _EDGE_SALT) ^ ser_hi
    klo = H.mix64(ser_hi, ser_lo, _EDGE_SALT) ^ cli_lo
    return khi, klo


def fold_edges(dep: DepGraph, cli_hi, cli_lo, cli_svc, ser_hi, ser_lo,
               byts, valid, tick, nconn=None) -> DepGraph:
    """Accumulate (cli→ser) flows into the edge slab (batched upsert).

    ``upsert_fast``: the edge working set is small and long-lived (one
    row per cli→ser dependency), so after warmup every batch is all-hit
    and the insert rounds are skipped entirely (``lax.cond``).

    ``nconn``: per-lane flow count (default 1 per lane — the raw-record
    path). Edge-folding agents ship PRE-AGGREGATED edges, so a lane may
    represent many flows (``engine/step.py:ingest_delta``)."""
    khi, klo = edge_key(cli_hi, cli_lo, ser_hi, ser_lo)
    tbl, rows, any_new, probe = table.upsert_fast2(dep.edge_tbl, khi, klo,
                                                   valid=valid)
    ok = valid & (rows >= 0)
    E = dep.e_nconn.shape[0]
    lanes = jnp.where(ok, rows, E)
    set_ = lambda col, v: col.at[lanes].set(v, mode="drop")  # noqa: E731

    # Identity columns only change when a NEW row is claimed — an
    # existing row already holds its (cli, ser) endpoint ids, and every
    # lane of a resolved key writes the values the row already has. In
    # steady state (all-hit, the hot loop) the five scatter-sets below
    # are pure redundancy at ~2 ms each per 32k-lane dispatch on one
    # core, so they ride the SAME miss signal the upsert's insert
    # machinery keys on. The carried operands are the five small (E,)
    # identity columns — nothing slab-sized crosses the cond boundary.
    def _write_ids(cols):
        chi, clo, csvc, shi, slo = cols
        return (set_(chi, cli_hi.astype(jnp.uint32)),
                set_(clo, cli_lo.astype(jnp.uint32)),
                set_(csvc, cli_svc),
                set_(shi, ser_hi.astype(jnp.uint32)),
                set_(slo, ser_lo.astype(jnp.uint32)))

    e_cli_hi, e_cli_lo, e_cli_svc, e_ser_hi, e_ser_lo = lax.cond(
        any_new, _write_ids, lambda cols: cols,
        (dep.e_cli_hi, dep.e_cli_lo, dep.e_cli_svc, dep.e_ser_hi,
         dep.e_ser_lo))
    return dep._replace(
        edge_tbl=tbl,
        e_cli_hi=e_cli_hi, e_cli_lo=e_cli_lo, e_cli_svc=e_cli_svc,
        e_ser_hi=e_ser_hi, e_ser_lo=e_ser_lo,
        e_ctr=dep.e_ctr.at[lanes].add(
            jnp.stack([jnp.where(ok, jnp.float32(1.0) if nconn is None
                                 else nconn.astype(jnp.float32), 0.0),
                       jnp.where(ok, byts, 0.0)], axis=1),
            mode="drop"),
        e_last_tick=set_(dep.e_last_tick, jnp.int32(tick)),
        n_dropped=dep.n_dropped
        + jnp.sum(valid & (rows < 0)).astype(jnp.float32),
        n_probe=dep.n_probe + probe,
    )


# ------------------------------------------------------------------ halves
class Halves(NamedTuple):
    """Dispatch lanes for cross-shard pairing (all shape (B,))."""
    flow_hi: jnp.ndarray
    flow_lo: jnp.ndarray
    is_cli: jnp.ndarray     # bool — this lane is the client-side half
    pay_hi: jnp.ndarray     # payload: cli entity id / ser glob id
    pay_lo: jnp.ndarray
    pay_svc: jnp.ndarray    # bool — (cli halves) entity is a listener
    byts: jnp.ndarray       # f32
    valid: jnp.ndarray


def halves_from_conn(cb):
    """Split a ConnBatch into direct-edge lanes and pairing halves.

    A conn record may know both sides (local flow / single-agent sim —
    the reference resolves those without shyama), only its client side
    (connect-observed, remote server), or only its server side
    (accept-observed, remote client). Returns
    ``(direct_lanes, halves)`` where direct_lanes is the tuple for
    ``fold_edges`` and halves is a :class:`Halves` for pairing.
    """
    cli_id_hi = jnp.where(cb.cli_rel_hi | cb.cli_rel_lo,
                          cb.cli_rel_hi, cb.cli_task_hi)
    cli_id_lo = jnp.where(cb.cli_rel_hi | cb.cli_rel_lo,
                          cb.cli_rel_lo, cb.cli_task_lo)
    cli_svc = (cb.cli_rel_hi | cb.cli_rel_lo) != 0
    know_cli = (cli_id_hi | cli_id_lo) != 0
    know_ser = (cb.svc_hi | cb.svc_lo) != 0
    byts = cb.bytes_sent + cb.bytes_rcvd
    direct = (cli_id_hi, cli_id_lo, cli_svc, cb.svc_hi, cb.svc_lo,
              byts, cb.valid & know_cli & know_ser)
    one_sided = cb.valid & (know_cli ^ know_ser)
    is_cli = know_cli
    halves = Halves(
        flow_hi=cb.flow_hi, flow_lo=cb.flow_lo, is_cli=is_cli,
        pay_hi=jnp.where(is_cli, cli_id_hi, cb.svc_hi),
        pay_lo=jnp.where(is_cli, cli_id_lo, cb.svc_lo),
        pay_svc=cli_svc & is_cli,
        byts=byts, valid=one_sided)
    return direct, halves


def pair_halves(dep: DepGraph, hv: Halves, tick) -> DepGraph:
    """Land halves in the half table; drain rows that just completed."""
    tbl, rows = table.upsert(dep.half_tbl, hv.flow_hi, hv.flow_lo,
                             valid=hv.valid)
    ok = hv.valid & (rows >= 0)
    Pc = dep.h_bytes.shape[0]
    cl = jnp.where(ok & hv.is_cli, rows, Pc)     # client-half lanes
    sl = jnp.where(ok & ~hv.is_cli, rows, Pc)    # server-half lanes
    cli_hi = dep.h_cli_hi.at[cl].set(hv.pay_hi.astype(jnp.uint32),
                                     mode="drop")
    cli_lo = dep.h_cli_lo.at[cl].set(hv.pay_lo.astype(jnp.uint32),
                                     mode="drop")
    cli_svc = dep.h_cli_svc.at[cl].set(hv.pay_svc, mode="drop")
    ser_hi = dep.h_ser_hi.at[sl].set(hv.pay_hi.astype(jnp.uint32),
                                     mode="drop")
    ser_lo = dep.h_ser_lo.at[sl].set(hv.pay_lo.astype(jnp.uint32),
                                     mode="drop")
    lanes = jnp.where(ok, rows, Pc)
    h_bytes = dep.h_bytes.at[lanes].max(jnp.where(ok, hv.byts, 0.0),
                                        mode="drop")
    cli_seen = dep.h_cli_seen.at[cl].set(True, mode="drop")
    ser_seen = dep.h_ser_seen.at[sl].set(True, mode="drop")
    last = dep.h_last_tick.at[lanes].set(jnp.int32(tick), mode="drop")

    done = cli_seen & ser_seen            # rows now holding both halves
    dep = dep._replace(
        half_tbl=tbl, h_cli_hi=cli_hi, h_cli_lo=cli_lo, h_cli_svc=cli_svc,
        h_ser_hi=ser_hi, h_ser_lo=ser_lo, h_bytes=h_bytes,
        h_cli_seen=cli_seen, h_ser_seen=ser_seen, h_last_tick=last,
        n_paired=dep.n_paired + jnp.sum(done).astype(jnp.float32),
        n_dropped=dep.n_dropped
        + jnp.sum(hv.valid & (rows < 0)).astype(jnp.float32),
    )
    # fold the completed rows' edges, then tombstone + clear them (drain —
    # the table only ever holds in-flight halves). A row can only become
    # done when a lane of THIS batch landed its second half, and every
    # done row is cleared the same step, so newly-done ≤ B — a bounded
    # nonzero gather covers all of them. (Folding edges with a P-lane
    # valid mask over the whole table was the dominant dep-fold cost:
    # a PROBES-round upsert at 65k lanes per step at the default capacity.)
    D = hv.valid.shape[0]
    idx = jnp.nonzero(done, size=D, fill_value=Pc)[0]
    get = lambda col: col.at[idx].get(mode="fill", fill_value=0)  # noqa: E731
    dep = fold_edges(dep, get(dep.h_cli_hi), get(dep.h_cli_lo),
                     get(dep.h_cli_svc), get(dep.h_ser_hi),
                     get(dep.h_ser_lo), get(dep.h_bytes),
                     idx < Pc, tick)
    return _clear_half_rows(dep, done)


def pair_halves_cond(dep: DepGraph, hv: Halves, tick) -> DepGraph:
    """``pair_halves`` skipped entirely (``lax.cond``) when the batch
    carries no one-sided halves — local/two-sided traffic (every flow
    whose agent observed both ends, the reference's non-shyama path)
    pays zero pairing cost. Identical semantics: with no valid lanes
    pair_halves inserts nothing and completes no rows, and done rows
    never persist across steps (drained the same step they complete)."""
    return lax.cond(jnp.any(hv.valid),
                    lambda d: pair_halves(d, hv, tick),
                    lambda d: d, dep)


def _clear_half_rows(dep: DepGraph, kill) -> DepGraph:
    tbl, killed = table.tombstone_rows(dep.half_tbl, kill)
    z = jnp.uint32(0)
    return dep._replace(
        half_tbl=tbl,
        h_cli_hi=jnp.where(killed, z, dep.h_cli_hi),
        h_cli_lo=jnp.where(killed, z, dep.h_cli_lo),
        h_cli_svc=jnp.where(killed, False, dep.h_cli_svc),
        h_ser_hi=jnp.where(killed, z, dep.h_ser_hi),
        h_ser_lo=jnp.where(killed, z, dep.h_ser_lo),
        h_bytes=jnp.where(killed, 0.0, dep.h_bytes),
        h_cli_seen=jnp.where(killed, False, dep.h_cli_seen),
        h_ser_seen=jnp.where(killed, False, dep.h_ser_seen),
        h_last_tick=jnp.where(killed, -1, dep.h_last_tick),
    )


def age(dep: DepGraph, tick, pair_ttl_ticks: int,
        edge_ttl_ticks: int) -> DepGraph:
    """TTL eviction: unpaired halves expire fast (the reference diag-dumps
    and drops unresolved conns); edges linger for the query horizon."""
    seen = dep.h_last_tick >= 0
    stale_h = seen & (jnp.int32(tick) - dep.h_last_tick
                      > jnp.int32(pair_ttl_ticks))
    dep = dep._replace(
        n_expired=dep.n_expired + jnp.sum(stale_h).astype(jnp.float32))
    dep = _clear_half_rows(dep, stale_h)
    e_seen = dep.e_last_tick >= 0
    stale_e = e_seen & (jnp.int32(tick) - dep.e_last_tick
                        > jnp.int32(edge_ttl_ticks))
    etbl, ekilled = table.tombstone_rows(dep.edge_tbl, stale_e)
    z = jnp.uint32(0)
    return dep._replace(
        edge_tbl=etbl,
        e_cli_hi=jnp.where(ekilled, z, dep.e_cli_hi),
        e_cli_lo=jnp.where(ekilled, z, dep.e_cli_lo),
        e_cli_svc=jnp.where(ekilled, False, dep.e_cli_svc),
        e_ser_hi=jnp.where(ekilled, z, dep.e_ser_hi),
        e_ser_lo=jnp.where(ekilled, z, dep.e_ser_lo),
        e_ctr=jnp.where(ekilled[:, None], 0.0, dep.e_ctr),
        e_last_tick=jnp.where(ekilled, -1, dep.e_last_tick),
    )


# ------------------------------------------------------- single-shard step
def dep_step(dep: DepGraph, cb, tick) -> DepGraph:
    """One conn batch → edges (single shard: no dispatch, halves pair
    locally — the n_shards=1 degenerate of the sharded step)."""
    direct, hv = halves_from_conn(cb)
    dep = fold_edges(dep, *direct, tick)
    return pair_halves_cond(dep, hv, tick)


def dep_fold_many(dep: DepGraph, cbs, tick) -> DepGraph:
    """K stacked conn batches → one flat direct-edge fold + chunked
    pairing.

    Direct (both-sides-known) lanes don't recycle table rows, so the
    whole K×B slab folds in ONE ``upsert_fast`` — all-hit in steady
    state, a single probe-match pass. Pairing DOES recycle rows (a
    matched half frees its slot for the next insert), so its one-sided
    lanes run in bounded chunks: each chunk's worst-case inserts stay
    under a quarter of the pair table (even on top of a steady-state
    unpaired backlog, an all-one-sided burst stays under the ~78%
    probe-exhaustion load documented in engine/table.py). Each chunk
    cond-skips entirely when it carries no one-sided lanes — the
    common case for local/two-sided traffic."""
    K, B = cbs.valid.shape[:2]
    n = K * B
    flat = jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), cbs)
    direct, hv = halves_from_conn(flat)
    dep = fold_edges(dep, *direct, tick)
    capacity = dep.h_last_tick.shape[0]
    chunk = max(1, min(n, capacity // 4))

    def body(carry, hvn):
        return pair_halves_cond(carry, hvn, tick), None

    nfull = n // chunk
    if nfull == 1 and n % chunk == 0:
        return pair_halves_cond(dep, hv, tick)

    def _pair_all(dep):
        if nfull:
            grouped = jax.tree.map(
                lambda x: x[: nfull * chunk].reshape(
                    (nfull, chunk) + x.shape[1:]), hv)
            dep, _ = lax.scan(body, dep, grouped)
        rem = n % chunk
        if rem:      # remainder lanes get their own bounded chunk
            tail = jax.tree.map(lambda x: x[nfull * chunk:], hv)
            dep = pair_halves_cond(dep, tail, tick)
        return dep

    # local/two-sided traffic (no one-sided half anywhere in the slab —
    # the common hot-path case) skips the whole chunked pairing scan
    # with ONE cond instead of paying K per-chunk cond evaluations; the
    # per-chunk conds still bound insert load when the outer is taken
    return lax.cond(jnp.any(hv.valid), _pair_all, lambda d: d, dep)


# ------------------------------------------------------------ sharded step
def dep_step_fn(mesh, cap_per_dest: int):
    """Compiled sharded step: (dep_stacked, conn_stacked, tick) → dep.

    Direct (both-sides-known) lanes fold into the local shard's edge slab.
    One-sided halves ride the capacity-disciplined staged ``all_to_all``
    to the flow-owner shard (payload columns travel with the key; on a
    multi-slice mesh the DCN axis is crossed at most once) and pair there.
    """
    from gyeeta_tpu.parallel.mesh import axes_of

    n = mesh.devices.size
    axes = axes_of(mesh)
    sizes = tuple(mesh.shape[a] for a in axes)
    spec = P(axes)

    @partial(jax.shard_map, mesh=mesh, in_specs=(spec, spec, P()),
             out_specs=spec, check_vma=False)
    def _step(dep, cb, tick):
        local = jax.tree.map(lambda x: x[0], dep)
        cb = jax.tree.map(lambda x: x[0], cb)
        direct, hv = halves_from_conn(cb)
        local = fold_edges(local, *direct, tick)
        routed, o_drop = _dispatch_halves(hv, axes, sizes, n,
                                          cap_per_dest)
        local = local._replace(n_dropped=local.n_dropped + o_drop)
        local = pair_halves_cond(local, routed, tick)
        return jax.tree.map(lambda x: x[None], local)

    return jax.jit(_step, donate_argnums=(0,))


def _dispatch_halves(hv: Halves, axes, sizes, n: int, cap: int):
    """Staged all_to_all capacity dispatch of Halves → received Halves."""
    from gyeeta_tpu.parallel.pairing import dispatch_fields

    owner = owner_shard(hv.flow_hi, hv.flow_lo, n)
    routed, r_val, dropped = dispatch_fields(
        {"fhi": (hv.flow_hi.astype(jnp.uint32), 0),
         "flo": (hv.flow_lo.astype(jnp.uint32), 0),
         "cli": (hv.is_cli, False),
         "phi": (hv.pay_hi.astype(jnp.uint32), 0),
         "plo": (hv.pay_lo.astype(jnp.uint32), 0),
         "psvc": (hv.pay_svc, False),
         "byts": (hv.byts, 0.0)},
        hv.valid, owner, axes, sizes, cap)
    return Halves(
        flow_hi=routed["fhi"], flow_lo=routed["flo"],
        is_cli=routed["cli"], pay_hi=routed["phi"],
        pay_lo=routed["plo"], pay_svc=routed["psvc"],
        byts=routed["byts"], valid=r_val), dropped


# ------------------------------------------------------------ edge rollup
class EdgeSet(NamedTuple):
    """A dense edge view: one shard's own slab, or the replicated merge
    of every shard's after rollup."""
    tbl: table.Table
    cli_hi: jnp.ndarray
    cli_lo: jnp.ndarray
    cli_svc: jnp.ndarray
    ser_hi: jnp.ndarray
    ser_lo: jnp.ndarray
    nconn: jnp.ndarray
    byts: jnp.ndarray
    n_dropped: jnp.ndarray   # () i32 — lanes a merge could not place


def _edge_merge(cap: int, cli_hi, cli_lo, cli_svc, ser_hi, ser_lo,
                nconn, byts, valid) -> EdgeSet:
    """Merge flat edge lanes (counts additive) into a fresh dense slab.
    A lane whose 16 probe slots are all taken is left out (odds about
    load^16 a key) and counted in ``n_dropped``."""
    khi, klo = edge_key(cli_hi, cli_lo, ser_hi, ser_lo)
    tbl, rows = table.upsert(table.init(cap), khi, klo, valid=valid)
    ok = valid & (rows >= 0)
    lanes = jnp.where(ok, rows, cap)
    set_ = lambda z, v: z.at[lanes].set(v, mode="drop")      # noqa: E731
    zero32 = jnp.zeros((cap,), jnp.uint32)
    return EdgeSet(
        tbl=tbl,
        cli_hi=set_(zero32, cli_hi.astype(jnp.uint32)),
        cli_lo=set_(zero32, cli_lo.astype(jnp.uint32)),
        cli_svc=set_(jnp.zeros((cap,), bool), cli_svc),
        ser_hi=set_(zero32, ser_hi.astype(jnp.uint32)),
        ser_lo=set_(zero32, ser_lo.astype(jnp.uint32)),
        nconn=jnp.zeros((cap,), jnp.float32).at[lanes].add(
            jnp.where(ok, nconn, 0.0), mode="drop"),
        byts=jnp.zeros((cap,), jnp.float32).at[lanes].add(
            jnp.where(ok, byts, 0.0), mode="drop"),
        n_dropped=jnp.sum(valid & (rows < 0), dtype=jnp.int32),
    )


def edges_local(dep: DepGraph) -> EdgeSet:
    """Single-shard edge view as an EdgeSet: the edge slab's own columns
    under its own key table. One shard has nothing to merge — the slab
    already is the dense keyed table a merge would rebuild — so every
    live edge is in the view and none can be dropped."""
    return EdgeSet(
        tbl=dep.edge_tbl,
        cli_hi=dep.e_cli_hi, cli_lo=dep.e_cli_lo, cli_svc=dep.e_cli_svc,
        ser_hi=dep.e_ser_hi, ser_lo=dep.e_ser_lo,
        nconn=dep.e_nconn, byts=dep.e_bytes,
        n_dropped=jnp.zeros((), jnp.int32))


def edge_rollup_fn(mesh, out_capacity: int):
    """Compiled sharded DepGraph → replicated merged EdgeSet."""
    from gyeeta_tpu.parallel.mesh import axes_of

    axes = axes_of(mesh)

    from gyeeta_tpu.parallel.mesh import gather_all

    @partial(jax.shard_map, mesh=mesh, in_specs=P(axes), out_specs=P(),
             check_vma=False)
    def _roll(dep):
        local = jax.tree.map(lambda x: x[0], dep)
        live = table.live_mask(local.edge_tbl)
        g = lambda x: gather_all(x, axes)       # noqa: E731
        return _edge_merge(
            out_capacity, g(local.e_cli_hi), g(local.e_cli_lo),
            g(local.e_cli_svc), g(local.e_ser_hi), g(local.e_ser_lo),
            g(local.e_nconn), g(local.e_bytes), g(live))

    return jax.jit(_roll)


# --------------------------------------------------------- mesh clustering
def mesh_clusters(es: EdgeSet, node_capacity: int, n_iters: int = 16):
    """Svc-mesh coalescing: connected components of the svc→svc edges.

    Returns ``(node_tbl, labels, sizes)``: a node table keyed by listener
    id, a per-row cluster label (the min node row reachable — stable,
    deterministic), and per-row member count of the row's cluster.
    Vectorized min-label propagation, ``n_iters`` fixed sweeps ≥ graph
    diameter (monitoring meshes are shallow; 16 covers 64k-node chains of
    fanout ≥2). The coalesce analogue of ``server/gy_shconnhdlr.cc:5198``.
    """
    use = table.live_mask(es.tbl) & es.cli_svc
    ntbl = table.init(node_capacity)
    ntbl, cli_rows = table.upsert(ntbl, es.cli_hi, es.cli_lo, valid=use)
    ntbl, ser_rows = table.upsert(ntbl, es.ser_hi, es.ser_lo, valid=use)
    ok = use & (cli_rows >= 0) & (ser_rows >= 0)
    cr = jnp.where(ok, cli_rows, node_capacity)
    sr = jnp.where(ok, ser_rows, node_capacity)
    labels = jnp.arange(node_capacity, dtype=jnp.int32)

    def body(labels, _):
        m = jnp.minimum(labels[jnp.where(ok, cli_rows, 0)],
                        labels[jnp.where(ok, ser_rows, 0)])
        m = jnp.where(ok, m, jnp.int32(node_capacity))
        labels = labels.at[cr].min(m, mode="drop")
        labels = labels.at[sr].min(m, mode="drop")
        return labels, None

    labels, _ = lax.scan(body, labels, None, length=n_iters)
    live = table.live_mask(ntbl)
    labels = jnp.where(live, labels, -1)
    counts = jnp.zeros((node_capacity + 1,), jnp.int32).at[
        jnp.where(live, labels, node_capacity)].add(1, mode="drop")
    sizes = jnp.where(live, counts[jnp.where(live, labels, 0)], 0)
    return ntbl, labels, sizes


# Process-wide compiled-builder memo (see sharded.memo_sharded).
from gyeeta_tpu.parallel.sharded import memoize_builder as _memoize  # noqa: E402

dep_step_fn = _memoize(dep_step_fn)
edge_rollup_fn = _memoize(edge_rollup_fn)
