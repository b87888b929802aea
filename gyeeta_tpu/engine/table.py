"""Device-resident keyed entity table (the RCU-hash-table replacement).

The reference keeps every keyed entity (listener by ``glob_id_``, task by
``aggr_task_id_``, conn by tuple hash) in liburcu lock-free hash tables
(``common/gy_rcu_inc.h:1664`` ``RCU_HASH_TABLE``), mutated one pointer at a
time by many threads. On TPU the equivalent is a fixed-capacity open-addressing
hash slab living in HBM:

- keys are 64-bit ids carried as ``(hi, lo)`` uint32 pairs (TPUs have no
  useful 64-bit integer path),
- lookup/insert is a *batched* vectorized probe: every lane of a microbatch
  resolves its row in ``PROBES`` unrolled gather/scatter rounds,
- per-entity state lives in separate ``(capacity, ...)`` column tensors
  indexed by the returned row ids (struct-of-arrays),
- delete writes a tombstone key; ``compact`` rebuilds the slab and permutes
  the state columns (the analogue of RCU grace-period reclamation
  (``gy_rcu_inc.h:487``) without any host round-trip).

Intra-batch insert races (two lanes claiming the same empty slot) are resolved
deterministically with a scatter-min "winner lane" pass, so the same batch
always produces the same table — a property the threaded original cannot give.

Everything is fixed-shape and branch-free → jits, shards (each mesh shard owns
an independent slab), and runs entirely on the VPU.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from gyeeta_tpu.utils import hashing as H

# Key sentinels. Real ids of ~0 are astronomically unlikely (ids are hashes);
# colliding with one merely loses that id, never corrupts others.
EMPTY = np.uint32(0xFFFFFFFF)
TOMB = np.uint32(0xFFFFFFFE)

PROBES = 16  # unrolled double-hash probe rounds
P1 = 4                    # stage-1 slots, probed for every lane
RESIDUE_DIV = PROBES      # stage 2 holds B // RESIDUE_DIV lanes
STAGED_MIN_LANES = 1024   # smaller batches stay single-stage: on a v5e the
#                           staged probe already wins at 256 lanes (30 µs
#                           against 64), but a probe that small is under
#                           0.25 ms a dispatch and each staged lookup is
#                           one more two-branch cond to compile
# Load guidance: a key whose probe positions are ALL occupied can never
# insert — it drops on every retry and permanently defeats the
# ``upsert_fast`` all-hit fast path (one such key forces the 16-round
# insert machinery on every dispatch). The permanent-failure odds are
# ~load^PROBES per key: at 8 probes, 0.5^8 ≈ 0.4% of keys at 50% load
# (observed in the bench: a stuck key cost ~2.5ms/µbatch forever);
# at 16 probes it is 0.0015% at 50% and 0.3% at 70%. Size slabs for
# ≤70% steady-state occupancy; drops are counted in ``n_drop`` and
# re-sent keys retry next sweep.
#
# What the 16 probes cost is gathered words: 5.7–8.5 ns a word on a v5e,
# whatever the batch, and a 16-slot probe of both key halves is 32 words
# a lane — 15.9 ms per 65,536 lanes, 74 % of the slab fold (PERF.md §5–6,
# PR 25–26). Almost none of them find anything: replaying the insert path
# (4,096-key batches), the share of keys that sit past probe slot
# 0 / 1 / 3 / 7 is 24.9 / 8.2 / 1.24 / 0.02 % at 50 % load, 0.07 % past
# slot 3 at 25 % load, 4.7 % at the 70 % sizing load. So ``lookup`` is
# staged (:func:`lookup_counted`): ``P1`` slots for every lane, the other
# slots for the residue only, 9.5 words a lane; B // 16 lanes hold the
# 70 % load's 4.7 %. ``upsert``'s insert rounds keep the full width.


class Table(NamedTuple):
    key_hi: jnp.ndarray   # (S,) uint32
    key_lo: jnp.ndarray   # (S,) uint32
    n_live: jnp.ndarray   # () int32 — live keys
    n_tomb: jnp.ndarray   # () int32 — tombstones awaiting compaction
    n_drop: jnp.ndarray   # () int32 — inserts dropped (probe exhaustion)


def init(capacity: int) -> Table:
    assert capacity & (capacity - 1) == 0, "capacity must be a power of two"
    return Table(
        key_hi=jnp.full((capacity,), EMPTY, jnp.uint32),
        key_lo=jnp.full((capacity,), EMPTY, jnp.uint32),
        n_live=jnp.zeros((), jnp.int32),
        n_tomb=jnp.zeros((), jnp.int32),
        n_drop=jnp.zeros((), jnp.int32),
    )


def _probe_slots(khi, klo, capacity: int):
    """(B, PROBES) candidate slots via double hashing (odd step)."""
    h1 = H.mix64(khi, klo, 0x7AB1E5)
    h2 = H.mix64(khi, klo, 0x57E9) | jnp.uint32(1)
    p = jnp.arange(PROBES, dtype=jnp.uint32)
    slots = (h1[:, None] + p[None, :] * h2[:, None]) & jnp.uint32(capacity - 1)
    return slots.astype(jnp.int32)


def _is_empty(hi, lo):
    return (hi == EMPTY) & (lo == EMPTY)


def _is_tomb(hi, lo):
    return (hi == TOMB) & (lo == TOMB)


def upsert(tbl: Table, khi, klo, valid=None):
    """Resolve (or insert) a batch of keys → (new_table, rows).

    rows: (B,) int32 — slab row per lane, or -1 for invalid lanes and for
    inserts dropped after probe exhaustion (counted in ``n_drop``).
    """
    capacity = tbl.key_hi.shape[0]
    khi = khi.astype(jnp.uint32)
    klo = klo.astype(jnp.uint32)
    B = khi.shape[0]
    if valid is None:
        valid = jnp.ones((B,), bool)
    # never insert sentinel-valued keys
    valid = valid & ~_is_empty(khi, klo) & ~_is_tomb(khi, klo)
    lane = jnp.arange(B, dtype=jnp.int32)
    slots = _probe_slots(khi, klo, capacity)            # (B, P)
    rows = jnp.full((B,), -1, jnp.int32)
    key_hi, key_lo = tbl.key_hi, tbl.key_lo
    inserted = jnp.zeros((), jnp.int32)

    def match_rows(key_hi, key_lo, rows):
        cur_hi = key_hi[slots]
        cur_lo = key_lo[slots]
        m = (cur_hi == khi[:, None]) & (cur_lo == klo[:, None])   # (B, P)
        pos = jnp.argmax(m, axis=1)
        found = jnp.any(m, axis=1) & valid
        mrow = slots[lane, pos]
        return jnp.where((rows < 0) & found, mrow, rows)

    for _ in range(PROBES):
        rows = match_rows(key_hi, key_lo, rows)
        unresolved = valid & (rows < 0)
        cur_hi = key_hi[slots]
        cur_lo = key_lo[slots]
        claimable = _is_empty(cur_hi, cur_lo) | _is_tomb(cur_hi, cur_lo)
        has_claim = jnp.any(claimable, axis=1)
        pos = jnp.argmax(claimable, axis=1)
        target = slots[lane, pos]
        want = unresolved & has_claim
        # deterministic winner per contested slot: lowest lane index
        winner = jnp.full((capacity,), B, jnp.int32)
        winner = winner.at[jnp.where(want, target, capacity)].min(
            lane, mode="drop")
        win = want & (winner[target] == lane)
        wtarget = jnp.where(win, target, capacity)
        was_tomb = _is_tomb(key_hi[target], key_lo[target])
        key_hi = key_hi.at[wtarget].set(khi, mode="drop")
        key_lo = key_lo.at[wtarget].set(klo, mode="drop")
        rows = jnp.where(win, target, rows)
        inserted = inserted + jnp.sum(win).astype(jnp.int32)
        tomb_reclaimed = jnp.sum(win & was_tomb).astype(jnp.int32)
        tbl = tbl._replace(n_tomb=tbl.n_tomb - tomb_reclaimed)
    # duplicates of a round-(P-1) winner resolve in this final pass
    rows = match_rows(key_hi, key_lo, rows)
    dropped = jnp.sum(valid & (rows < 0)).astype(jnp.int32)
    new_tbl = Table(
        key_hi=key_hi,
        key_lo=key_lo,
        n_live=tbl.n_live + inserted,
        n_tomb=tbl.n_tomb,
        n_drop=tbl.n_drop + dropped,
    )
    return new_tbl, rows


def upsert_fast(tbl: Table, khi, klo, valid=None):
    """Upsert that skips the insert machinery when every key already
    resolves — the steady state of the ingest hot loop (service keys
    are long-lived; inserts happen at announce/churn rate, not event
    rate). One probe-match pass decides; ``lax.cond`` executes only the
    taken branch on TPU, so the PROBES unrolled claim rounds (gather +
    scatter-min winner election per round) cost nothing once the
    working set is resident — the moral equivalent of the reference's
    RCU read-mostly fast path vs its insert slow path
    (``gy_rcu_inc.h:1664``)."""
    tbl, rows, _, _ = upsert_fast2(tbl, khi, klo, valid)
    return tbl, rows


def upsert_fast2(tbl: Table, khi, klo, valid=None):
    """:func:`upsert_fast` → ``(table, rows, any_miss, probe)``.

    ``any_miss`` () bool — True when this batch carried at least one key
    that was not already resolvable (i.e. the insert machinery ran).
    Callers use it to cond-skip work that only matters for NEW rows
    (e.g. the dep-graph edge identity columns, which existing rows
    already hold). ``probe`` — the (2,) int32 counts of the all-hit
    probe (:func:`lookup_counted`)."""
    khi = khi.astype(jnp.uint32)
    klo = klo.astype(jnp.uint32)
    if valid is None:
        valid = jnp.ones((khi.shape[0],), bool)
    rows0, probe = lookup_counted(tbl, khi, klo, valid)
    any_miss = jnp.any(valid & (rows0 < 0)
                       & ~_is_empty(khi, klo) & ~_is_tomb(khi, klo))
    tbl, rows = jax.lax.cond(
        any_miss,
        lambda t: upsert(t, khi, klo, valid),
        lambda t: (t, rows0),
        tbl)
    return tbl, rows, any_miss, probe


def _first_match(tbl: Table, khi, klo, slots):
    """→ (B,) int32: per lane the FIRST of its ``slots`` (B, P) that
    holds its key, else -1. The two key-half gathers share one index
    array; the pick is a masked max, not a third gather (a
    ``slots[lane, argmax]`` pick cost 0.7 ms per 65,536 lanes on a v5e
    beside 4.0 ms of key gathers)."""
    m = (tbl.key_hi[slots] == khi[:, None]) \
        & (tbl.key_lo[slots] == klo[:, None])
    first = m & (jnp.cumsum(m, axis=1) == 1)
    return jnp.max(jnp.where(first, slots, -1), axis=1)


def _lookup_full(tbl: Table, khi, klo, valid):
    """The single-stage probe: all ``PROBES`` slots of every lane."""
    rows = _first_match(
        tbl, khi, klo, _probe_slots(khi, klo, tbl.key_hi.shape[0]))
    return jnp.where(valid, rows, -1)


def _compact(mask, size: int):
    """Lane ids of the first ``size`` set lanes of ``mask`` (B,),
    ascending, padded with B. One sort: 0.2 ms per 65,536 lanes on a
    v5e, where ``jnp.nonzero(size=)`` (a B-update scatter-add) took
    0.6 ms."""
    B = mask.shape[0]
    lane = jnp.arange(B, dtype=jnp.int32)
    return jax.lax.sort(jnp.where(mask, lane, B))[:size]


def lookup_counted(tbl: Table, khi, klo, valid=None):
    """:func:`lookup` → ``(rows, probe)``; ``probe`` is (2,) int32: the
    lanes stage 1 left open, and 1 when they overflowed the residue
    (0 / 0 for a single-stage batch).

    A two-stage probe. A key sits at the first of its ``PROBES`` slots
    that was free when it was inserted, so stage 1 looks at the first
    ``P1`` slots of every lane and stage 2 at the other slots of the
    lanes still open, compacted into ``B // RESIDUE_DIV`` lanes. More
    open lanes than that (a burst of unknown keys, a slab far above its
    sizing load) take the other slots at full width instead. Either way
    the rows are those of :func:`_lookup_full`: the first match among
    the first ``P1`` slots, else the first among the rest.
    """
    capacity = tbl.key_hi.shape[0]
    khi = khi.astype(jnp.uint32)
    klo = klo.astype(jnp.uint32)
    B = khi.shape[0]
    if valid is None:
        valid = jnp.ones((B,), bool)
    if B < STAGED_MIN_LANES:
        return (_lookup_full(tbl, khi, klo, valid),
                jnp.zeros((2,), jnp.int32))
    R = B // RESIDUE_DIV
    slots = _probe_slots(khi, klo, capacity)
    rows1 = jnp.where(valid, _first_match(tbl, khi, klo, slots[:, :P1]), -1)
    open_ = valid & (rows1 < 0)
    n_open = jnp.sum(open_).astype(jnp.int32)

    def residue(_):
        idx = _compact(open_, R)
        lane = jnp.minimum(idx, B - 1)      # padding probes a lane twice
        rhi, rlo = khi[lane], klo[lane]
        rows2 = _first_match(
            tbl, rhi, rlo, _probe_slots(rhi, rlo, capacity)[:, P1:])
        return rows1.at[idx].set(rows2, mode="drop")

    def full(_):
        rows2 = _first_match(tbl, khi, klo, slots[:, P1:])
        return jnp.where(open_, rows2, rows1)

    # the branches close over the key columns read-only and yield (B,)
    # rows: nothing slab-sized is carried through the cond
    over = n_open > R
    rows = jax.lax.cond(over, full, residue, None)
    return rows, jnp.stack([n_open, over.astype(jnp.int32)])


def lookup(tbl: Table, khi, klo, valid=None):
    """Find rows for a batch of keys without inserting. -1 = absent."""
    return lookup_counted(tbl, khi, klo, valid)[0]


def delete(tbl: Table, khi, klo, valid=None):
    """Tombstone a batch of keys → (new_table, rows_deleted).

    Callers must clear state columns at the returned rows (>=0). The row
    stays unusable until ``compact`` or until an insert reclaims the
    tombstone.
    """
    capacity = tbl.key_hi.shape[0]
    rows = lookup(tbl, khi, klo, valid)
    tgt = jnp.where(rows >= 0, rows, capacity)
    key_hi = tbl.key_hi.at[tgt].set(TOMB, mode="drop")
    key_lo = tbl.key_lo.at[tgt].set(TOMB, mode="drop")
    # count distinct rows: duplicate lanes of one key must not double-count
    hit = jnp.zeros((capacity + 1,), bool).at[tgt].set(True)
    ndel = jnp.sum(hit[:capacity]).astype(jnp.int32)
    return Table(
        key_hi=key_hi,
        key_lo=key_lo,
        n_live=tbl.n_live - ndel,
        n_tomb=tbl.n_tomb + ndel,
        n_drop=tbl.n_drop,
    ), rows


def live_mask(tbl: Table):
    return ~_is_empty(tbl.key_hi, tbl.key_lo) & \
        ~_is_tomb(tbl.key_hi, tbl.key_lo)


def tombstone_rows(tbl: Table, row_mask):
    """Tombstone every live row where ``row_mask`` is True.

    The batched ageing primitive (the reference evicts idle entities via
    per-entry timestamps walked by scheduler jobs, e.g. MAGGR_TASK
    ageing): callers build the mask from a last-seen-tick column. Returns
    (new_table, killed_mask); state columns at killed rows should be
    zeroed by the caller (or left — compact zeroes them)."""
    kill = live_mask(tbl) & row_mask
    n = jnp.sum(kill).astype(jnp.int32)
    return tbl._replace(
        key_hi=jnp.where(kill, TOMB, tbl.key_hi),
        key_lo=jnp.where(kill, TOMB, tbl.key_lo),
        n_live=tbl.n_live - n,
        n_tomb=tbl.n_tomb + n,
    ), kill


def compact(tbl: Table, state_cols):
    """Reclaim tombstones and zero dead state columns — in place.

    In this probe design a tombstone is *operationally identical* to an
    empty slot: ``match_rows``/``lookup`` scan all probe positions with
    no early termination, and inserts claim either. So compaction never
    needs to relocate keys — it reclassifies TOMB → EMPTY and zeroes the
    dead rows' state, O(S) with zero insert failures. (An earlier rebuild
    that re-upserted every key into a fresh slab dropped ~1.7% of live
    entities at 77% load when probe chains exhausted — the scale test
    caught it; in-place reclamation cannot lose rows. Rows also keep
    their ids across compaction.) The analogue of an RCU grace-period
    sweep (``gy_rcu_inc.h:487``), minus the relocation the pointer world
    requires.

    state_cols: pytree of ``(S, ...)`` arrays indexed by row. Returns
    (new_table, new_state_cols). Runs fully on device (jit-able).
    """
    tomb = _is_tomb(tbl.key_hi, tbl.key_lo)
    live = live_mask(tbl)
    new_tbl = Table(
        key_hi=jnp.where(tomb, EMPTY, tbl.key_hi),
        key_lo=jnp.where(tomb, EMPTY, tbl.key_lo),
        n_live=tbl.n_live,
        n_tomb=jnp.zeros((), jnp.int32),
        n_drop=tbl.n_drop,
    )

    def zero_dead(col):
        keep = live.reshape((-1,) + (1,) * (col.ndim - 1))
        return jnp.where(keep, col, jnp.zeros_like(col))

    return new_tbl, jax.tree_util.tree_map(zero_dead, state_cols)
