"""t-digest as fixed-capacity centroid tensors.

The high-accuracy quantile sketch for readback paths (north-star config #1:
single-stream RTT p50/p95/p99 vs exact). Complements ``loghist`` (the bulk
per-entity path): t-digest gives sub-percent tail accuracy independent of the
value range.

Design is the *merging* t-digest (Dunning), but compression uses k-bin
clustering instead of the sequential greedy pass: sort centroids+samples by
mean, compute midpoint quantiles q, assign cluster id = floor(k1(q)) with the
arcsine scale k1(q) = δ/2π·asin(2q−1), and segment-sum into the fixed C slots.
Everything is fixed-shape (sort + scatter), so it jits, vmaps over entity
axes, and runs on the VPU — no data-dependent loop like the CPU original.

State merge is concat+recompress → shard roll-up uses gathered concat
(all_gather of (C,2) tensors is tiny) rather than psum.

A digest per entity stores its centroids flat, (E·C,), and so does the
staging buffer, (E·cap,): the flush gathers and writes back the rows it
compresses at flat indices (``sketch/flat.py``); readers take the row
view with :func:`rows`.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from gyeeta_tpu.sketch import flat


class TDigest(NamedTuple):
    means: jnp.ndarray    # (C,) float32, sorted ascending among occupied;
    #                       (E·C,) flat for E entities
    weights: jnp.ndarray  # like means, 0 = empty slot
    vmin: jnp.ndarray     # (...,) float32 observed min (inf if empty)
    vmax: jnp.ndarray     # (...,) float32 observed max (-inf if empty)


def init(capacity: int = 128, entities: tuple = ()) -> TDigest:
    """One digest, or with ``entities`` = (E,) one per entity, its
    centroids stored flat as (E·C,)."""
    n = int(np.prod(entities, dtype=np.int64))
    return TDigest(
        means=jnp.zeros((n * capacity,), jnp.float32),
        weights=jnp.zeros((n * capacity,), jnp.float32),
        vmin=jnp.full(entities, jnp.inf, jnp.float32),
        vmax=jnp.full(entities, -jnp.inf, jnp.float32),
    )


def rows(sk: TDigest) -> TDigest:
    """The (..., E, C) row view of a flat per-entity digest (a single
    digest is its own view)."""
    shape = sk.vmin.shape + (-1,)
    return sk._replace(means=sk.means.reshape(shape),
                       weights=sk.weights.reshape(shape))


def _k1(q, delta):
    # arcsine scale: dense bins at the tails → tail quantile accuracy
    return (delta / (2.0 * jnp.pi)) * jnp.arcsin(
        jnp.clip(2.0 * q - 1.0, -1.0, 1.0)
    )


def _compress(means, weights, capacity: int):
    """Cluster (means, weights) rows into ≤capacity centroids. 1-D inputs."""
    delta = 2.0 * (capacity - 1)
    # empty slots sort to the end
    sort_key = jnp.where(weights > 0, means, jnp.inf)
    order = jnp.argsort(sort_key)
    m = means[order]
    w = weights[order]
    tot = jnp.sum(w)
    cum = jnp.cumsum(w)
    q_mid = (cum - 0.5 * w) / jnp.maximum(tot, 1e-30)
    k = _k1(q_mid, delta) - _k1(jnp.float32(0.0), delta)
    cid = jnp.clip(jnp.floor(k).astype(jnp.int32), 0, capacity - 1)
    cid = jnp.where(w > 0, cid, capacity - 1)
    new_w = jax.ops.segment_sum(w, cid, num_segments=capacity)
    new_s = jax.ops.segment_sum(w * m, cid, num_segments=capacity)
    new_m = jnp.where(new_w > 0, new_s / jnp.maximum(new_w, 1e-30), 0.0)
    return new_m, new_w


def update(sk: TDigest, values, valid=None) -> TDigest:
    """Fold a batch of unit-weight samples into a (single-entity) digest."""
    capacity = sk.means.shape[-1]
    w_in = jnp.ones_like(values, jnp.float32)
    if valid is not None:
        w_in = jnp.where(valid, w_in, 0.0)
    vals = values.astype(jnp.float32)
    all_m = jnp.concatenate([sk.means, vals])
    all_w = jnp.concatenate([sk.weights, w_in])
    new_m, new_w = _compress(all_m, all_w, capacity)
    vmasked_min = jnp.where(w_in > 0, vals, jnp.inf)
    vmasked_max = jnp.where(w_in > 0, vals, -jnp.inf)
    return TDigest(
        means=new_m,
        weights=new_w,
        vmin=jnp.minimum(sk.vmin, vmasked_min.min()),
        vmax=jnp.maximum(sk.vmax, vmasked_max.max()),
    )


def stage_samples(stage_v, stage_n, rows, values, valid=None):
    """Append a batch of per-entity samples into an S × cap staging
    buffer WITHOUT compressing — the amortization half of the buffered
    merging t-digest (Dunning's merging variant buffers inserts and
    compresses when the buffer fills; here the fold loop stages every
    microbatch and compresses once per K-deep dispatch, because the
    vmapped sort in ``_compress`` is by far the most expensive op in
    the fold — measured 81%% of the full fold cost).

    stage_v: (S·cap,) float32 values stored flat, slot j of entity s at
    ``s * cap + j`` (``sketch/flat.py``); stage_n: (S,) int32 fill
    counts. Returns (stage_v, stage_n, n_overflow). Overflowing samples
    (entity buffer full) are dropped and counted — the loghist path
    remains the lossless estimator.
    """
    S = stage_n.shape[0]
    cap = stage_v.shape[0] // S
    B = rows.shape[0]
    vals = values.astype(jnp.float32)
    ok = rows >= 0
    if valid is not None:
        ok = ok & valid
    rows_ok = jnp.where(ok, rows, S)
    order = jnp.argsort(rows_ok)
    r_s = rows_ok[order]
    v_s = vals[order]
    lane = jnp.arange(B, dtype=jnp.int32)
    first = jnp.concatenate([jnp.ones((1,), bool), r_s[1:] != r_s[:-1]])
    seg_start = jax.lax.cummax(jnp.where(first, lane, 0))
    pos = lane - seg_start
    base = stage_n[jnp.clip(r_s, 0, S - 1)]
    slot = base + pos
    keep = (r_s < S) & (slot < cap)
    n_overflow = jnp.sum((r_s < S) & (slot >= cap)).astype(jnp.int32)
    tgt_row = jnp.where(keep, r_s, S)
    tgt_slot = jnp.where(keep, slot, 0)
    stage_v = stage_v.at[tgt_row * cap + tgt_slot].set(v_s, mode="drop")
    added = jnp.zeros((S + 1,), jnp.int32).at[tgt_row].add(
        keep.astype(jnp.int32), mode="drop")[:S]
    return stage_v, stage_n + added, n_overflow


def flush_staged(sk: TDigest, stage_v, stage_n):
    """Fold a flat staging buffer into the per-entity digest in ONE
    vmapped compression over their row views; returns (new_digest,
    zeroed stage_v, zeroed stage_n)."""
    r = rows(sk)
    S, C = r.means.shape
    cap = stage_v.shape[0] // S
    stage_r = flat.rows(stage_v, cap)                       # (S, cap)
    occ = jnp.arange(cap)[None, :] < stage_n[:, None]       # (S, cap)
    w_st = occ.astype(jnp.float32)
    all_m = jnp.concatenate([r.means, stage_r], axis=-1)
    all_w = jnp.concatenate([r.weights, w_st], axis=-1)
    new_m, new_w = jax.vmap(_compress, in_axes=(0, 0, None))(all_m, all_w,
                                                             C)
    v_for_min = jnp.where(occ, stage_r, jnp.inf)
    v_for_max = jnp.where(occ, stage_r, -jnp.inf)
    return TDigest(
        means=new_m.reshape(-1), weights=new_w.reshape(-1),
        vmin=jnp.minimum(sk.vmin, v_for_min.min(axis=-1)),
        vmax=jnp.maximum(sk.vmax, v_for_max.max(axis=-1)),
    ), jnp.zeros_like(stage_v), jnp.zeros_like(stage_n)


def flush_staged_topm(sk: TDigest, stage_v, stage_n, m: int):
    """Partial flush: compress only the ``m`` entities with the fullest
    stages — cost O(m·(C+cap)·log) instead of O(S·(C+cap)·log).

    The full ``flush_staged`` vmaps the compression sort over EVERY
    entity row even when almost all stages are empty; at north-star
    geometry (S=65k) that is a ~38M-element sort per flush — measured
    6.2 s on one CPU core and the dominant term of the r4 fold collapse
    (VERDICT r4 weak #3). Entities outside the top-m keep their staged
    samples (nothing is lost); callers drain iteratively or let
    pressure re-trigger. Selection by ``lax.top_k`` over the fill
    counts; rows with zero staged samples pass through untouched.

    Returns (new_digest, stage_v, stage_n) with the flushed rows' stage
    cleared. The flat digest and stage are gathered and written back
    at the selected rows' own slots: a row view of the whole (the
    stage alone is 128 MiB at north-star geometry) would relayout it
    on every flush.
    """
    S = stage_n.shape[0]
    C = sk.means.shape[0] // S
    cap = stage_v.shape[0] // S
    m = min(m, S)
    nsel, idx = jax.lax.top_k(stage_n, m)              # (m,)
    occ = jnp.arange(cap)[None, :] < nsel[:, None]     # (m, cap)
    cents = flat.row_lanes(idx, C)                     # (m, C)
    sel_means = sk.means[cents]
    sel_weights = sk.weights[cents]
    lanes = flat.row_lanes(idx, cap)                   # (m, cap)
    sel_stage = stage_v[lanes]
    all_m = jnp.concatenate([sel_means, sel_stage], axis=-1)
    all_w = jnp.concatenate([sel_weights, occ.astype(jnp.float32)],
                            axis=-1)
    new_m, new_w = jax.vmap(_compress, in_axes=(0, 0, None))(all_m, all_w,
                                                             C)
    # empty-stage rows: recompression is a no-op in value but not in
    # centroid layout — keep the original row bit-for-bit instead
    has = nsel > 0
    new_m = jnp.where(has[:, None], new_m, sel_means)
    new_w = jnp.where(has[:, None], new_w, sel_weights)
    v_for_min = jnp.where(occ, sel_stage, jnp.inf)
    v_for_max = jnp.where(occ, sel_stage, -jnp.inf)
    return TDigest(
        means=sk.means.at[cents].set(new_m),
        weights=sk.weights.at[cents].set(new_w),
        vmin=sk.vmin.at[idx].min(v_for_min.min(axis=-1)),
        vmax=sk.vmax.at[idx].max(v_for_max.max(axis=-1)),
    ), stage_v.at[lanes].set(0.0), stage_n.at[idx].set(0)


def merge(a: TDigest, b: TDigest) -> TDigest:
    ra, rb = rows(a), rows(b)
    capacity = ra.means.shape[-1]
    all_m = jnp.concatenate([ra.means, rb.means], axis=-1)
    all_w = jnp.concatenate([ra.weights, rb.weights], axis=-1)
    if a.vmin.ndim == 0:
        new_m, new_w = _compress(all_m, all_w, capacity)
    else:
        flat_m = all_m.reshape(-1, all_m.shape[-1])
        flat_w = all_w.reshape(-1, all_w.shape[-1])
        new_m, new_w = jax.vmap(_compress, in_axes=(0, 0, None))(
            flat_m, flat_w, capacity
        )
        new_m = new_m.reshape(a.means.shape)
        new_w = new_w.reshape(a.weights.shape)
    return TDigest(
        means=new_m,
        weights=new_w,
        vmin=jnp.minimum(a.vmin, b.vmin),
        vmax=jnp.maximum(a.vmax, b.vmax),
    )


def quantiles(sk: TDigest, qs):
    """Quantile estimates for a single-entity digest. qs: (Q,) → (Q,)."""
    qs = jnp.asarray(qs, jnp.float32)
    w = sk.weights
    m = sk.means
    # occupied centroids are already in ascending-mean order except empty
    # slots (weight 0) interleaved at the tail of value 0 — resort defensively.
    sort_key = jnp.where(w > 0, m, jnp.inf)
    order = jnp.argsort(sort_key)
    m = m[order]
    w = w[order]
    # Empty slots sort to the tail with weight 0 and mean 0; their midpoint
    # mass equals the total, so a tail quantile whose target exceeds the last
    # occupied centroid's midpoint would otherwise interpolate toward 0.
    # Substitute vmax so that region interpolates last-midpoint → observed max
    # (mirror of the `below` branch toward vmin).
    m = jnp.where(w > 0, m, sk.vmax)
    tot = jnp.sum(w)
    cum = jnp.cumsum(w)
    left = cum - 0.5 * w                      # midpoint mass of each centroid
    target = qs * tot                         # (Q,)
    # find the pair of adjacent centroid midpoints bracketing target
    ge = left[None, :] >= target[:, None]     # (Q, C)
    hi_idx = jnp.argmax(ge, axis=-1)
    any_ge = jnp.any(ge, axis=-1)
    hi_idx = jnp.where(any_ge, hi_idx, m.shape[-1] - 1)
    lo_idx = jnp.maximum(hi_idx - 1, 0)
    x0 = left[lo_idx]
    x1 = left[hi_idx]
    y0 = m[lo_idx]
    y1 = m[hi_idx]
    t = jnp.where(x1 > x0, (target - x0) / jnp.maximum(x1 - x0, 1e-30), 0.0)
    est = y0 + t * (y1 - y0)
    # clamp into observed range; below-first-midpoint → interp from vmin
    below = target < left[0]
    est = jnp.where(below, sk.vmin + (m[0] - sk.vmin) *
                    (target / jnp.maximum(left[0], 1e-30)), est)
    est = jnp.clip(est, sk.vmin, sk.vmax)
    return jnp.where(tot > 0, est, 0.0)


def quantiles_entities(sk: TDigest, qs):
    """Vmapped quantiles over a per-entity digest's rows → (S, Q)."""
    r = rows(sk)
    return jax.vmap(
        lambda m, w, vn, vx: quantiles(TDigest(m, w, vn, vx), qs),
        in_axes=(0, 0, 0, 0))(r.means, r.weights, r.vmin, r.vmax)


def count(sk: TDigest):
    """Total weight, per entity for a per-entity digest."""
    return rows(sk).weights.sum(axis=-1)


# ---------------------------------------------------------------- numpy ref
def np_quantiles_exact(values: np.ndarray, qs) -> np.ndarray:
    return np.quantile(np.asarray(values, np.float64), qs)
