"""Count-Min sketch as a device tensor.

Replaces the reference's per-flow exact counters kept in RCU hash tables
(``common/gy_socket_stat.h:999`` ``tcp_tbl_`` byte/packet counts) for the
unbounded-key regime: per-5-tuple bytes/sec, per-endpoint event counts.
Point-update pointer chasing becomes one batched scatter-add per microbatch.

State is ``(depth, width)``; row streams derive from TWO independent
hashes via Kirsch-Mitzenmacher double hashing (``bucket_r = h1 + r·h2``
— provably preserves the CMS error bounds, *Less Hashing, Same
Performance*, and costs 2 key mixes instead of ``depth``; the fold-path
hash work is ~depth/2 cheaper). Estimates are upper bounds; error ≤
e·N/width with prob 1-e^-depth. Merge is elementwise ``+`` → roll-up
over shards is a plain ``psum``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from gyeeta_tpu.utils import hashing as H


class CMS(NamedTuple):
    counts: jnp.ndarray  # (depth, width) float32 (sums) or int32 (counts)


def init(depth: int = 4, width: int = 1 << 16, dtype=jnp.float32) -> CMS:
    return CMS(counts=jnp.zeros((depth, width), dtype=dtype))


def update(sk: CMS, key_hi, key_lo, values, valid=None) -> CMS:
    """Scatter-add ``values`` for 64-bit keys ``(key_hi, key_lo)``.

    ``valid``: optional bool mask (padding lanes contribute nothing).
    """
    depth, width = sk.counts.shape
    vals = values.astype(sk.counts.dtype)
    if valid is not None:
        vals = jnp.where(valid, vals, jnp.zeros_like(vals))
    # One fused scatter over all rows: flatten (row, bucket) into row*width+idx.
    buckets = H.bucket_indices_km(key_hi, key_lo, depth, width)
    rows = [b + r * width for r, b in enumerate(buckets)]
    flat_idx = jnp.concatenate(rows)
    flat_vals = jnp.tile(vals, depth)
    counts = sk.counts.reshape(-1).at[flat_idx].add(flat_vals)
    return CMS(counts=counts.reshape(depth, width))


def query(sk: CMS, key_hi, key_lo):
    """Point estimate (min over rows) for a batch of keys."""
    depth, width = sk.counts.shape
    est = None
    for r, idx in enumerate(H.bucket_indices_km(key_hi, key_lo, depth,
                                                width)):
        v = sk.counts[r, idx]
        est = v if est is None else jnp.minimum(est, v)
    return est


def upper_bound(sk: CMS, key_hi, key_lo, rows: int = 1):
    """Looser point estimate using only the first ``rows`` hash rows —
    still a valid upper bound (every row receives all mass), at 1/depth
    the gather cost. Candidate filters (top-K compaction) want exactly
    this: cheap, safe-side, ranking quality degrades gracefully with
    collisions."""
    depth, width = sk.counts.shape
    rows = min(rows, depth)
    est = None
    for r, idx in enumerate(H.bucket_indices_km(key_hi, key_lo, rows,
                                                width)):
        v = sk.counts[r, idx]
        est = v if est is None else jnp.minimum(est, v)
    return est


def merge(a: CMS, b: CMS) -> CMS:
    return CMS(counts=a.counts + b.counts)


def total(sk: CMS):
    """Total inserted weight (any row sums to it)."""
    return sk.counts[0].sum()


# ---------------------------------------------------------------- numpy ref
def np_update(counts: np.ndarray, key_hi, key_lo, values):
    depth, width = counts.shape
    buckets = H.bucket_indices_km(np.asarray(key_hi), np.asarray(key_lo),
                                  depth, width)
    for r, idx in enumerate(buckets):
        np.add.at(counts[r], idx, values)
    return counts


def np_query(counts: np.ndarray, key_hi, key_lo):
    depth, width = counts.shape
    buckets = H.bucket_indices_km(np.asarray(key_hi), np.asarray(key_lo),
                                  depth, width)
    est = None
    for r, idx in enumerate(buckets):
        v = counts[r][idx]
        est = v if est is None else np.minimum(est, v)
    return est
