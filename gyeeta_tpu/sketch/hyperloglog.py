"""HyperLogLog distinct counting as a device tensor.

Replaces the reference's exact distinct-endpoint tracking (RCU entity tables +
``CONN_BITMAP``, ``common/gy_socket_stat.h:390``) with a fixed 2^p-register
sketch: cardinality of distinct peers/flows per service or per host with
~1.04/sqrt(2^p) standard error (p=14 → 0.8%).

Register update is a scatter-max; cross-shard merge is elementwise max →
roll-up over shards is ``lax.pmax``. One tensor can hold a sketch per
tracked service row: such a per-entity sketch is stored flat, (E·m,), and
its registers are updated at ``row * m + register`` (``sketch/flat.py``);
readers take the (E, m) view with :func:`rows`.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from gyeeta_tpu.sketch import flat
from gyeeta_tpu.utils import hashing as H

_HLL_SALT = 0x1F123BB5


class HLL(NamedTuple):
    regs: jnp.ndarray  # (m,) int32 registers (0..32-p+1); (E·m,) flat
    #                    for E entities, (..., E, m) in a row view


def init(p: int = 14, entities: tuple = ()) -> HLL:
    """One sketch, or with ``entities`` = (E,) one per entity, stored
    flat as (E·m,)."""
    m = 1 << p
    n = int(np.prod(entities, dtype=np.int64))
    return HLL(regs=jnp.zeros((n * m,), dtype=jnp.int32))


def rows(sk: HLL, p: int) -> HLL:
    """The (..., E, m) row view of a flat per-entity sketch."""
    return HLL(regs=flat.rows(sk.regs, 1 << p))


def take_rows(sk: HLL, p: int, idx) -> HLL:
    """The sketches of entities ``idx`` as (len(idx), m)."""
    return HLL(regs=flat.take_rows(sk.regs, idx, 1 << p))


def _idx_rank(key_hi, key_lo, p: int):
    h = H.mix64(key_hi, key_lo, _HLL_SALT)
    is_np = isinstance(h, np.ndarray)
    if is_np:
        idx = (h >> np.uint32(32 - p)).astype(np.int32)
        w = (h << np.uint32(p)).astype(np.uint32)
        rank = np.minimum(H.leading_zeros32(w), 32 - p) + 1
    else:
        idx = (h >> (32 - p)).astype(jnp.int32)
        w = (h << p).astype(jnp.uint32)
        rank = jnp.minimum(H.leading_zeros32(w), 32 - p) + 1
    return idx, rank


def update(sk: HLL, key_hi, key_lo, valid=None) -> HLL:
    """Global (no entity axis) register update via scatter-max."""
    p = int(np.log2(sk.regs.shape[-1]))
    idx, rank = _idx_rank(key_hi, key_lo, p)
    if valid is not None:
        rank = jnp.where(valid, rank, 0)
    return HLL(regs=sk.regs.at[idx].max(rank))


def update_entities(sk: HLL, p: int, entity_row, key_hi, key_lo,
                    valid=None) -> HLL:
    """Per-entity update of a flat (E·m,) sketch: scatter-max at
    ``entity_row * m + register``."""
    idx, rank = _idx_rank(key_hi, key_lo, p)
    if valid is not None:
        rank = jnp.where(valid, rank, 0)
        entity_row = jnp.where(valid, entity_row, 0)
    return HLL(regs=sk.regs.at[entity_row * (1 << p) + idx].max(rank))


def estimate(sk: HLL):
    """Cardinality estimate per entity (HLL with small/large-range correction,
    Flajolet et al.; 32-bit hash variant)."""
    m = sk.regs.shape[-1]
    if m >= 128:
        alpha = 0.7213 / (1.0 + 1.079 / m)
    elif m == 64:
        alpha = 0.709
    elif m == 32:
        alpha = 0.697
    else:
        alpha = 0.673
    regs = sk.regs.astype(jnp.float32)
    inv_sum = jnp.sum(jnp.exp2(-regs), axis=-1)
    raw = alpha * m * m / inv_sum
    zeros = jnp.sum(sk.regs == 0, axis=-1).astype(jnp.float32)
    # small-range: linear counting when estimate <= 2.5m and empty regs exist
    lc = m * jnp.log(m / jnp.maximum(zeros, 1e-9))
    small = (raw <= 2.5 * m) & (zeros > 0)
    est = jnp.where(small, lc, raw)
    # large-range (32-bit hash space)
    two32 = jnp.float32(2.0**32)
    large = est > two32 / 30.0
    est = jnp.where(large, -two32 * jnp.log1p(-est / two32), est)
    return est


def merge(a: HLL, b: HLL) -> HLL:
    return HLL(regs=jnp.maximum(a.regs, b.regs))


# ---------------------------------------------------------------- numpy ref
def np_update(regs: np.ndarray, key_hi, key_lo):
    p = int(np.log2(regs.shape[-1]))
    idx, rank = _idx_rank(np.asarray(key_hi), np.asarray(key_lo), p)
    np.maximum.at(regs, idx, rank)
    return regs
