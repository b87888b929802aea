"""Log-bucketed histogram — the workhorse quantile sketch.

This is the direct tensor analogue of the reference's bucketed histograms
(``GY_HISTOGRAM`` ``common/gy_statistics.h:553`` with fixed threshold tables
like ``RESP_TIME_HASH`` :1677 — 15 buckets, 1ms–15s — and percentile
interpolation), generalized to geometric buckets fine enough for <2% relative
quantile error (DDSketch-style guarantee: midpoint interpolation bounds the
relative error by (γ-1)/2).

State is ``(..., B)`` counts with arbitrary leading entity axes — one row per
tracked service/host — so a single scatter-add per microbatch updates
thousands of per-entity histograms at once (replacing per-listener
``resp_hist_`` pointer walks). Merge is ``+`` → psum roll-up.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np


class LogHistSpec(NamedTuple):
    vmin: float
    vmax: float
    nbuckets: int

    @property
    def gamma(self) -> float:
        return float((self.vmax / self.vmin) ** (1.0 / self.nbuckets))

    @property
    def rel_error(self) -> float:
        """Guaranteed max relative quantile error (midpoint interpolation)."""
        g = self.gamma
        return (g - 1.0) / (g + 1.0)


# Response-time spec: 10us .. 100s. gamma≈1.0328 → ≤1.7% error.
RESP_TIME_SPEC = LogHistSpec(vmin=1e-5, vmax=100.0, nbuckets=512)
# QPS / rate spec, mirrors HASH_10_5000 (gy_statistics.h:1908) but geometric.
RATE_SPEC = LogHistSpec(vmin=0.1, vmax=1e7, nbuckets=256)
# Generic percent 0..100 (PERCENT_HASH :1624) — linear is fine via log trick
PERCENT_SPEC = LogHistSpec(vmin=0.5, vmax=100.0, nbuckets=128)


def init(spec: LogHistSpec, entities: tuple = (), dtype=jnp.float32):
    return jnp.zeros(entities + (spec.nbuckets,), dtype=dtype)


def bucket_of(spec: LogHistSpec, values):
    """values -> bucket index [0, B). Values below vmin clamp to 0, above
    vmax clamp to B-1. Works for jnp and np arrays."""
    xp = np if isinstance(values, np.ndarray) else jnp
    v = xp.maximum(values.astype(xp.float32), spec.vmin)
    inv_log_gamma = 1.0 / np.log(spec.gamma)
    b = xp.floor(xp.log(v / spec.vmin) * inv_log_gamma).astype(xp.int32)
    return xp.clip(b, 0, spec.nbuckets - 1)


def bucket_mid(spec: LogHistSpec, bucket):
    """Geometric midpoint of each bucket (the <2%-error estimator)."""
    xp = np if isinstance(bucket, np.ndarray) else jnp
    g = spec.gamma
    return spec.vmin * xp.exp(
        (bucket.astype(xp.float32) + 0.5) * np.float32(np.log(g))
    )


def update(hist, spec: LogHistSpec, values, weights=None, valid=None):
    """Global histogram (no entity axis) scatter-add."""
    b = bucket_of(spec, values)
    w = jnp.ones_like(values, dtype=hist.dtype) if weights is None \
        else weights.astype(hist.dtype)
    if valid is not None:
        w = jnp.where(valid, w, jnp.zeros_like(w))
    return hist.at[b].add(w)


def update_entities(hist, spec: LogHistSpec, entity_row, values,
                    weights=None, valid=None):
    """Per-entity scatter-add at (row, bucket) of an (E, B) hist, or at
    ``row * B + bucket`` of one stored flat, (E·B,) (``sketch/flat.py``)."""
    b = bucket_of(spec, values)
    w = jnp.ones_like(values, dtype=hist.dtype) if weights is None \
        else weights.astype(hist.dtype)
    if valid is not None:
        w = jnp.where(valid, w, jnp.zeros_like(w))
        entity_row = jnp.where(valid, entity_row, 0)
    if hist.ndim == 1:
        return hist.at[entity_row * spec.nbuckets + b].add(w)
    return hist.at[entity_row, b].add(w)


def quantiles(hist, spec: LogHistSpec, qs):
    """Quantile estimates per entity.

    hist: (..., B); qs: (Q,) in [0,1]. Returns (..., Q) float32.
    Mirrors the reference's percentile interpolation
    (``get_percentile_locked``, gy_statistics.h) but vectorized over all
    entities and quantiles at once. Empty histograms return 0.
    """
    qs = jnp.asarray(qs, dtype=jnp.float32)
    cdf = jnp.cumsum(hist.astype(jnp.float32), axis=-1)        # (..., B)
    tot = cdf[..., -1:]                                        # (..., 1)
    target = qs * tot                                          # (..., Q)
    # first bucket where cdf >= target
    ge = cdf[..., None, :] >= target[..., :, None] - 1e-6      # (..., Q, B)
    idx = jnp.argmax(ge, axis=-1).astype(jnp.int32)            # (..., Q)
    val = bucket_mid(spec, idx)
    return jnp.where(tot > 0, val, 0.0)


def merge(a, b):
    return a + b


def counts_total(hist):
    return hist.sum(axis=-1)


def mean(hist, spec: LogHistSpec):
    mids = bucket_mid(spec, jnp.arange(spec.nbuckets, dtype=jnp.int32))
    tot = hist.sum(axis=-1)
    s = (hist.astype(jnp.float32) * mids).sum(axis=-1)
    return jnp.where(tot > 0, s / jnp.maximum(tot, 1.0), 0.0)


# ---------------------------------------------------------------- numpy ref
def np_update(hist: np.ndarray, spec: LogHistSpec, values, weights=None):
    b = bucket_of(spec, np.asarray(values, dtype=np.float32))
    w = np.ones_like(values) if weights is None else weights
    np.add.at(hist, b, w)
    return hist
