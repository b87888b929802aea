"""Per-entity arrays stored flat, and their row views.

A per-entity array that the slab fold scatters into at (row, col) is
stored as ONE axis of E·W elements and scattered at the flat index
``row * W + col``. The chip's compiler lowers a two-index scatter onto
the flattened operand, so a target kept as (E, W) pays a relayout of
the whole array into the flat tiling and another back out on every
fold; a 1-D target is scattered in place. Readers that run once a tick
or a query take the (E, W) view with :func:`rows`; a reader of a few
rows gathers them from the flat array with :func:`take_rows` instead
of viewing the whole.
"""

from __future__ import annotations

import jax.numpy as jnp


def rows(x, w: int):
    """(..., E·W) → the (..., E, W) row view (leading axes, such as a
    stacked shard axis, pass through)."""
    return x.reshape(x.shape[:-1] + (x.shape[-1] // w, w))


def row_lanes(idx, w: int):
    """Flat indices of rows ``idx``: (..., W)."""
    return idx[..., None] * w + jnp.arange(w, dtype=idx.dtype)


def take_rows(x, idx, w: int):
    """Rows ``idx`` of a flat (E·W,) array as (len(idx), W): one gather
    of the rows asked for."""
    return x[row_lanes(idx, w)]
