"""One flat block per dispatch: the host→device packing of fold inputs.

A fused fold's sections are a few dozen small columns (a conn/resp slab
is 22). Handed to ``jax.device_put`` one by one, each is its own PJRT
buffer, its own host-side linearisation into the chip's tiled layout and
its own transfer — the time is paid per array, not per byte (PERF.md §6,
PR 30). So every dispatch carries ONE flat ``uint32`` block: the leaves
one after the other in tree-flatten order, each from a 64-byte line of
its own (:func:`offsets`), and the compiled fold slices, bitcasts and
reshapes them back out (:func:`unpack` — the first op of the fold).

The layout is nothing but each leaf's ``(dtype, shape)`` in order
(:func:`layout_of`), read off the arrays themselves; offsets follow from
it. Leaves of 4-byte and 1-byte dtypes exist today (``uint32`` key
halves, ``float32``, ``int32``, ``bool`` flags); anything else is
refused rather than guessed at.

The module imports numpy only (``decode.py`` and the agents stay off
jax); :func:`unpack` runs under a trace and imports ``jax.lax`` there.
"""

from __future__ import annotations

import math

import numpy as np


def layout_of(leaves) -> tuple:
    """``((dtype, shape), ...)`` of the leaves, in order — hashable, the
    static half of a packed fold's signature."""
    return tuple((a.dtype, a.shape) for a in leaves)


def _words(dtype: np.dtype, shape: tuple) -> int:
    if dtype.itemsize not in (1, 4):
        raise TypeError(f"cannot pack {dtype}{shape}: only 4-byte and "
                        f"1-byte leaves have a place in the word block")
    return -(-math.prod(shape) * dtype.itemsize // 4)


# Every leaf starts on a cache line, and one that fills its last line
# leaves the next one empty. A slab's columns are 2^15 or 2^16 lanes:
# written back to back they sit an exact power of two apart, lane i of
# every column in the same cache set, and the columnar decoders write 16
# of them in step — a slab decode a fifth slower than into separately
# allocated columns (PERF.md §6, PR 30). One line of stagger a column
# spreads them over neighbouring sets.
LINE = 16     # words: 64 bytes


def offsets(layout: tuple) -> tuple[list, int]:
    """Word offset of every leaf, and the block's length in words."""
    offs, at = [], 0
    for dtype, shape in layout:
        offs.append(at)
        at += (_words(dtype, shape) // LINE + 1) * LINE
    return offs, at


def _slot(block: np.ndarray, off: int, dtype: np.dtype, shape: tuple):
    """The leaf-typed, leaf-shaped view of ``block`` at word ``off``."""
    n = math.prod(shape) * dtype.itemsize
    return block.view(np.uint8)[4 * off: 4 * off + n].view(dtype).reshape(
        shape)


def alloc(layout: tuple) -> tuple[np.ndarray, list]:
    """A zeroed block and one writable view per leaf: decoders that fill
    the views have packed the block, with no copy."""
    offs, nwords = offsets(layout)
    block = np.zeros(nwords, np.uint32)
    return block, [_slot(block, off, dtype, shape)
                   for off, (dtype, shape) in zip(offs, layout)]


def pack(leaves, into: np.ndarray | None = None) -> np.ndarray:
    """The leaves as one block. ``into``: a block the caller owns and may
    rewrite now (a staging slab's); it is used when it has this layout's
    length, and a leaf that already lies at its place in it (decoded
    through :func:`alloc`'s views) is not copied. Otherwise a fresh
    block."""
    offs, nwords = offsets(layout_of(leaves))
    if into is None or into.size != nwords:
        into = np.zeros(nwords, np.uint32)
    base = into.ctypes.data
    for a, off in zip(leaves, offs):
        if a.ctypes.data != base + 4 * off or not a.flags.c_contiguous:
            _slot(into, off, a.dtype, a.shape)[...] = a
    return into


def unpack(block, layout: tuple) -> list:
    """Inverse of :func:`pack` on the device side (traced): the leaves,
    bit for bit, in their dtypes and shapes."""
    import jax.numpy as jnp
    from jax import lax

    offs, _ = offsets(layout)
    leaves = []
    for off, (dtype, shape) in zip(offs, layout):
        w = lax.slice(block, (off,), (off + _words(dtype, shape),))
        if dtype.itemsize == 1:
            # four leaf bytes a word, little-endian like the host view
            w = lax.bitcast_convert_type(w, jnp.uint8).reshape(-1)[
                : math.prod(shape)]
            w = (w != 0) if dtype == np.bool_ \
                else lax.bitcast_convert_type(w, dtype)
        elif dtype != np.uint32:
            w = lax.bitcast_convert_type(w, dtype)
        leaves.append(w.reshape(shape))
    # the leaves become buffers of their own before anything reads them
    # (one fusion, a few MB): as slices of one parameter they change
    # what the compiler keeps in fast memory further down — the slab
    # fold's largest gather lost its place there and the fold took 0.9
    # ms longer (PERF.md §6, PR 30). Behind the barrier the fold
    # compiles as it does over separate columns.
    return list(lax.optimization_barrier(tuple(leaves)))
