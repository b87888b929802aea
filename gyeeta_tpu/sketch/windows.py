"""Multi-resolution time windows over additive sketch state.

Tensor analogue of ``folly::MultiLevelTimeSeries`` as used by
``TIME_HISTOGRAM`` (``common/gy_statistics.h:1083``) with the reference's
canonical level set ``Level_5s_5min_5days_all`` (:1545): every statistic is
readable over the last 5 s, last 5 min, last 5 days, and process lifetime.

Design: the engine ticks at a fixed base cadence (default 5 s — the service
state cadence, ``gy_socket_stat.cc:152``). Each level above the base is a ring
of ``nslots`` sub-slabs plus a rolling ``total``; on tick the just-finished
base slab is added into every level's current sub-slab, and when a level's
stride boundary passes, its ring advances and the expired sub-slab is
subtracted from the rolling total. All branch-free (``jnp.where`` on tick
predicates) so the whole thing lives inside the jitted update step.

Works over any *additive* state array (loghist slabs, CMS tensors, packed
stat columns). Non-additive sketches (HLL max-merge) use the same ring but
``maximum`` recombine at query time instead of a rolling total.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import jax.numpy as jnp
import numpy as np


class WindowSpec(NamedTuple):
    """One level: covers ``stride_ticks * nslots`` base ticks."""
    stride_ticks: int  # base ticks per sub-slab
    nslots: int        # ring length

    @property
    def span_ticks(self) -> int:
        return self.stride_ticks * self.nslots


# 5s base tick; "all" is a plain accumulator, handled separately. Coverage of
# a level oscillates in [span - stride + 1, span] base ticks: right after a
# stride boundary the just-expired sub-slab's stride-1 older ticks are gone.
# 5 min = 60 ticks; 5 days = 86400 ticks (matching Level_5s_5min_5days_all,
# common/gy_statistics.h:1545).
LEVELS_DEFAULT: tuple[WindowSpec, ...] = (
    WindowSpec(stride_ticks=5, nslots=12),     # 5 min span, 25 s resolution
    WindowSpec(stride_ticks=3600, nslots=24),  # 5 day span, 5 h resolution
)


class MultiWindow(NamedTuple):
    """Windowed view of one additive state array of shape ``shape``.

    cur:    (shape) slab being filled this base tick; may be stored flat
            (a fold scatter target, ``sketch/flat.py``): ``cur_rows``
            gives it in ``shape``
    rings:  tuple of (nslots, *shape) per level
    totals: tuple of (shape) rolling per-level totals
    alltime:(shape) lifetime accumulator
    tick:   () int32 — base ticks since start
    """
    cur: jnp.ndarray
    rings: tuple
    totals: tuple
    alltime: jnp.ndarray
    tick: jnp.ndarray


def init(shape: tuple, levels: Sequence[WindowSpec] = LEVELS_DEFAULT,
         dtype=jnp.float32) -> MultiWindow:
    return MultiWindow(
        cur=jnp.zeros(shape, dtype),
        rings=tuple(jnp.zeros((lv.nslots,) + tuple(shape), dtype)
                    for lv in levels),
        totals=tuple(jnp.zeros(shape, dtype) for _ in levels),
        alltime=jnp.zeros(shape, dtype),
        tick=jnp.zeros((), jnp.int32),
    )


def cur_rows(win: MultiWindow):
    """The current slab in the window's shape (a view of a flat ``cur``)."""
    return win.cur.reshape(win.alltime.shape)


def add(win: MultiWindow, delta) -> MultiWindow:
    """Accumulate into the current base slab (called per microbatch)."""
    return win._replace(cur=win.cur + delta)


def tick(win: MultiWindow, levels: Sequence[WindowSpec] = LEVELS_DEFAULT
         ) -> MultiWindow:
    """Close the current base slab: fold into every level, advance rings."""
    t = win.tick
    cur = cur_rows(win)
    new_rings = []
    new_totals = []
    for lv, ring, total in zip(levels, win.rings, win.totals):
        slot = (t // lv.stride_ticks) % lv.nslots
        boundary = (t % lv.stride_ticks) == 0
        # at a stride boundary the slab at `slot` expires and is replaced
        ring = ring.at[slot].set(
            jnp.where(boundary, cur, ring[slot] + cur))
        # resync the rolling total from the ring at each boundary: float32
        # add/subtract drift would otherwise accumulate over the 5-day
        # level's 86,400 ticks (ADVICE r1). Off-boundary: cheap increment.
        total = jnp.where(boundary, ring.sum(axis=0), total + cur)
        new_rings.append(ring)
        new_totals.append(total)
    return MultiWindow(
        cur=jnp.zeros_like(win.cur),
        rings=tuple(new_rings),
        totals=tuple(new_totals),
        alltime=win.alltime + cur,
        tick=t + 1,
    )


def read(win: MultiWindow, level: int):
    """Windowed sum for a level: -1 = current base slab, len(levels) = all."""
    cur = cur_rows(win)
    if level == -1:
        return cur
    if level < len(win.totals):
        return win.totals[level] + cur
    return win.alltime + cur


# ---------------------------------------------------------------- numpy ref
class NpMultiWindow:
    """Exact sliding-window reference (stores every base slab)."""

    def __init__(self, shape, levels=LEVELS_DEFAULT):
        self.levels = levels
        self.slabs = []          # closed base slabs, oldest first
        self.cur = np.zeros(shape, np.float64)

    def add(self, delta):
        self.cur = self.cur + delta

    def tick(self):
        self.slabs.append(self.cur)
        self.cur = np.zeros_like(self.cur)

    def read(self, level: int):
        if level == -1:
            return self.cur
        if level < len(self.levels):
            lv = self.levels[level]
            # the device ring covers the slabs since the oldest *unexpired*
            # sub-slab boundary — coverage oscillates in
            # [span - stride + 1, span] base ticks (dips right after a
            # stride boundary expires a whole sub-slab at once).
            if not self.slabs:
                return self.cur.copy()
            # the ring's content is fixed by the LAST processed tick index:
            # slab i survives iff its slot wasn't overwritten since, i.e.
            # (t_last//stride - i//stride) < nslots  (replay reference).
            t_last = len(self.slabs) - 1
            keep = np.zeros_like(self.cur)
            for i, s in enumerate(self.slabs):
                age = (t_last // lv.stride_ticks) - (i // lv.stride_ticks)
                if age < lv.nslots:
                    keep = keep + s
            return keep + self.cur
        return sum(self.slabs, np.zeros_like(self.cur)) + self.cur


class NpTrueSlidingWindow:
    """Independent oracle: an exact trailing-span sliding window.

    Unlike ``NpMultiWindow`` (which replays device ring semantics), this is
    the spec-level answer: the sum of exactly the last ``span_ticks`` closed
    base slabs plus the open one. Device reads must match it within ±stride
    base ticks of slab mass (tests assert bracketing between the true sums
    over span-stride and span ticks).
    """

    def __init__(self, shape, levels=LEVELS_DEFAULT):
        self.levels = levels
        self.slabs = []
        self.cur = np.zeros(shape, np.float64)

    def add(self, delta):
        self.cur = self.cur + delta

    def tick(self):
        self.slabs.append(self.cur)
        self.cur = np.zeros_like(self.cur)

    def read_span(self, n_ticks: int):
        """Exact sum over the trailing ``n_ticks`` closed slabs + open slab."""
        tail = self.slabs[-n_ticks:] if n_ticks > 0 else []
        return sum(tail, np.zeros_like(self.cur)) + self.cur
