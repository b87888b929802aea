"""Send log + poll log → freshness.

The generator logs every marker it sends, ``(seq, t_sent)``; the poller
logs every answer, ``(snaptick, gauge, t_received)`` where ``gauge`` is the
newest marker that answer contains. For every tick whose snapshot is first
seen inside the window, the lag is the time the first answer from that
snapshot was received minus the time the last marker it contains was
sent: queue wait in, window length out. A tick's snapshot that never shows
(a stalled tick) has no sample of its own; the next one that does carries
the stall in its lag only as far as its own newest marker is old, so
``ticks`` beside the mean says how many were seen.
"""

from __future__ import annotations


def tick_lags(markers: list, polls: list, t0: float, t1: float) -> list:
    """→ ``[(snaptick, lag_s)]`` for every snapshot first seen in
    ``[t0, t1)`` that contains a marker."""
    t_sent = dict(markers)
    out = []
    seen = None
    for tick, gauge, t_recv in polls:
        if seen is not None and tick <= seen:
            continue
        first = seen is None
        seen = tick
        if first or not (t0 <= t_recv < t1) or gauge not in t_sent:
            # the first answer of the log shows a snapshot of unknown age
            continue
        out.append((tick, t_recv - t_sent[gauge]))
    return out


def summary(markers: list, polls: list, t0: float, t1: float) -> dict:
    lags = [lag for _t, lag in tick_lags(markers, polls, t0, t1)]
    if not lags:
        return {}
    return {"fresh_lag_ms": 1e3 * sum(lags) / len(lags),
            "fresh_lag_max_ms": 1e3 * max(lags), "ticks": len(lags),
            "lags_ms": [round(1e3 * lag) for lag in lags]}
