"""What ``ingest_records`` knows of each record kind, once for both
runtimes.

Of the kinds ``decode.drain_chunks`` yields, seven fold on the device
(``SECTION_COUNTERS``, :func:`section_builders`); the rest update
host-side registries, counters or the name table (:class:`HostIngest`),
identically in :class:`~gyeeta_tpu.runtime.Runtime` and
:class:`~gyeeta_tpu.parallel.shardedrt.ShardedRuntime`. There the
runtimes differ only in how a raw resp record array is staged
(``_stage_bridged_resp``: one backlog vs per-shard buckets).
"""

from __future__ import annotations

import numpy as np

from gyeeta_tpu.ingest import decode, native, wire


# a native resp stream is "live" for bridge-suppression purposes if it
# reported within this many base ticks (2 min at 5s)
_RESP_FRESH_TICKS = 24

# device-fold kinds → selfstats record counter
SECTION_COUNTERS = {
    "listener": "listener_records", "host": "host_records",
    "task": "task_records", "ping": "task_pings",
    "cpumem": "cpumem_records", "trace": "trace_records",
    "delta": "preagg_delta_records",
}


def section_builders(cfg) -> dict:
    """Device-fold kind → columnar builder ``(recs, lanes, stats)``."""
    # delta decode geometry: payload indices outside it are dropped +
    # counted at decode, never scattered out of range
    delta_dims = dict(
        resp_nbuckets=cfg.resp_spec.nbuckets,
        hll_m_svc=1 << cfg.hll_p_svc,
        hll_m_glob=1 << cfg.hll_p_global)
    return {
        "listener": lambda r, sz, st: decode.listener_batch_fast(
            r, sz, stats=st),
        "host": lambda r, sz, st: decode.host_batch_fast(r, sz, stats=st),
        "task": lambda r, sz, st: decode.task_batch_fast(r, sz, stats=st),
        "ping": lambda r, sz, st: decode.ping_batch(r, sz, stats=st),
        "cpumem": lambda r, sz, st: decode.cpumem_batch_fast(
            r, sz, stats=st),
        "trace": lambda r, sz, st: decode.trace_batch(r, sz),
        "delta": lambda r, sz, st: decode.delta_batch(
            r, sz, stats=st, **delta_dims),
    }


# host-side kinds → (registry attribute, selfstats counter of what the
# registry's ``update`` reports)
HOST_KINDS = {
    "listener_info": ("svcreg", "listener_infos"),
    "host_info": ("hostinfo", "host_infos"),
    "cgroup": ("cgroups", "cgroup_records"),
    "mount": ("mounts", "mount_records"),
    "netif": ("netifs", "netif_records"),
    "names": ("names", "names_interned"),
}

# NOTIFY_AGENT_STATS field → server counter: agent delivery-continuity
# deltas (the only process that can see a spool drop is the agent; the
# server is where /metrics renders)
_AGENT_STAT_COUNTERS = (
    ("spool_dropped", "spool_dropped"),
    ("spool_dropped_records", "spool_dropped_records"),
    ("spool_resent", "spool_resent"),
    ("connect_timeouts", "agent_connect_timeouts"),
)


def owned(buf, stats) -> bytes:
    """``buf`` as bytes that outlive the buffer they lie in: ``bytes``
    as they are; a view of a conn's receive buffer copied once, and
    counted (``edge_owned_copies``: 0 while nothing that keeps bytes —
    the journal, the decode pipeline, the shard feeder, the reference
    adapter — is on)."""
    if isinstance(buf, bytes):
        return buf
    stats.bump("edge_owned_copies")
    return bytes(buf)


class HostIngest:
    """Mixin over ``stats``, ``cfg``, ``opts``, ``_reg_lock``, ``_cols``,
    ``_tick_no``, ``_sweep_last_seq``, ``_host_resp_tick``, the
    registries named in ``HOST_KINDS``, ``traceconns``,
    ``_stage_bridged_resp(recs)`` and, for :meth:`feed`, ``spans``,
    ``journal``, ``_journal_replaying``, ``_pending`` and
    ``ingest_records(recs)``."""

    def feed(self, buf, hid: int = 0, conn_id: int = 0) -> int:
        """Ingest a byte stream (any number of frames, any mix of types).

        Returns records accepted. Trailing partial frames are buffered for
        the next call (epoll partial-read resume semantics). ``hid`` /
        ``conn_id`` attribute the bytes in the write-ahead journal (the
        serving edge passes them; direct feeds default to 0).

        ``buf`` is any contiguous buffer object. The serving edge
        (``net/server.py``) hands in a VIEW of a conn's receive buffer,
        which it overwrites as soon as this returns: ``drain2`` copies
        the records out, and whoever keeps bytes past the call — the
        partial-frame resume buffer, the journal's writer queue — takes
        an owned copy here, counted as ``edge_owned_copies`` (``bytes``
        in are kept as they are: no copy, no count).

        What happens to the records is the runtime's
        ``ingest_records``: staged host-side and folded a slab at a
        time, with no device readback anywhere in this path;
        ``run_tick`` / ``query`` flush first, so staged events are never
        invisible at a cadence or query boundary."""
        with self.spans.span("feed", nrec=len(buf)):
            return self._feed(buf, hid, conn_id)

    def _feed(self, buf, hid: int, conn_id: int) -> int:
        # no resume bytes pending (the common case): skip the big-buffer
        # bytes concat — at slab geometry it copies ~9MB per feed
        data = (self._pending + buf) if self._pending else buf
        try:
            with self.spans.span("deframe", nrec=len(data),
                                 path=native.decode_path(),
                                 annotate=True):
                recs, consumed, unknown = native.drain2(data)
        except wire.FrameError:
            self.stats.bump("frames_bad")
            self._pending = b""       # poison frame: drop buffer, resync
            raise
        self._pending = owned(data[consumed:], self.stats) \
            if consumed < len(data) else b""
        # WAL append AFTER validation, BEFORE the fold: exactly the
        # bytes drain2 accepted (a pending partial frame journals in
        # the call that completes it — each byte exactly once). Replay
        # suppresses the append (chunks are already in the WAL).
        if (consumed and self.journal is not None
                and not self._journal_replaying):
            self.journal.append(owned(data[:consumed], self.stats),
                                hid=hid, conn_id=conn_id,
                                tick=self._tick_no)
        if unknown:
            # skipped unknown-subtype frames (version skew / corrupted
            # subtype byte): accounted loss, never silent loss
            self.stats.bump("records_unknown_subtype", unknown)
        return self.ingest_records(recs)

    def _ingest_sweep_marks(self, sw) -> int:
        """NOTIFY_SWEEP_SEQ: advance the per-host high-water mark (the
        WAL dedup state; max is order-insensitive, so the concatenated
        drain order is fine)."""
        if sw is None or not len(sw):
            return 0
        for h, s in zip(sw["host_id"].tolist(), sw["seq"].tolist()):
            if s > self._sweep_last_seq.get(h, 0):
                self._sweep_last_seq[h] = s
        self.stats.bump("sweep_marks", len(sw))
        return len(sw)

    def _note_native_resp(self, resp) -> None:
        """Remember the tick each host last sent a native RESP_SAMPLE:
        the trace→resp bridge skips hosts with a RECENT native stream
        (per-host precedence — no steady-state double counting when a
        host sends both; a dead resp stream un-suppresses after
        ``_RESP_FRESH_TICKS``). Startup transient: trace frames arriving
        before the host's first resp frame are bridged and may overlap
        the first native window — bounded by one window."""
        hid = resp["host_id"]
        self._host_resp_tick[hid[hid < self.cfg.n_hosts]] = self._tick_no

    def _ingest_host_kind(self, kind: str, recs) -> int:
        """Fold one host-side chunk; returns the records it counts as
        telemetry events (names and agent stats count none)."""
        if kind == "agent_stats":
            for fld, ctr in _AGENT_STAT_COUNTERS:
                tot = int(recs[fld].sum())
                if tot:
                    self.stats.bump(ctr, tot)
            return 0
        attr, ctr = HOST_KINDS[kind]
        # registry updates run under the registry lock: their columns
        # render on query worker threads in snapshot mode
        # (query/snapshot.py) and dict iteration must not race a
        # structural mutation
        with self._reg_lock:
            self.stats.bump(ctr, getattr(self, attr).update(recs))
        if kind == "names":
            # not telemetry events, but resolved name strings are part
            # of every snapshot view: cached columns are stale
            self._cols.bump()
            return 0
        return len(recs)

    def _observe_trace(self, recs) -> None:
        """Host-side half of the trace fold: registry observe + the
        trace→resp bridge with per-host native-stream precedence."""
        with self._reg_lock:
            self.traceconns.observe(recs)
        if not self.opts.trace_resp_bridge:
            return
        rs = decode.resp_from_trace(recs)
        hid = rs["host_id"]
        fresh = (self._tick_no - self._host_resp_tick[
            np.minimum(hid, self.cfg.n_hosts - 1)] <= _RESP_FRESH_TICKS)
        rs = rs[(hid >= self.cfg.n_hosts) | ~fresh]
        if len(rs):
            self._stage_bridged_resp(rs)
            self.stats.bump("resp_from_trace", len(rs))
