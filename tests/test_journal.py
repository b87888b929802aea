"""Durable-ingest tier: write-ahead journal, replay-on-restart, WAL
dedup, admission control, and the recovery satellites.

Covers the PR-5 durability contract at every layer below the chaos
e2e: the segmented WAL file format (torn-tail repair, rotation,
position/truncate), Runtime feed→journal→replay equivalence, the
checkpoint-position handshake (replay starts where the checkpoint's
state ends), the NOTIFY_SWEEP_SEQ / REGISTER_RESP last_seq dedup loop,
COMM_THROTTLE round trips, the GYTREC torn-tail fix, stale .tmp.npz
sweeping, and the graceful-shutdown = empty-WAL-window invariant.
"""

from __future__ import annotations

import asyncio
import os

import numpy as np
import pytest

from gyeeta_tpu import version
from gyeeta_tpu.engine.aggstate import EngineCfg
from gyeeta_tpu.ingest import wire
from gyeeta_tpu.net import GytServer, NetAgent
from gyeeta_tpu.runtime import Runtime
from gyeeta_tpu.sim.partha import ParthaSim
from gyeeta_tpu.utils import checkpoint as ckpt
from gyeeta_tpu.utils import journal as J
from gyeeta_tpu.utils import replay
from gyeeta_tpu.utils.config import RuntimeOpts
from gyeeta_tpu.utils.journal import Journal
from gyeeta_tpu.utils.selfstats import Stats

CFG = EngineCfg(n_hosts=4, svc_capacity=64, task_capacity=128,
                conn_batch=64, resp_batch=64, listener_batch=32,
                fold_k=2)


# ------------------------------------------------------- WAL file format
def test_journal_roundtrip_position_and_attribution(tmp_path):
    st = Stats()
    j = Journal(tmp_path / "wal", fsync_bytes=1, stats=st)
    j.append(b"alpha", hid=3, conn_id=9, tick=2)
    j.fsync()              # position() contract: durable end AFTER a
    pos = j.position()     # blocking sync (checkpoint_extra's usage)
    j.append(b"beta" * 200, hid=7, conn_id=11, tick=5)
    out = list(j.read_from(None))
    assert [(h, t, c, b) for h, t, c, b in out] \
        == [(3, 2, 9, b"alpha"), (7, 5, 11, b"beta" * 200)]
    # replay-from-position: exactly the post-checkpoint window
    assert [b for _, _, _, b in j.read_from(pos)] == [b"beta" * 200]
    assert st.counters["wal_appended_chunks"] == 2
    j.fsync()                  # blocking form drains the worker sync
    assert st.counters["wal_fsyncs"] >= 1          # fsync_bytes=1
    j.close()


def test_journal_torn_tail_truncated_and_counted(tmp_path):
    st = Stats()
    j = Journal(tmp_path / "wal", stats=st)
    j.append(b"good chunk", hid=1)
    j.close()
    seg = j._segpath(0)
    size0 = seg.stat().st_size
    with open(seg, "ab") as f:        # SIGKILL mid-write: half a header
        f.write(b"\x01\x02\x03\x04")
    st2 = Stats()
    j2 = Journal(tmp_path / "wal", stats=st2)
    assert st2.counters["wal_torn_tail"] == 1
    assert seg.stat().st_size == size0             # physically truncated
    # appends continue cleanly after the repair
    j2.append(b"after repair", hid=2)
    assert [b for _, _, _, b in j2.read_from(None)] \
        == [b"good chunk", b"after repair"]
    j2.close()


def test_journal_rotation_and_truncate_upto(tmp_path):
    st = Stats()
    j = Journal(tmp_path / "wal", segment_max_bytes=1 << 16,
                fsync_bytes=1 << 30, stats=st)
    blob = b"x" * 8192
    for i in range(20):
        j.append(blob, hid=i)
    j.fsync()              # drain the writer thread before inspecting
    segs = j.segments()
    assert len(segs) >= 2                          # rotated
    assert st.counters["wal_rotations"] >= 1
    # everything still reads back, in order, across segments
    assert len(list(j.read_from(None))) == 20
    # checkpoint at the newest segment: older segments are superseded
    newest = j.position()[0]
    ndel = j.truncate_upto(newest)
    assert ndel == len(segs) - 1
    assert j.segments() == [newest]
    j.close()


def test_journal_seal_floor_and_sealed_reads(tmp_path):
    """The compaction handoff (ISSUE 8): seal_active rotates so every
    appended byte sits in an immutable segment; a registered truncate
    floor holds unconsumed segments back from checkpoint truncation;
    read_sealed walks positions resumably and never touches the active
    segment."""
    st = Stats()
    j = Journal(tmp_path / "wal", fsync_bytes=1 << 30, stats=st)
    j.append(b"a" * 100, hid=1, tick=1)
    j.append(b"b" * 100, hid=2, tick=2)
    assert j.seal_active() == 1            # rotated: 0 is sealed now
    assert j.sealed_upto() == 1
    j.append(b"c" * 100, hid=3, tick=3)    # lands in the ACTIVE segment
    j.fsync()
    got = list(J.read_sealed(tmp_path / "wal", None, j.sealed_upto()))
    assert [g[3] for g in got] == [1, 2]   # hid; active seg excluded
    assert got[0][0] == 0 and got[1][1] > got[0][1]   # seq + offsets
    # resume from the recorded mid-segment position → only chunk 2
    pos = (got[0][0], got[0][1])
    rest = list(J.read_sealed(tmp_path / "wal", pos, j.sealed_upto()))
    assert [g[3] for g in rest] == [2]
    # floor: a checkpoint "past" the sealed segment cannot delete it
    # until the compactor has consumed it
    j.set_truncate_floor(0)
    assert j.truncate_upto(j.position()[0]) == 0
    assert 0 in j.segments()
    j.set_truncate_floor(1)                # compactor consumed seg 0
    assert j.truncate_upto(j.position()[0]) == 1
    assert 0 not in j.segments()
    j.set_truncate_floor(0)                # floors never move backward
    assert j._truncate_floor == 1
    # sealing an empty active segment is a no-op (no rotation storm)
    seq = j.seal_active()
    assert j.seal_active() == seq
    j.close()


def test_named_truncate_floors_pin_unshipped_segments(tmp_path):
    """A sealed-but-unshipped segment pins the truncate floor: the
    effective bound is the MIN over all named floors (compactor AND
    shipper), so checkpoint truncation can never delete a segment the
    remote compaction region has not durably landed."""
    j = Journal(tmp_path / "wal", segment_max_bytes=1 << 14,
                fsync_bytes=1 << 30)
    blob = b"x" * 4096
    for i in range(24):
        j.append(blob, hid=i)
    j.seal_active()
    segs = j.segments()
    assert len(segs) >= 3
    newest = j.position()[0]
    # compactor consumed everything, but the shipper has only landed
    # segment 0 remotely → ship floor 1 bounds the deletion
    j.set_truncate_floor(newest, name="compact")
    j.set_truncate_floor(1, name="ship")
    assert j._truncate_floor == 1
    assert j.truncate_upto(newest) == 1
    assert 0 not in j.segments()
    assert 1 in j.segments()
    # each named floor is individually monotone: a late/stale ship
    # floor below the current one never re-opens deleted ground
    j.set_truncate_floor(0, name="ship")
    assert j._truncate_floor == 1
    # ship catches up past compact → compact floor now binds
    j.set_truncate_floor(newest + 5, name="ship")
    assert j._truncate_floor == newest
    j.close()

    # same contract on the sharded WAL (per-shard floor lists)
    sj = J.ShardedJournal(tmp_path / "swal", 2,
                          segment_max_bytes=1 << 14)
    for i in range(64):
        sj.append(blob, hid=i % 4, conn_id=i)
    sj.seal_active()
    upto = sj.sealed_upto()
    assert all(u >= 1 for u in upto)
    sj.set_truncate_floor(list(upto), name="compact")
    sj.set_truncate_floor([0] * len(upto), name="ship")
    pos = sj.position()
    deleted = sj.truncate_upto(pos)
    assert deleted == 0                    # ship floor 0 pins everything
    for s, sh in enumerate(sj.shards):
        assert 0 in sh.segments(), s
    sj.set_truncate_floor(list(upto), name="ship")
    assert sj.truncate_upto(pos) > 0       # released once shipped
    sj.close()


# ---------------------------------------------- Runtime feed → WAL → replay
def test_runtime_wal_replay_equals_direct_fold(tmp_path):
    sim = ParthaSim(n_hosts=2, n_svcs=2, seed=3)
    bufs = [sim.conn_frames(64) + sim.resp_frames(64) for _ in range(3)]

    rt = Runtime(CFG, RuntimeOpts(journal_dir=str(tmp_path / "wal")))
    fed = sum(rt.feed(b, hid=1, conn_id=5) for b in bufs)
    rt.flush()
    rt.journal.fsync()
    want = float(np.asarray(rt.state.n_conn))

    # a replacement process replays the journal through the SAME
    # decode/fold path and lands on identical device counters
    rt2 = Runtime(CFG, RuntimeOpts(journal_dir=str(tmp_path / "wal")))
    rep = rt2.replay_journal(None)
    assert rep["chunks"] == 3 and rep["records"] == fed
    assert float(np.asarray(rt2.state.n_conn)) == want
    assert rt2.stats.counters["wal_replayed_records"] == fed
    # replay does NOT re-append (the chunks are already in the WAL)
    assert rt2.stats.counters.get("wal_appended_chunks", 0) == 0
    rt.close()
    rt2.close()


def test_checkpoint_position_bounds_replay(tmp_path):
    """The checkpoint records the fsynced WAL position: replay from it
    re-folds ONLY the post-checkpoint window (checkpoint + replay never
    double-folds), and the post-save truncation drops superseded
    segments."""
    sim = ParthaSim(n_hosts=2, n_svcs=2, seed=4)
    rt = Runtime(CFG, RuntimeOpts(
        journal_dir=str(tmp_path / "wal"),
        checkpoint_dir=str(tmp_path / "ck"),
        checkpoint_every_ticks=1))
    rt.feed(sim.conn_frames(64), hid=0, conn_id=1)
    rt.flush()
    report = rt.run_tick()                   # checkpoint with WAL pos
    assert "checkpoint" in report
    n_mid = float(np.asarray(rt.state.n_conn))
    post = sim.conn_frames(32)
    n_post = rt.feed(post, hid=1, conn_id=1)
    rt.flush()
    rt.journal.fsync()
    want = float(np.asarray(rt.state.n_conn))

    from gyeeta_tpu.server_main import restore_latest_checkpoint
    rt2 = Runtime(CFG, RuntimeOpts(
        journal_dir=str(tmp_path / "wal"),
        checkpoint_dir=str(tmp_path / "ck")))
    assert restore_latest_checkpoint(rt2, str(tmp_path / "ck")) \
        == report["checkpoint"]
    rt2.flush()
    assert float(np.asarray(rt2.state.n_conn)) == want
    # only the post-checkpoint chunk replayed (the pre-checkpoint fold
    # came back through the snapshot, not the journal)
    assert rt2.stats.counters["wal_replayed_chunks"] == 1
    assert rt2.stats.counters["wal_replayed_records"] == n_post
    assert want > n_mid
    rt.close()
    rt2.close()


def test_clean_shutdown_leaves_empty_wal_window(tmp_path):
    """Graceful stop = final checkpoint at the journal end + truncate:
    the respawn's replay phase re-folds ZERO chunks."""
    sim = ParthaSim(n_hosts=2, n_svcs=2, seed=5)
    rt = Runtime(CFG, RuntimeOpts(
        journal_dir=str(tmp_path / "wal"),
        checkpoint_dir=str(tmp_path / "ck")))
    rt.feed(sim.conn_frames(64), hid=0, conn_id=1)
    rt.flush()
    rt.close()                                   # journal fsync+close
    extra = J.checkpoint_extra(rt, rt._tick_no)
    path = ckpt.save(str(tmp_path / "ck" / "gyt_final_00000000.npz"),
                     CFG, rt.state, extra=extra)
    J.post_checkpoint_truncate(rt, extra)

    from gyeeta_tpu.server_main import restore_latest_checkpoint
    rt2 = Runtime(CFG, RuntimeOpts(
        journal_dir=str(tmp_path / "wal"),
        checkpoint_dir=str(tmp_path / "ck")))
    assert restore_latest_checkpoint(rt2, str(tmp_path / "ck")) \
        == str(path)
    assert rt2.stats.counters.get("wal_replayed_chunks", 0) == 0
    assert float(np.asarray(rt2.state.n_conn)) \
        == float(np.asarray(rt.state.n_conn))
    rt2.close()


# ------------------------------------------------- sweep-seq dedup loop
def test_sweep_seq_high_water_mark_checkpointed(tmp_path):
    rt = Runtime(CFG, RuntimeOpts(journal_dir=str(tmp_path / "wal")))
    rec = np.zeros(1, wire.SWEEP_SEQ_DT)
    for hid, seq in ((1, 3), (1, 7), (2, 5), (1, 6)):
        rec["host_id"], rec["seq"] = hid, seq
        rt.feed(wire.encode_frame(wire.NOTIFY_SWEEP_SEQ, rec))
    assert rt._sweep_last_seq == {1: 7, 2: 5}    # max, order-insensitive
    extra = J.checkpoint_extra(rt, tick=4)
    assert extra["sweep_seq"] == {"1": 7, "2": 5}
    assert tuple(extra["wal"]) == rt.journal.position()
    rt.close()


def test_register_resp_last_seq_roundtrip():
    # v4 tail present
    b = wire.encode_register_resp(wire.REG_OK, 3,
                                  version.CURR_WIRE_VERSION, 41)
    hsz = wire.HEADER_DT.itemsize
    st, hid, ver, seq, _pre = wire.decode_register_resp(b[hsz:])
    assert (st, hid, seq) == (wire.REG_OK, 3, 41)
    # legacy 16-byte payload (pre-v4 server): last_seq defaults to 0
    legacy = np.zeros((), wire.REGISTER_RESP_DT)
    legacy["status"], legacy["host_id"] = wire.REG_OK, 9
    st, hid, _ver, seq, _pre = wire.decode_register_resp(legacy.tobytes())
    assert (st, hid, seq) == (wire.REG_OK, 9, 0)


def test_agent_prunes_acked_sweeps():
    a = NetAgent(seed=301)
    for seq in (4, 5, 6):
        a._spool_push(bytes([seq]) * 50, 10, seq)
    a._unconfirmed.append((b"u" * 20, 3, 3))
    a._prune_acked(5)
    # sweeps 3,4,5 are durable on the server: only 6 survives
    assert [e[2] for e in a._spool] == [6]
    assert len(a._unconfirmed) == 0
    assert a._spool_bytes == 50
    assert a.stats.counters["spool_pruned_acked"] == 3
    assert a.stats.counters["spool_pruned_records"] == 23


def test_sweep_seq_mark_opens_every_sweep():
    a = NetAgent(seed=302, n_svcs=2, n_groups=3)
    a.host_id = 2
    from gyeeta_tpu.sim.partha import ParthaSim as PS
    a.sim = PS(n_hosts=1, n_svcs=2, n_groups=3, seed=1002, host_base=2)
    b1 = a.build_sweep(8, 8)
    b2 = a.build_sweep(8, 8)
    assert a._sweep_seq == 2
    from gyeeta_tpu.ingest import native
    for buf, want in ((b1, 1), (b2, 2)):
        recs, _, _ = native.drain2(buf)
        sw = recs[wire.NOTIFY_SWEEP_SEQ]
        assert len(sw) == 1
        assert int(sw["host_id"][0]) == 2 and int(sw["seq"][0]) == want


# ------------------------------------------------------ throttle control
def test_throttle_wire_roundtrip():
    b = wire.encode_throttle_multi(((wire.FEED_TRACE, 250),
                                    (wire.FEED_ALL, 0)))
    hsz = wire.HEADER_DT.itemsize
    hdr = np.frombuffer(b, wire.HEADER_DT, count=1)[0]
    assert int(hdr["data_type"]) == wire.COMM_THROTTLE
    recs = wire.decode_throttle(b[hsz:])
    assert recs["feed"].tolist() == [wire.FEED_TRACE, wire.FEED_ALL]
    assert recs["hold_ms"].tolist() == [250, 0]


def test_throttle_level_thresholds(tmp_path):
    rt = Runtime(CFG)
    srv = GytServer(rt, tick_interval=None, throttle_hold_ms=500,
                    throttle_lag_s=0.5, throttle_pending_mb=1.0)
    assert srv.throttle_level() == 0
    rt.stats.gauge("journal_fsync_lag_seconds", 0.8)
    assert srv.throttle_level() == 1               # trace feeds first
    rt.stats.gauge("journal_fsync_lag_seconds", 0.0)
    rt.stats.gauge("journal_pending_bytes", 2 << 20)
    assert srv.throttle_level() == 1
    rt.stats.gauge("journal_pending_bytes", 0.0)
    rt.stats.gauge("engine_drop_pressure", 1.0)
    assert srv.throttle_level() == 2               # engine shedding: all
    rt.stats.gauge("engine_drop_pressure", 0.0)
    assert srv.throttle_level() == 0
    srv.throttle_hold_ms = 0                       # controller disabled
    rt.stats.gauge("engine_drop_pressure", 1.0)
    assert srv.throttle_level() == 0
    rt.stats.gauge("engine_drop_pressure", 0.0)


def test_throttle_push_holds_and_releases_agent():
    rt = Runtime(CFG)

    async def scenario():
        srv = GytServer(rt, tick_interval=None)
        host, port = await srv.start()
        a = NetAgent(seed=303, n_svcs=2, n_groups=3)
        await a.connect(host, port)
        rt.stats.gauge("engine_drop_pressure", 1.0)
        n = await srv.push_throttle()
        assert n == 1
        await asyncio.sleep(0.1)
        assert a._held(wire.FEED_ALL) and a._held(wire.FEED_TRACE)
        assert srv._throttle_level == 2
        # labeled transition counter + state gauge → exposition
        assert rt.stats.counters["throttle|feed=all"] >= 1
        assert rt.stats.gauges["throttle_state"] == 2.0
        from gyeeta_tpu.obs import prom
        assert 'gyt_throttle_total{feed="all"}' in prom.render(rt.stats)
        # pressure clears → early release rides one frame
        rt.stats.gauge("engine_drop_pressure", 0.0)
        await srv.push_throttle()
        await asyncio.sleep(0.1)
        assert not a._held(wire.FEED_ALL)
        assert not a._held(wire.FEED_TRACE)
        assert rt.stats.gauges["throttle_state"] == 0.0
        # a held agent spools instead of sending — the run_forever
        # decision point, exercised against a REAL hold
        await a.close()
        rt.stats.gauge("engine_drop_pressure", 1.0)
        stop = asyncio.Event()
        task = asyncio.create_task(a.run_forever(
            host, port, interval=0.05, n_conn=8, n_resp=8, stop=stop))
        loop = asyncio.get_running_loop()
        t_end = loop.time() + 5.0
        while a._writer is None and loop.time() < t_end:
            await asyncio.sleep(0.02)
        # the controller re-pushes while pressure persists: a LONG hold
        # so the cadence can't expire it mid-assertion
        srv.throttle_hold_ms = 30_000
        await srv.push_throttle()
        t_end = loop.time() + 5.0
        while (a.stats.counters.get("sweeps_throttled", 0) < 1
               and loop.time() < t_end):
            await asyncio.sleep(0.05)
        assert a.stats.counters.get("sweeps_throttled", 0) >= 1
        assert a.spool_len() >= 1
        # release: the loop drains the spool without a reconnect
        reconn_before = a.stats.counters.get("agent_reconnects", 0)
        rt.stats.gauge("engine_drop_pressure", 0.0)
        await srv.push_throttle()
        t_end = loop.time() + 5.0
        while a.spool_len() and loop.time() < t_end:
            await asyncio.sleep(0.05)
        assert a.spool_len() == 0
        assert a.stats.counters.get("spool_resent", 0) >= 1
        assert a.stats.counters.get("agent_reconnects", 0) \
            == reconn_before
        stop.set()
        await asyncio.wait_for(task, 5.0)
        await a.close()
        await srv.stop()

    asyncio.run(scenario())


# ------------------------------------------------ replay.py torn tail fix
def test_gytrec_torn_tail_counted_not_struct_error(tmp_path):
    cap = tmp_path / "cap.gytrec"
    rec = replay.StreamRecorder(cap)
    rec.write(b"A" * 100)
    rec.write(b"B" * 100)
    rec.close()
    data = cap.read_bytes()
    # chop mid-payload of the FINAL chunk
    cap.write_bytes(data[:-40])
    st = Stats()
    got = list(replay.read_chunks(cap, stats=st))
    assert [c for _, c in got] == [b"A" * 100]
    assert st.counters["replay_torn_tail"] == 1
    # chop mid-HEADER too (the struct.error shape)
    cap.write_bytes(data[: len(replay.MAGIC) + 5])
    st2 = Stats()
    assert list(replay.read_chunks(cap, stats=st2)) == []
    assert st2.counters["replay_torn_tail"] == 1
    # play() threads the same stat and stops cleanly
    cap.write_bytes(data[:-40])
    st3 = Stats()
    fed = []
    n = replay.play(cap, fed.append, stats=st3)
    assert n == 100 and fed == [b"A" * 100]
    assert st3.counters["replay_torn_tail"] == 1


def test_recorder_fsync_on_chunk_flag(tmp_path, monkeypatch):
    calls = []
    real_fsync = os.fsync
    monkeypatch.setattr(os, "fsync", lambda fd: (calls.append(fd),
                                                 real_fsync(fd))[1])
    rec = replay.StreamRecorder(tmp_path / "a.gytrec", fsync=True)
    rec.write(b"x" * 10)
    rec.write(b"y" * 10)
    rec.close()
    assert len(calls) == 2
    rec2 = replay.StreamRecorder(tmp_path / "b.gytrec")
    rec2.write(b"x" * 10)
    rec2.close()
    assert len(calls) == 2                         # default: no fsync


# ------------------------------------------------- stale .tmp.npz sweep
def test_stale_tmp_swept_and_candidates_unpolluted(tmp_path):
    from gyeeta_tpu.server_main import checkpoint_candidates
    rt = Runtime(CFG)
    good = tmp_path / "gyt_tick_00000010.npz"
    ckpt.save(str(good), CFG, rt.state, extra={"tick": 10})
    # a crash mid-save strands the staging file
    stale = tmp_path / "gyt_tick_00000020.tmp.npz"
    stale.write_bytes(b"half-written npz junk")
    older = tmp_path / "gyt_tick_00000005.tmp.npz"
    older.write_bytes(b"older junk")
    # candidates never see tmp files (ordering unpolluted)
    assert checkpoint_candidates(str(tmp_path)) == [str(good)]
    # the daemon-start sweep removes them
    assert ckpt.sweep_stale_tmp(str(tmp_path)) == 2
    assert not list(tmp_path.glob("*.tmp.npz"))
    assert checkpoint_candidates(str(tmp_path)) == [str(good)]
    # …and every SUCCESSFUL save re-sweeps (a fresh orphan disappears
    # the next time a checkpoint lands)
    stale.write_bytes(b"junk again")
    ckpt.save(str(tmp_path / "gyt_tick_00000030.npz"), CFG, rt.state,
              extra={"tick": 30})
    assert not list(tmp_path.glob("*.tmp.npz"))
    rt.close()


# -------------------------------------- graceful shutdown (daemon path)
def test_daemon_sigterm_drains_checkpoints_and_truncates(tmp_path,
                                                        monkeypatch):
    """SIGTERM during an active feed: staged slabs drain, the final
    checkpoint records the journal end, superseded segments drop, and
    a --restore-latest respawn replays ZERO chunks."""
    from gyeeta_tpu import server_main as SM

    # pin the daemon's engine geometry to the module CFG (env layer of
    # config.load_engine_cfg) so the respawn Runtime below matches
    for k, v in (("SVC_CAPACITY", 64), ("N_HOSTS", 4),
                 ("TASK_CAPACITY", 128), ("CONN_BATCH", 64),
                 ("RESP_BATCH", 64), ("LISTENER_BATCH", 32),
                 ("FOLD_K", 2)):
        monkeypatch.setenv(f"GYT_{k}", str(v))
    ckdir = tmp_path / "ck"
    wal = tmp_path / "wal"
    args = SM.parse_args([
        "--host", "127.0.0.1", "--port", "0",
        "--checkpoint-dir", str(ckdir), "--journal-dir", str(wal),
        "--restore-latest", "--tick-interval", "0",
        "--stats-interval", "3600", "--log-level", "WARNING"])
    args.tick_interval = None                      # manual ticks

    async def scenario():
        d = SM.Daemon(args)
        host, port = await d.srv.start()
        a = NetAgent(seed=304, n_svcs=2, n_groups=3)
        await a.connect(host, port)
        for _ in range(2):
            await a.send_sweep(n_conn=32, n_resp=32)
        await asyncio.sleep(0.1)
        staged_before = d.rt._n_conn_raw + d.rt._n_resp_raw
        await a.close()
        # the SIGTERM path: handle_signal → shutdown
        d.handle_signal(15)
        assert d.stop_event.is_set()
        await d.shutdown()
        return d.rt, staged_before

    rt1, staged_before = asyncio.run(scenario())
    assert staged_before > 0                 # the feed really was active
    assert rt1._n_conn_raw + rt1._n_resp_raw == 0    # drained
    finals = list(ckdir.glob("gyt_final_*.npz"))
    assert len(finals) == 1
    # respawn: restores the final checkpoint, replays an EMPTY window
    rt2 = Runtime(CFG, RuntimeOpts(journal_dir=str(wal),
                                   checkpoint_dir=str(ckdir)))
    assert SM.restore_latest_checkpoint(rt2, str(ckdir)) \
        == str(finals[0])
    assert rt2.stats.counters.get("wal_replayed_chunks", 0) == 0
    assert float(np.asarray(rt2.state.n_conn)) \
        == float(np.asarray(rt1.state.n_conn))
    rt2.close()
