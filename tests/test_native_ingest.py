"""Native C++ deframer: bit parity with the Python decoder + throughput
sanity (ref: the L1 epoll validate+batch stage, gy_mconnhdlr.cc:2430)."""

import time

import numpy as np
import pytest

from gyeeta_tpu.ingest import native, wire
from gyeeta_tpu.sim.partha import ParthaSim


needs_native = pytest.mark.skipif(
    not native.available(), reason="libgytdeframe.so not built")


def mixed_stream(seed=7, n_conn=3000, n_resp=9000):
    sim = ParthaSim(n_hosts=8, n_svcs=4, seed=seed)
    return (sim.conn_frames(n_conn) + sim.resp_frames(n_resp)
            + sim.listener_frames()
            + wire.encode_frame(wire.NOTIFY_HOST_STATE,
                                sim.host_state_records()))


@needs_native
def test_native_matches_python():
    buf = mixed_stream()
    nat, consumed_n = native.drain(buf)
    py, consumed_p = native._drain_py(buf)
    assert consumed_n == consumed_p == len(buf)
    assert set(nat) == set(py)
    for st in nat:
        # byte-level parity: random bits can land NaN float patterns
        # and NaN != NaN under array_equal
        assert nat[st].tobytes() == py[st].tobytes(), st


@needs_native
def test_native_partial_frame():
    buf = mixed_stream(n_conn=100, n_resp=0)
    cut = len(buf) - 33
    nat, consumed = native.drain(buf[:cut])
    py, consumed_p = native._drain_py(buf[:cut])
    assert consumed == consumed_p < cut
    for st in set(nat) | set(py):
        assert np.array_equal(nat[st], py[st])


@needs_native
def test_native_rejects_bad_magic():
    buf = bytearray(mixed_stream(n_conn=10, n_resp=0))
    buf[0] = 0x11
    with pytest.raises(wire.FrameError):
        native.drain(bytes(buf))


@needs_native
def test_native_skips_unknown_subtype():
    known = wire.encode_frame(wire.NOTIFY_RESP_SAMPLE,
                              np.zeros(5, wire.RESP_SAMPLE_DT))
    unknown = wire.encode_frame(777, np.zeros(3, wire.RESP_SAMPLE_DT))
    out, consumed = native.drain(unknown + known)
    assert consumed == len(unknown) + len(known)
    assert list(out) == [wire.NOTIFY_RESP_SAMPLE]
    assert len(out[wire.NOTIFY_RESP_SAMPLE]) == 5


@needs_native
def test_native_faster_than_python_on_small_frames():
    """Many small frames is where interpreter overhead bites — the case
    the native path exists for. Sanity: native >= python throughput."""
    sim = ParthaSim(n_hosts=8, n_svcs=4, seed=11)
    recs = sim.resp_records(20000)
    buf = b"".join(wire.encode_frame(wire.NOTIFY_RESP_SAMPLE,
                                     recs[i:i + 16])
                   for i in range(0, 20000, 16))

    def best_of(f, n=3):
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            f(buf)
            ts.append(time.perf_counter() - t0)
        return min(ts)

    native.drain(buf)          # warm the ctypes loader
    t_nat = best_of(native.drain)
    t_py = best_of(native._drain_py)
    # be generous (CI noise): native should not be slower
    assert t_nat < t_py, (t_nat, t_py)


def test_all_subtypes_covered_by_native_table():
    """Every subtype wire.py registers must round-trip through drain() —
    native and Python paths identically (the r2 native deframer silently
    dropped AGGR_TASK frames; this pins the whole-vocabulary contract)."""
    buf = b""
    rng = np.random.default_rng(3)
    for st, dt in sorted(wire.DTYPE_OF_SUBTYPE.items()):
        recs = np.frombuffer(
            rng.integers(0, 2 ** 63, 7 * dt.itemsize // 8,
                         dtype=np.int64).tobytes(), dt)
        buf += wire.encode_frame(st, recs)
    nat, consumed_n = native.drain(buf)
    py, consumed_p = native._drain_py(buf)
    assert consumed_n == consumed_p == len(buf)
    assert set(nat) == set(py) == set(wire.DTYPE_OF_SUBTYPE)
    for st in nat:
        # byte-level parity: random bits can land NaN float patterns
        # and NaN != NaN under array_equal
        assert nat[st].tobytes() == py[st].tobytes(), st


def _rand_records(rng, dt, n):
    nwords = max(n * dt.itemsize // 8, 1)
    return np.frombuffer(
        rng.integers(0, 2 ** 63, nwords, dtype=np.int64).tobytes(),
        dt, count=n)


def _drain_or_err(fn, buf):
    try:
        recs, consumed = fn(buf)
        return recs, consumed, None
    except wire.FrameError:
        return None, None, "frame_error"


@needs_native
def test_parity_fuzz_streams():
    """1000+ randomized mixed-subtype frame streams — including
    truncated tails, poison frames (bad magic / bad total_sz /
    nevents-over-cap / nevents-overflow) and unknown subtypes — must
    decode IDENTICALLY through the native and NumPy paths: same record
    bytes per subtype, same consumed count, same error outcomes."""
    rng = np.random.default_rng(20260804)
    subtypes = sorted(wire.DTYPE_OF_SUBTYPE)
    n_err = n_err_py = n_trunc = 0
    for trial in range(1000):
        parts = []
        for _ in range(int(rng.integers(1, 6))):
            st = int(rng.choice(subtypes))
            dt = wire.DTYPE_OF_SUBTYPE[st]
            nev = int(rng.integers(0, 17))
            frame = bytearray(wire.encode_frame(st, _rand_records(
                rng, dt, nev)))
            p = rng.random()
            if p < 0.04:       # poison: bad magic
                frame[0] ^= 0x5A
            elif p < 0.08:     # poison: bad total_sz
                frame[4:8] = int(rng.choice([4, 2 ** 25])).to_bytes(
                    4, "little")
            elif p < 0.12:     # poison: nevents over the subtype cap
                frame[20:24] = (wire.MAX_OF_SUBTYPE[st] + 1).to_bytes(
                    4, "little")
            elif p < 0.16:     # poison: nevents overflows the frame
                frame[20:24] = (nev + 8).to_bytes(4, "little")
            elif p < 0.22:     # unknown subtype: skipped, never an error
                frame[16:20] = int(rng.integers(500, 1000)).to_bytes(
                    4, "little")
            parts.append(bytes(frame))
        buf = b"".join(parts)
        if rng.random() < 0.25 and len(buf) > 4:  # truncated tail frame
            buf = buf[: len(buf) - int(rng.integers(1, len(parts[-1])))]
            n_trunc += 1
        nat, cons_n, err_n = _drain_or_err(native.drain, buf)
        py, cons_p, err_p = _drain_or_err(native._drain_py, buf)
        assert err_n == err_p, (trial, err_n, err_p)
        if err_n is not None:
            n_err += 1
            n_err_py += 1
            continue
        assert cons_n == cons_p, trial
        assert set(nat) == set(py), trial
        for st in nat:
            assert nat[st].tobytes() == py[st].tobytes(), (trial, st)
    # identical error counters across the whole fuzz run, and the fuzz
    # actually exercised the poison/truncation branches
    assert n_err == n_err_py
    assert n_err > 50, n_err
    assert n_trunc > 100, n_trunc


@needs_native
def test_native_resp_decode_parity():
    """gyt_decode_resp must be bit-identical to decode.resp_batch."""
    from gyeeta_tpu.ingest import decode
    from gyeeta_tpu.sim.partha import ParthaSim

    sim = ParthaSim(n_hosts=8, n_svcs=4, seed=21)
    recs = sim.resp_records(3000)
    a = decode.resp_batch_fast(recs, 4096)
    b = decode.resp_batch(recs, 4096)
    for f in a._fields:
        assert np.asarray(getattr(a, f)).tobytes() == \
            np.asarray(getattr(b, f)).tobytes(), f


@needs_native
@pytest.mark.parametrize("fast,ref,dt,size", [
    ("listener_batch_fast", "listener_batch", "LISTENER_STATE_DT", 64),
    ("host_batch_fast", "host_batch", "HOST_STATE_DT", 64),
    ("task_batch_fast", "task_batch", "AGGR_TASK_DT", 64),
    ("cpumem_batch_fast", "cpumem_batch", "CPU_MEM_DT", 64),
])
def test_native_sweep_decode_parity(fast, ref, dt, size):
    """The generic pack kernels (split_u64 / pack_f32 / pack_i32) must
    reproduce every NumPy sweep builder bit-for-bit on random records
    (random bits include NaN float patterns — compare bytes)."""
    from gyeeta_tpu.ingest import decode

    rng = np.random.default_rng(hash(fast) % 2 ** 31)
    recs = _rand_records(rng, getattr(wire, dt), 40)
    a = getattr(decode, fast)(recs, size)
    b = getattr(decode, ref)(recs, size)
    for f in a._fields:
        assert np.asarray(getattr(a, f)).tobytes() == \
            np.asarray(getattr(b, f)).tobytes(), f


@needs_native
def test_chunked_slab_assembly_parity():
    """conn/resp *_parts builders decode a LIST of staged chunks into
    the slab at lane offsets — output must equal the single-array
    decode of the concatenation (no np.concatenate on the hot path)."""
    from gyeeta_tpu.ingest import decode
    from gyeeta_tpu.sim.partha import ParthaSim

    sim = ParthaSim(n_hosts=8, n_svcs=4, seed=13)
    conn = sim.conn_records(700)
    resp = sim.resp_records(1500)
    cchunks = [conn[:100], conn[100:550], conn[550:]]
    rchunks = [resp[:1], resp[1:999], resp[999:]]
    a = decode.conn_batch_parts(cchunks, 1024)
    b = decode.conn_batch(conn, 1024)
    for f in a._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)),
            err_msg=f)
    ar = decode.resp_batch_parts(rchunks, 2048)
    br = decode.resp_batch(resp, 2048)
    for f in ar._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(ar, f)), np.asarray(getattr(br, f)),
            err_msg=f)
    # slab form: (k, b) reshape of the same flat decode
    s = decode.conn_slab(cchunks, 2, 512)
    assert s.svc_hi.shape == (2, 512)
    np.testing.assert_array_equal(s.svc_hi.reshape(-1), b.svc_hi[:1024])


def test_take_raw_chunks_no_copy():
    """take_raw_chunks returns views of the staged arrays (no
    concatenate, no copy) and take_raw only concatenates multi-chunk
    takes."""
    from gyeeta_tpu.ingest import decode

    a = np.zeros(100, wire.RESP_SAMPLE_DT)
    b = np.zeros(50, wire.RESP_SAMPLE_DT)
    lst = [a, b]
    chunks, got = decode.take_raw_chunks(lst, 80)
    assert got == 80 and len(chunks) == 1
    assert chunks[0].base is a or chunks[0] is a  # view, not a copy
    assert len(lst) == 2 and len(lst[0]) == 20
    # single-array take returns the array itself — no copy
    lst2 = [a]
    out = decode.take_raw(lst2, 200, wire.RESP_SAMPLE_DT)
    assert out is a


def test_force_python_fallback_env(monkeypatch):
    """GYT_PY_INGEST=1 forces the pure-Python decode path everywhere:
    native.available() flips off, the fast builders fall back
    (bit-identically) and the fallback counter records it."""
    from gyeeta_tpu.ingest import decode
    from gyeeta_tpu.sim.partha import ParthaSim
    from gyeeta_tpu.utils.selfstats import Stats

    sim = ParthaSim(n_hosts=4, n_svcs=2, seed=5)
    recs = sim.resp_records(100)
    monkeypatch.setenv("GYT_PY_INGEST", "1")
    assert not native.available()
    st = Stats()
    rb = decode.resp_batch_fast(recs, 128, stats=st)
    assert st.counters["ref_fallback_decoded"] == 100
    assert "ref_native_decoded" not in st.counters
    ref = decode.resp_batch(recs, 128)
    for f in rb._fields:
        assert np.asarray(getattr(rb, f)).tobytes() == \
            np.asarray(getattr(ref, f)).tobytes(), f
    # drain() falls back to the python decoder too
    buf = sim.resp_frames(64)
    py, consumed = native.drain(buf)
    assert consumed == len(buf)
    monkeypatch.delenv("GYT_PY_INGEST")


@needs_native
def test_native_path_counter(monkeypatch):
    from gyeeta_tpu.ingest import decode
    from gyeeta_tpu.sim.partha import ParthaSim
    from gyeeta_tpu.utils.selfstats import Stats

    sim = ParthaSim(n_hosts=4, n_svcs=2, seed=6)
    st = Stats()
    decode.conn_batch_fast(sim.conn_records(64), 128, stats=st)
    decode.listener_batch_fast(sim.listener_records()
                               if hasattr(sim, "listener_records")
                               else _rand_records(
                                   np.random.default_rng(0),
                                   wire.LISTENER_STATE_DT, 8),
                               64, stats=st)
    assert st.counters["ref_native_decoded"] >= 64
    assert "ref_fallback_decoded" not in st.counters


def test_native_conn_decode_parity():
    """gyt_decode_conn must be bit-identical to decode.conn_batch on
    random records, including NAT-translated tuples and accept flags."""
    import numpy as np
    import pytest

    from gyeeta_tpu.ingest import decode, native, wire
    from gyeeta_tpu.sim.partha import ParthaSim

    if not native.available():
        pytest.skip("native deframer not built")
    sim = ParthaSim(n_hosts=8, n_svcs=4, seed=77)
    recs = sim.conn_records(512)
    # exercise the NAT path: give some records translated tuples
    cli, ser = sim.svc_conn_records(64, split_halves=True)
    recs = np.concatenate([recs, cli, ser])
    rng = np.random.default_rng(5)
    nat_rows = rng.choice(len(recs), 100, replace=False)
    recs["nat_cli"]["ip"][nat_rows, :4] = rng.integers(
        1, 255, (100, 4), dtype=np.uint8)
    recs["nat_cli"]["port"][nat_rows] = rng.integers(
        1024, 65535, 100, dtype=np.uint16)
    # ...and the server-side DNAT branch (nat_ser), on overlapping and
    # disjoint rows so all four nat_c/nat_s combinations occur
    nat_s_rows = rng.choice(len(recs), 100, replace=False)
    recs["nat_ser"]["ip"][nat_s_rows, :4] = rng.integers(
        1, 255, (100, 4), dtype=np.uint8)
    recs["nat_ser"]["port"][nat_s_rows] = rng.integers(
        1024, 65535, 100, dtype=np.uint16)

    size = 1024
    a = native.decode_conn(recs, size)
    b = decode.conn_batch(recs, size)
    assert a is not None
    for field in a._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, field)), np.asarray(getattr(b, field)),
            err_msg=field)


@pytest.mark.parametrize("alloc", ["conn", "resp", "slab"])
def test_staging_columns_are_views_of_one_block(alloc):
    """The columns the allocators hand out are what the decoders write
    into AND what crosses to the device: C-contiguous, writable, in the
    fold's dtypes, one after the other inside one word block
    (ingest/pack.py), ``valid`` among them."""
    from gyeeta_tpu.ingest import decode, pack

    nc, nr = {"conn": (96, 0), "resp": (0, 200), "slab": (96, 200)}[alloc]
    if alloc == "slab":
        block, conn, resp = decode.alloc_slab_cols(nc, nr)
    else:
        conn = decode.alloc_conn_cols(nc) if nc else {}
        resp = decode.alloc_resp_cols(nr) if nr else {}
        block = next(iter({**conn, **resp}.values())).base
    assert block.dtype == np.uint32 and block.ndim == 1
    assert block.flags.owndata and not block.any()
    assert tuple(conn) == (decode.ConnBatch._fields if conn else ())
    assert tuple(resp) == (decode.RespBatch._fields if resp else ())
    layout = tuple((np.dtype(dt), (n,)) for n, dts in (
        (nc, decode._CONN_DTYPES), (nr, decode._RESP_DTYPES)) for dt in dts)
    offs, nwords = pack.offsets(layout)
    assert block.size == nwords and np.all(np.diff(offs) > 0)
    place = dict(zip(
        [("conn", f) for f in decode.ConnBatch._fields]
        + [("resp", f) for f in decode.RespBatch._fields],
        zip(offs, layout)))
    for which, cols in (("conn", conn), ("resp", resp)):
        for name, a in cols.items():
            off, (dt, shape) = place[which, name]
            assert a.dtype == dt and a.shape == shape, name
            assert a.flags.c_contiguous and a.flags.writeable, name
            assert a.base is block, name
            # each from a 64-byte line of its own
            assert a.ctypes.data == block.ctypes.data + 4 * off, name
            assert off % pack.LINE == 0, name


@needs_native
def test_native_and_numpy_decoders_agree_through_the_block():
    """Decoding into the block's views (the native path, a reused
    buffer whose earlier fill was larger) equals the NumPy decoders'
    fresh columns, column by column and as the packed block."""
    from gyeeta_tpu.ingest import decode, pack

    sim = ParthaSim(n_hosts=8, n_svcs=4, seed=31)
    block, ccols, rcols = decode.alloc_slab_cols(1024, 2048)
    decode.conn_batch_parts([sim.conn_records(900)], 1024, out=ccols)
    decode.resp_batch_parts([sim.resp_records(2048)], 2048, out=rcols)
    conn, resp = sim.conn_records(333), sim.resp_records(777)
    a = decode.conn_batch_parts([conn[:100], conn[100:]], 1024,
                                out=ccols, clear_to=900)
    ar = decode.resp_batch_parts([resp], 2048, out=rcols, clear_to=2048)
    b, br = decode.conn_batch(conn, 1024), decode.resp_batch(resp, 2048)
    for got, want in ((a, b), (ar, br)):
        for f in got._fields:
            assert getattr(got, f).base is block, f
            assert getattr(got, f).tobytes() == \
                getattr(want, f).tobytes(), f
    assert np.array_equal(block, pack.pack(list(b) + list(br)))


# ------------------------------------------------- any buffer object in
def _as_buffer(kind: str, buf: bytes):
    """``buf`` as the serving edge may hand it in: the conn's receive
    buffer is a bytearray, and a run of frames a view into it."""
    if kind == "bytes":
        return buf
    if kind == "bytearray":
        return bytearray(buf)
    if kind == "memoryview":                # offset + writable backing
        return memoryview(bytearray(b"\xa5" * 13 + buf + b"\x5a" * 7)
                          )[13:13 + len(buf)]
    return memoryview(b"\xa5" * 5 + buf)[5:]            # read-only


@pytest.mark.parametrize("path", ["native", "python"])
@pytest.mark.parametrize("kind", ["bytes", "bytearray", "memoryview",
                                  "memoryview_ro"])
def test_drain2_takes_any_buffer(kind, path):
    """Equal records, ``consumed`` and ``unknown`` whatever object
    carries the bytes, and nothing of it is referenced afterwards (the
    edge overwrites its receive buffer right after the call)."""
    if path == "native" and not native.available():
        pytest.skip("libgytdeframe.so not built")
    drain2 = native.drain2 if path == "native" else native._drain_py2
    whole = (mixed_stream(n_conn=40, n_resp=90)
             + wire.encode_frame(777, np.zeros(3, wire.RESP_SAMPLE_DT)))
    buf = whole + mixed_stream(seed=8, n_conn=5, n_resp=0)[:-33]
    want, consumed_w, unknown_w = drain2(buf)
    assert len(whole) <= consumed_w < len(buf) and unknown_w == 3
    obj = _as_buffer(kind, buf)
    got, consumed, unknown = drain2(obj)
    assert (consumed, unknown) == (consumed_w, unknown_w)
    if kind in ("bytearray", "memoryview"):
        obj[:] = bytes(len(obj))            # the edge's next read
    assert set(got) == set(want)
    for st in want:
        assert got[st].tobytes() == want[st].tobytes(), st
