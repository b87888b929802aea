"""ctypes loader for the native deframer, with pure-Python fallback.

``drain(buf)`` is the L1 ingest entry point: one pass over a byte stream →
{subtype: contiguous record array} + bytes consumed. Uses the C++ fast
path (built lazily on first use when g++ is available), else
``wire.decode_frames``.

The subtype table is pushed INTO the library from ``wire.DTYPE_OF_SUBTYPE``
at load time (``gyt_set_table``) and echoed back (``gyt_layout``) — the
native path structurally cannot drift from wire.py the way a compiled-in
table could.

Beyond deframing, this module is the host half of the **wire→columnar
compiler**: ``decode_conn_into``/``decode_resp_into`` and the generic
``split_u64_into``/``pack_f32_into``/``pack_i32_into`` kernels decode raw
record arrays straight into caller-provided preallocated NumPy column
buffers at a lane offset (zero-copy, GIL released for the whole pass).
Column plans (field offset + scalar kind) are compiled HERE from the
wire.py dtypes and executed in C++ — ``ingest/decode.py`` keeps the
bit-identical NumPy reference implementations as the fallback.

Setting ``GYT_PY_INGEST=1`` forces the pure-Python path everywhere (a
debug knob; see OPERATIONS.md) — checked on every load so tests can
toggle it per-process.
"""

from __future__ import annotations

import ctypes
import os
import pathlib

import numpy as np

from gyeeta_tpu.ingest import wire

_SO = pathlib.Path(__file__).resolve().parent / "libgytdeframe.so"
_SRC = pathlib.Path(__file__).resolve().parent / "deframe.cpp"
_lib = None
_load_failed = False

_ERRNAMES = {1: "bad magic", 2: "bad total_sz", 3: "batch cap exceeded",
             4: "nevents does not fill frame", 5: "output buffer full",
             6: "bad subtype table",
             7: "unexpected data_type on event stream",
             8: "payload checksum mismatch"}
# rc → FrameError.reason (the frames_rejected|reason=... label values;
# identical to the labels the pure-Python decoder raises with)
_ERRREASON = {1: "bad_magic", 2: "bad_size", 3: "bad_size",
              4: "bad_size", 6: "bad_frame", 7: "bad_dtype",
              8: "checksum"}

# drain() output ordering; derived from wire.py, never hand-maintained
_SCAN_ORDER = tuple(sorted(wire.DTYPE_OF_SUBTYPE))

# scalar kind codes of the C++ pack kernels (deframe.cpp PackKind)
_KIND = {("u", 1): 1, ("u", 2): 2, ("u", 4): 3, ("u", 8): 4,
         ("i", 4): 5, ("f", 4): 6}


def _forced_python() -> bool:
    return os.environ.get("GYT_PY_INGEST", "") not in ("", "0")


def _ensure_built() -> bool:
    """Build (or rebuild, if deframe.cpp is newer) the shared object."""
    try:
        if _SO.exists() and (not _SRC.exists()
                             or _SO.stat().st_mtime >= _SRC.stat().st_mtime):
            return True
        from gyeeta_tpu.ingest.native import build
        build.build(verbose=False)
        return True
    except Exception:
        return _SO.exists()


def _load():
    global _lib, _load_failed
    if _forced_python():
        return None
    if _lib is not None or _load_failed:
        return _lib
    if not _ensure_built():
        _load_failed = True
        return None
    try:
        lib = ctypes.CDLL(str(_SO))
        return _bind_and_handshake(lib)
    except Exception:
        # unloadable or stale .so (e.g. missing gyt_set_table symbol):
        # fall back to the pure-Python decoder permanently
        _load_failed = True
        return None


def _bind_and_handshake(lib):
    global _lib
    i64p = ctypes.POINTER(ctypes.c_int64)
    lib.gyt_set_table.restype = ctypes.c_int32
    lib.gyt_set_table.argtypes = [i64p, ctypes.c_int32]
    lib.gyt_extract.restype = ctypes.c_int32
    lib.gyt_extract.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_uint32,
        ctypes.c_void_p, ctypes.c_int64, i64p, i64p, i64p]
    lib.gyt_extract_multi.restype = ctypes.c_int32
    lib.gyt_extract_multi.argtypes = [
        ctypes.c_char_p, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_void_p), i64p, i64p, i64p]
    lib.gyt_scan.restype = ctypes.c_int32
    lib.gyt_scan.argtypes = [ctypes.c_char_p, ctypes.c_int64, i64p, i64p]
    # sizing scan that also counts records in skipped unknown-subtype
    # frames (chaos-tier loss accounting); a .so predating the symbol
    # fails the bind here and the loader falls back to pure Python
    lib.gyt_scan2.restype = ctypes.c_int32
    lib.gyt_scan2.argtypes = [ctypes.c_char_p, ctypes.c_int64, i64p,
                              i64p, i64p]
    lib.gyt_layout.restype = ctypes.c_int32
    lib.gyt_layout.argtypes = [i64p, ctypes.c_int64]
    # push the subtype table from wire.py (single source of truth) ...
    n = len(_SCAN_ORDER)
    tri = (ctypes.c_int64 * (3 * n))()
    for i, st in enumerate(_SCAN_ORDER):
        tri[i * 3 + 0] = st
        tri[i * 3 + 1] = wire.DTYPE_OF_SUBTYPE[st].itemsize
        tri[i * 3 + 2] = wire.MAX_OF_SUBTYPE[st]
    rc = lib.gyt_set_table(tri, n)
    if rc != 0:
        raise RuntimeError(f"gyt_set_table: {_ERRNAMES.get(rc, rc)}")
    # ... and verify the round-trip covers every subtype
    back = (ctypes.c_int64 * (3 * n))()
    got = lib.gyt_layout(back, n)
    native = {int(back[i * 3]): (int(back[i * 3 + 1]), int(back[i * 3 + 2]))
              for i in range(got)}
    expect = {st: (wire.DTYPE_OF_SUBTYPE[st].itemsize,
                   wire.MAX_OF_SUBTYPE[st]) for st in _SCAN_ORDER}
    if native != expect:
        raise RuntimeError(
            f"native deframer layout mismatch: {native} != {expect}")
    # columnar conn-decode layout push (same single-source discipline)
    lib.gyt_set_conn_layout.restype = ctypes.c_int32
    lib.gyt_set_conn_layout.argtypes = [i64p, ctypes.c_int32]
    lib.gyt_decode_conn.restype = ctypes.c_int32
    lib.gyt_decode_conn.argtypes = [ctypes.c_void_p, ctypes.c_int64] + \
        [ctypes.c_void_p] * 16
    dt = wire.TCP_CONN_DT
    off = {name: dt.fields[name][1] for name in dt.names}
    fields = [dt.itemsize,
              off["cli"], off["ser"], off["nat_cli"], off["nat_ser"],
              off["tusec_start"], off["tusec_close"],
              off["cli_task_aggr_id"], off["cli_related_listen_id"],
              off["ser_glob_id"], off["bytes_sent"], off["bytes_rcvd"],
              off["host_id"], off["flags"],
              wire.IP_PORT_DT.fields["port"][1]]
    arr = (ctypes.c_int64 * len(fields))(*fields)
    rc = lib.gyt_set_conn_layout(arr, len(fields))
    if rc != 0:
        raise RuntimeError(f"gyt_set_conn_layout: "
                           f"{_ERRNAMES.get(rc, rc)}")
    # resp-decode layout push (wire.RESP_SAMPLE_DT)
    lib.gyt_set_resp_layout.restype = ctypes.c_int32
    lib.gyt_set_resp_layout.argtypes = [i64p, ctypes.c_int32]
    lib.gyt_decode_resp.restype = ctypes.c_int32
    lib.gyt_decode_resp.argtypes = [ctypes.c_void_p, ctypes.c_int64] + \
        [ctypes.c_void_p] * 4
    rdt = wire.RESP_SAMPLE_DT
    rfields = [rdt.itemsize, rdt.fields["glob_id"][1],
               rdt.fields["resp_usec"][1], rdt.fields["host_id"][1]]
    rarr = (ctypes.c_int64 * len(rfields))(*rfields)
    rc = lib.gyt_set_resp_layout(rarr, len(rfields))
    if rc != 0:
        raise RuntimeError(f"gyt_set_resp_layout: "
                           f"{_ERRNAMES.get(rc, rc)}")
    # generic pack kernels (column plans ride along each call)
    lib.gyt_pack_f32.restype = ctypes.c_int32
    lib.gyt_pack_f32.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, i64p,
        ctypes.c_int32, ctypes.c_void_p]
    lib.gyt_split_u64.restype = ctypes.c_int32
    lib.gyt_split_u64.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_void_p, ctypes.c_void_p]
    lib.gyt_pack_i32.restype = ctypes.c_int32
    lib.gyt_pack_i32.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_void_p]
    _lib = lib
    return _lib


def _check(rc: int, what: str) -> None:
    if rc != 0:
        raise RuntimeError(f"{what}: {_ERRNAMES.get(rc, rc)}")


def _ptr(a, off: int = 0):
    """ctypes pointer to lane ``off`` of a contiguous 1-D/2-D array."""
    v = a[off:] if off else a
    return v.ctypes.data_as(ctypes.c_void_p)


def _recs_ptr(recs: np.ndarray):
    recs = np.ascontiguousarray(recs)
    # keep a reference alive for the duration of the call site
    return recs, recs.ctypes.data_as(ctypes.c_void_p)


# column plans: (dtype, fields) → compiled (src_off, kind) int64 array.
# Compiled once per subtype from the wire.py dtype — the "compiler" half
# of the wire→columnar path; deframe.cpp's kernels are the executor.
_PLANS: dict = {}


def _plan(dt: np.dtype, fields: tuple):
    key = (dt, fields)
    ops = _PLANS.get(key)
    if ops is None:
        vals = []
        for f in fields:
            fdt, foff = dt.fields[f][0], dt.fields[f][1]
            vals += [foff, _KIND[(fdt.kind, fdt.itemsize)]]
        ops = (ctypes.c_int64 * len(vals))(*vals)
        _PLANS[key] = ops
    return ops


def available() -> bool:
    return _load() is not None


def decode_path() -> str:
    """The decode path a span reports: ``native`` or ``python``."""
    return "native" if available() else "python"


# ------------------------------------------------------ columnar kernels
def decode_conn_into(recs: np.ndarray, cols: dict, off: int = 0) -> bool:
    """Decode TCP_CONN records into flat column arrays at lane ``off``
    (cols: the ConnBatch columns by field name, each contiguous and of
    length >= off+len(recs); ``valid`` is the caller's to set). Returns False when the native library is
    unavailable — callers fall back to decode.conn_batch."""
    lib = _load()
    if lib is None:
        return False
    if recs.dtype != wire.TCP_CONN_DT:
        raise TypeError(f"decode_conn_into needs TCP_CONN_DT records, "
                        f"got {recs.dtype}")  # C++ walks layout offsets
    recs, rp = _recs_ptr(recs)
    _check(lib.gyt_decode_conn(
        rp, len(recs),
        _ptr(cols["svc_hi"], off), _ptr(cols["svc_lo"], off),
        _ptr(cols["flow_hi"], off), _ptr(cols["flow_lo"], off),
        _ptr(cols["cli_hi"], off), _ptr(cols["cli_lo"], off),
        _ptr(cols["cli_task_hi"], off), _ptr(cols["cli_task_lo"], off),
        _ptr(cols["cli_rel_hi"], off), _ptr(cols["cli_rel_lo"], off),
        _ptr(cols["bytes_sent"], off), _ptr(cols["bytes_rcvd"], off),
        _ptr(cols["duration_us"], off), _ptr(cols["host_id"], off),
        _ptr(cols["is_close"], off), _ptr(cols["is_accept"], off)),
        "gyt_decode_conn")
    return True


def decode_resp_into(recs: np.ndarray, svc_hi, svc_lo, resp_us, host_id,
                     off: int = 0) -> bool:
    """Decode RESP_SAMPLE records into flat columns at lane ``off``
    (bit-identical to decode.resp_batch's numpy math)."""
    lib = _load()
    if lib is None:
        return False
    if recs.dtype != wire.RESP_SAMPLE_DT:
        raise TypeError(f"decode_resp_into needs RESP_SAMPLE_DT records, "
                        f"got {recs.dtype}")
    recs, rp = _recs_ptr(recs)
    _check(lib.gyt_decode_resp(
        rp, len(recs), _ptr(svc_hi, off), _ptr(svc_lo, off),
        _ptr(resp_us, off), _ptr(host_id, off)), "gyt_decode_resp")
    return True


def split_u64_into(recs: np.ndarray, field: str, hi, lo,
                   off: int = 0) -> bool:
    """One u64 record field → (hi, lo) uint32 columns at lane ``off``."""
    lib = _load()
    if lib is None:
        return False
    recs, rp = _recs_ptr(recs)
    _check(lib.gyt_split_u64(
        rp, len(recs), recs.dtype.itemsize, recs.dtype.fields[field][1],
        _ptr(hi, off), _ptr(lo, off)), "gyt_split_u64")
    return True


def pack_f32_into(recs: np.ndarray, fields: tuple, out: np.ndarray,
                  off: int = 0) -> bool:
    """Record fields → float32 matrix rows [off:off+n) of ``out``
    (shape (size, len(fields)), C-contiguous)."""
    lib = _load()
    if lib is None:
        return False
    if not out.flags.c_contiguous or out.dtype != np.float32 \
            or out.shape[1] != len(fields):
        raise ValueError(f"pack_f32_into needs a C-contiguous float32 "
                         f"(size, {len(fields)}) output, got "
                         f"{out.dtype}{out.shape}")
    recs, rp = _recs_ptr(recs)
    _check(lib.gyt_pack_f32(
        rp, len(recs), recs.dtype.itemsize, _plan(recs.dtype, fields),
        len(fields), _ptr(out, off)), "gyt_pack_f32")
    return True


def pack_i32_into(recs: np.ndarray, field: str, out, off: int = 0) -> bool:
    """One scalar record field → int32 column at lane ``off``."""
    lib = _load()
    if lib is None:
        return False
    fdt = recs.dtype.fields[field][0]
    recs, rp = _recs_ptr(recs)
    _check(lib.gyt_pack_i32(
        rp, len(recs), recs.dtype.itemsize, recs.dtype.fields[field][1],
        _KIND[(fdt.kind, fdt.itemsize)], _ptr(out, off)), "gyt_pack_i32")
    return True


def decode_conn(recs, size: int):
    """Native columnar TCP_CONN decode → ConnBatch (or None when the
    native library is unavailable — callers fall back to
    decode.conn_batch). Semantics bit-identical to the Python decoder;
    tests/test_native_ingest.py diffs them on random records."""
    if _load() is None:
        return None
    from gyeeta_tpu.ingest import decode as D

    if len(recs) > size:
        raise ValueError(f"{len(recs)} records exceed batch size {size};"
                         f" split upstream")
    cols = D.alloc_conn_cols(size)
    decode_conn_into(recs, cols, 0)
    cols["valid"][:len(recs)] = True
    return D.ConnBatch(**cols)


def _buf_ptr(buf):
    """→ (keepalive, ``c_char_p``, nbytes) of a contiguous buffer
    object: the C side reads the bytes where they lie (``bytes`` pass
    as they are; a ``bytearray`` or a ``memoryview``, offset or
    read-only, through the address numpy resolves for it; numpy
    refuses one that is not contiguous)."""
    if isinstance(buf, bytes):
        return buf, buf, len(buf)
    a = np.frombuffer(buf, np.uint8)
    return a, ctypes.c_char_p(a.ctypes.data), a.size


def drain(buf) -> tuple[dict, int]:
    """byte stream → ({subtype: structured record array}, consumed).
    Thin wrapper over :func:`drain2` for callers that don't need the
    unknown-subtype record count."""
    out, consumed, _unknown = drain2(buf)
    return out, consumed


def drain2(buf) -> tuple[dict, int, int]:
    """byte stream → ({subtype: record array}, consumed, unknown_recs).

    ``buf`` is any contiguous buffer object (``bytes``, ``bytearray``,
    ``memoryview``): the records are COPIED out into arrays of their
    own, and nothing of ``buf`` is referenced once this returns — the
    serving edge hands in a view of a conn's receive buffer and
    overwrites it right after.

    Native path when built; identical semantics to the Python decoder
    (validation errors raise wire.FrameError either way). Two passes
    total: one sizing scan, then ONE frame walk that appends every
    subtype's records into its preallocated array (gyt_extract_multi).
    ``unknown_recs`` counts records claimed by skipped unknown-subtype
    frames — the feed path attributes them to a counter so a corrupted
    subtype byte is accounted loss, never silent loss.
    """
    lib = _load()
    if lib is None:
        return _drain_py2(buf)
    n = len(_SCAN_ORDER)
    counts = (ctypes.c_int64 * n)()
    consumed = ctypes.c_int64()
    unknown = ctypes.c_int64()
    _keep, ptr, nbytes = _buf_ptr(buf)      # _keep: alive until return
    rc = lib.gyt_scan2(ptr, nbytes, counts, ctypes.byref(consumed),
                       ctypes.byref(unknown))
    if rc != 0:
        raise wire.FrameError(f"native scan: {_ERRNAMES.get(rc, rc)}",
                              reason=_ERRREASON.get(rc, "bad_frame"))
    out: dict = {}
    outs = (ctypes.c_void_p * n)()
    caps = (ctypes.c_int64 * n)()
    nrec = (ctypes.c_int64 * n)()
    nonempty = False
    for i, subtype in enumerate(_SCAN_ORDER):
        if counts[i] == 0:
            continue
        rec = np.empty(counts[i], wire.DTYPE_OF_SUBTYPE[subtype])
        out[subtype] = rec
        outs[i] = rec.ctypes.data
        caps[i] = rec.nbytes
        nonempty = True
    if not nonempty:
        return out, int(consumed.value), int(unknown.value)
    c2 = ctypes.c_int64()
    rc = lib.gyt_extract_multi(ptr, nbytes, outs, caps, nrec,
                               ctypes.byref(c2))
    if rc != 0:
        raise wire.FrameError(f"native extract: {_ERRNAMES.get(rc, rc)}",
                              reason=_ERRREASON.get(rc, "bad_frame"))
    for i, subtype in enumerate(_SCAN_ORDER):
        if counts[i]:
            assert nrec[i] == counts[i], (subtype, nrec[i], counts[i])
    return out, int(consumed.value), int(unknown.value)


def _drain_py(buf: bytes) -> tuple[dict, int]:
    out, consumed, _unknown = _drain_py2(buf)
    return out, consumed


def _drain_py2(buf: bytes) -> tuple[dict, int, int]:
    cnt: dict = {}
    frames, consumed = wire.decode_frames(buf, counts=cnt,
                                          event_only=True)
    out: dict = {}
    for subtype, recs in frames:
        if not len(recs):
            continue     # drain contract: no empty entries (native parity)
        if subtype in out:
            out[subtype] = np.concatenate([out[subtype], recs])
        else:
            out[subtype] = recs.copy()
    return out, consumed, cnt.get("unknown_records", 0)
