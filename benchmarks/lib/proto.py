"""The served wire protocol, as a client sees it — the benchmark's own copy.

Record layouts, framing, the registration handshake, the query channel and
the identity hashes (flow key, interned names) that answers are named by.
Copied from ``gyeeta_tpu/ingest/wire.py``, ``utils/hashing.py``,
``ingest/decode.py`` (``fold_ip``, ``split_u64``) and ``net/agent.py``
(``register``, ``QueryClient``) so that the yardstick — generator, clients,
recount — imports nothing of the program. numpy and asyncio only.
"""

from __future__ import annotations

import asyncio
import json

import numpy as np

MAGIC_PM = 0x47590001
MAGIC_MS = 0x47590002
MAGIC_NQ = 0x47590003
MAGICS = (MAGIC_PM, MAGIC_MS, MAGIC_NQ)
MAX_COMM_DATA_SZ = 16 * 1024 * 1024
WIRE_VERSION = 5

COMM_EVENT_NOTIFY = 1
COMM_QUERY_CMD = 2
COMM_QUERY_RESP = 3
COMM_REGISTER_REQ = 4
COMM_REGISTER_RESP = 5

NOTIFY_TCP_CONN = 10
NOTIFY_LISTENER_STATE = 11
NOTIFY_HOST_STATE = 12
NOTIFY_RESP_SAMPLE = 13
NOTIFY_NAME_INTERN = 16
NOTIFY_LISTENER_INFO = 18
NOTIFY_HOST_INFO = 19

# per-message record caps (reference gy_comm_proto.h:1711, :2222)
MAX_OF_SUBTYPE = {
    NOTIFY_TCP_CONN: 2048, NOTIFY_LISTENER_STATE: 512,
    NOTIFY_HOST_STATE: 4096, NOTIFY_RESP_SAMPLE: 4096,
    NOTIFY_NAME_INTERN: 1024, NOTIFY_LISTENER_INFO: 1024,
    NOTIFY_HOST_INFO: 1024,
}

HEADER_DT = np.dtype([("magic", "<u4"), ("total_sz", "<u4"),
                      ("data_type", "<u4"), ("padding_sz", "<u4")])
EVENT_NOTIFY_DT = np.dtype([("subtype", "<u4"), ("nevents", "<u4")])
IP_PORT_DT = np.dtype([("ip", "u1", (16,)), ("port", "<u2"),
                       ("pad", "u1", (6,))])
TCP_CONN_DT = np.dtype([
    ("cli", IP_PORT_DT), ("ser", IP_PORT_DT),
    ("nat_cli", IP_PORT_DT), ("nat_ser", IP_PORT_DT),
    ("tusec_start", "<u8"), ("tusec_close", "<u8"),
    ("cli_task_aggr_id", "<u8"), ("cli_related_listen_id", "<u8"),
    ("cli_madhava_id", "<u8"), ("peer_machine_id_hi", "<u8"),
    ("peer_machine_id_lo", "<u8"), ("ser_related_listen_id", "<u8"),
    ("ser_glob_id", "<u8"), ("ser_madhava_id", "<u8"),
    ("bytes_sent", "<u8"), ("bytes_rcvd", "<u8"),
    ("cli_pid", "<i4"), ("ser_pid", "<i4"),
    ("ser_conn_hash", "<u4"), ("ser_sock_inode", "<u4"),
    ("cli_comm_id", "<u8"), ("ser_comm_id", "<u8"),
    ("cli_cmdline_id", "<u8"), ("host_id", "<u4"), ("flags", "<u4"),
])
LISTENER_STATE_DT = np.dtype([
    ("glob_id", "<u8"), ("nqrys_5s", "<u4"), ("total_resp_5sec", "<u4"),
    ("nconns", "<u4"), ("nconns_active", "<u4"), ("ntasks", "<u4"),
    ("p95_5s_resp_ms", "<u4"), ("p95_5min_resp_ms", "<u4"),
    ("curr_kbytes_inbound", "<u4"), ("curr_kbytes_outbound", "<u4"),
    ("ser_errors", "<u4"), ("cli_errors", "<u4"),
    ("tasks_delay_usec", "<u4"), ("tasks_cpudelay_usec", "<u4"),
    ("tasks_blkiodelay_usec", "<u4"), ("tasks_user_cpu", "<u4"),
    ("tasks_sys_cpu", "<u4"), ("tasks_rss_mb", "<u4"),
    ("ntasks_issue", "<u2"), ("is_http_svc", "u1"), ("curr_state", "u1"),
    ("curr_issue", "u1"), ("issue_bit_hist", "u1"),
    ("high_resp_bit_hist", "u1"), ("last_issue_subsrc", "u1"),
    ("query_flags", "<u4"), ("host_id", "<u4"), ("pad", "u1", (4,)),
    ("issue_string_id", "<u8"),
])
HOST_STATE_DT = np.dtype([
    ("curr_time_usec", "<u8"), ("ntasks_issue", "<u4"),
    ("ntasks_severe", "<u4"), ("ntasks", "<u4"), ("nlisten_issue", "<u4"),
    ("nlisten_severe", "<u4"), ("nlisten", "<u4"), ("curr_state", "u1"),
    ("issue_bit_hist", "u1"), ("cpu_issue", "u1"), ("mem_issue", "u1"),
    ("severe_cpu_issue", "u1"), ("severe_mem_issue", "u1"),
    ("pad", "u1", (2,)), ("host_id", "<u4"), ("pad2", "u1", (4,)),
])
RESP_SAMPLE_DT = np.dtype([("glob_id", "<u8"), ("resp_usec", "<u4"),
                           ("host_id", "<u4")])
LISTENER_INFO_DT = np.dtype([
    ("glob_id", "<u8"), ("addr", IP_PORT_DT), ("tusec_start", "<u8"),
    ("cmdline_id", "<u8"), ("comm_id", "<u8"),
    ("related_listen_id", "<u8"), ("pid", "<i4"), ("is_any_ip", "u1"),
    ("is_http", "u1"), ("pad", "u1", (2,)), ("host_id", "<u4"),
    ("pad2", "u1", (4,)),
])
HOST_INFO_DT = np.dtype([
    ("host_id", "<u4"), ("ncpus", "<u2"), ("nnuma", "<u2"),
    ("ram_mb", "<u4"), ("swap_mb", "<u4"), ("boot_tusec", "<u8"),
    ("kern_ver_id", "<u8"), ("distro_id", "<u8"), ("cputype_id", "<u8"),
    ("instance_id", "<u8"), ("region_id", "<u8"), ("zone_id", "<u8"),
    ("virt_type", "u1"), ("cloud_type", "u1"), ("is_k8s", "u1"),
    ("pad", "u1", (5,)),
])
NAME_KIND_COMM, NAME_KIND_SVC, NAME_KIND_HOST = 1, 2, 3
NAME_KIND_API, NAME_KIND_MISC = 4, 5
MAX_NAME_BYTES = 48
NAME_INTERN_DT = np.dtype([("name_id", "<u8"), ("kind", "<u4"),
                           ("nlen", "<u4"),
                           ("name", "u1", (MAX_NAME_BYTES,))])
REGISTER_REQ_DT = np.dtype([
    ("machine_id_hi", "<u8"), ("machine_id_lo", "<u8"),
    ("wire_version", "<u4"), ("conn_type", "<u4"), ("hostname_id", "<u8")])
REGISTER_RESP_DT = np.dtype([("status", "<u4"), ("host_id", "<u4"),
                             ("curr_version", "<u4"), ("pad", "u1", (4,))])
CONN_EVENT, CONN_QUERY = 1, 2
REG_OK = 0
QUERY_HDR_DT = np.dtype([("seqid", "<u8"), ("status", "<u4"),
                         ("nbytes", "<u4")])
QS_OK, QS_PARTIAL = 0, 3
CHK_FLAG = 0x80000000
_CHK_SHIFT = 8


class ProtoError(Exception):
    """A frame the protocol does not allow, in either direction."""


# ----------------------------------------------------------------- framing
def _xor8(b) -> int:
    a = np.frombuffer(b, np.uint8)
    return int(np.bitwise_xor.reduce(a)) if a.size else 0


def _frame(data_type: int, payload: bytes, magic: int) -> bytes:
    pad = (-len(payload)) % 8
    hdr = np.zeros((), HEADER_DT)
    hdr["magic"] = magic
    hdr["total_sz"] = HEADER_DT.itemsize + len(payload) + pad
    hdr["data_type"] = data_type
    hdr["padding_sz"] = pad
    return hdr.tobytes() + payload + b"\x00" * pad


def encode_frame(subtype: int, records: np.ndarray) -> bytes:
    """COMM_HEADER + EVENT_NOTIFY + records, with the payload checksum."""
    cap = MAX_OF_SUBTYPE[subtype]
    if len(records) > cap:
        raise ProtoError(f"{len(records)} records > cap {cap} for "
                         f"subtype {subtype}")
    payload = records.tobytes()
    hdr = np.zeros((), HEADER_DT)
    hdr["magic"] = MAGIC_PM
    hdr["total_sz"] = (HEADER_DT.itemsize + EVENT_NOTIFY_DT.itemsize
                       + len(payload))
    hdr["data_type"] = COMM_EVENT_NOTIFY
    ev = np.zeros((), EVENT_NOTIFY_DT)
    ev["subtype"] = subtype
    ev["nevents"] = len(records)
    ev_b = ev.tobytes()
    hdr["padding_sz"] = CHK_FLAG | ((_xor8(ev_b) ^ _xor8(payload))
                                    << _CHK_SHIFT)
    return hdr.tobytes() + ev_b + payload


def encode_frames(subtype: int, records: np.ndarray) -> bytes:
    """Any number of records, split at the subtype's per-message cap."""
    cap = MAX_OF_SUBTYPE[subtype]
    return b"".join(encode_frame(subtype, records[i:i + cap])
                    for i in range(0, len(records), cap))


async def read_frame(reader) -> tuple:
    """→ (data_type, payload), header validated before the body read."""
    hsz = HEADER_DT.itemsize
    hdr = np.frombuffer(await reader.readexactly(hsz), HEADER_DT, 1)[0]
    total = int(hdr["total_sz"])
    pad = int(hdr["padding_sz"]) & 0xFF
    if int(hdr["magic"]) not in MAGICS or total < hsz \
            or total >= MAX_COMM_DATA_SZ or pad > total - hsz:
        raise ProtoError(f"bad frame header {hdr}")
    body = await reader.readexactly(total - hsz)
    return int(hdr["data_type"]), body[:len(body) - pad]


# ------------------------------------------------------------- the clients
async def register(host: str, port: int, machine_id: int, conn_type: int):
    """Open and register one connection → (reader, writer, host_id)."""
    reader, writer = await asyncio.open_connection(host, port)
    try:
        r = np.zeros((), REGISTER_REQ_DT)
        r["machine_id_hi"] = np.uint64((machine_id >> 64)
                                       & 0xFFFFFFFFFFFFFFFF)
        r["machine_id_lo"] = np.uint64(machine_id & 0xFFFFFFFFFFFFFFFF)
        r["wire_version"] = WIRE_VERSION
        r["conn_type"] = conn_type
        writer.write(_frame(COMM_REGISTER_REQ, r.tobytes(), MAGIC_PM))
        await writer.drain()
        dtype, payload = await read_frame(reader)
        if dtype != COMM_REGISTER_RESP:
            raise ProtoError(f"expected REGISTER_RESP, got {dtype}")
        resp = np.frombuffer(payload, REGISTER_RESP_DT, count=1)[0]
        if int(resp["status"]) != REG_OK:
            raise ProtoError(f"registration refused: status "
                             f"{int(resp['status'])}")
    except BaseException:
        writer.close()
        raise
    return reader, writer, int(resp["host_id"])


class QueryClient:
    """One query connection: a JSON request, its whole (possibly
    chunked) JSON answer. One request in flight at a time."""

    def __init__(self, machine_id: int = 0x51C0FFEE):
        self.machine_id = machine_id
        self._reader = self._writer = None
        self._seq = 0

    async def connect(self, host: str, port: int) -> None:
        self._reader, self._writer, _ = await register(
            host, port, self.machine_id, CONN_QUERY)

    async def query(self, req: dict, timeout: float = 60.0) -> dict:
        return await asyncio.wait_for(self._query(req), timeout)

    async def _query(self, req: dict) -> dict:
        self._seq += 1
        body = json.dumps(req).encode()
        h = np.zeros((), QUERY_HDR_DT)
        h["seqid"] = np.uint64(self._seq)
        h["nbytes"] = len(body)
        self._writer.write(_frame(COMM_QUERY_CMD, h.tobytes() + body,
                                  MAGIC_NQ))
        await self._writer.drain()
        chunks = []
        while True:
            dtype, payload = await read_frame(self._reader)
            if dtype != COMM_QUERY_RESP:
                raise ProtoError(f"expected QUERY_RESP, got {dtype}")
            qh = np.frombuffer(payload, QUERY_HDR_DT, count=1)[0]
            if int(qh["seqid"]) != self._seq:
                raise ProtoError(f"seqid {int(qh['seqid'])} != {self._seq}")
            n = int(qh["nbytes"])
            chunks.append(payload[QUERY_HDR_DT.itemsize:
                                  QUERY_HDR_DT.itemsize + n])
            if int(qh["status"]) != QS_PARTIAL:
                break
        obj = json.loads(b"".join(chunks) or b"null")
        if int(qh["status"]) != QS_OK:
            raise ProtoError(str((obj or {}).get("error", obj)))
        return obj

    def close(self) -> None:
        if self._writer is not None:
            self._writer.close()
            self._writer = None


# ------------------------------------------------- identities (hashes)
_C1, _C2, _GOLDEN = 0x85EBCA6B, 0xC2B2AE35, 0x9E3779B9


def fmix32(h):
    h = np.asarray(h, dtype=np.uint32)
    with np.errstate(over="ignore"):
        h = h ^ (h >> np.uint32(16))
        h = h * np.uint32(_C1)
        h = h ^ (h >> np.uint32(13))
        h = h * np.uint32(_C2)
        h = h ^ (h >> np.uint32(16))
    return h


def mix64(hi, lo, salt: int = 0):
    hi = np.asarray(hi, dtype=np.uint32)
    lo = np.asarray(lo, dtype=np.uint32)
    with np.errstate(over="ignore"):
        s = np.uint32((salt + 1) & 0xFFFFFFFF) * np.uint32(_GOLDEN)
        h = fmix32(lo ^ s)
        h = fmix32(hi ^ h ^ np.uint32(salt & 0xFFFFFFFF))
    return h


def fold_ip(ip_bytes: np.ndarray):
    """(N,16) uint8 → two uint32 words (xor-fold halves)."""
    w = ip_bytes.reshape(-1, 4, 4).copy().view("<u4").reshape(-1, 4)
    return (w[:, 0] ^ w[:, 2]).astype(np.uint32), \
        (w[:, 1] ^ w[:, 3]).astype(np.uint32)


def flow_id(conn: np.ndarray) -> np.ndarray:
    """The 64-bit flow key the server names a TCP_CONN record's flow by
    (``topk`` rows carry it as ``id``): the 5-tuple hash of
    ``utils/hashing.py:flow_key`` over the un-NATed addresses."""
    c_hi, c_lo = fold_ip(np.ascontiguousarray(conn["cli"]["ip"]))
    s_hi, s_lo = fold_ip(np.ascontiguousarray(conn["ser"]["ip"]))
    ports = (conn["cli"]["port"].astype(np.uint32) << np.uint32(16)) \
        | (conn["ser"]["port"].astype(np.uint32) & np.uint32(0xFFFF))
    a = mix64(c_hi, c_lo, 1)
    b = mix64(s_hi, s_lo, 2)
    with np.errstate(over="ignore"):
        lo = fmix32(a ^ (ports * np.uint32(_C1)))
        hi = fmix32(b ^ (np.uint32(6) * np.uint32(_C2)) ^ lo)
    return (hi.astype(np.uint64) << np.uint64(32)) | lo.astype(np.uint64)


def hash_name(data: bytes, salt: int = 0) -> int:
    """Interned-name id (``utils/hashing.py:hash_bytes_np``)."""
    h = np.uint32(0x811C9DC5 ^ (salt & 0xFFFFFFFF))
    g = np.uint32(0x01000193)
    with np.errstate(over="ignore"):
        pad = (-len(data)) % 4
        w = np.frombuffer(data + b"\x00" * pad, dtype=np.uint32)
        h1 = h
        h2 = h ^ np.uint32(_GOLDEN)
        for word in w:
            h1 = (h1 ^ word) * g
            h2 = fmix32(h2 + word)
        h1 = fmix32(h1 ^ np.uint32(len(data)))
        h2 = fmix32(h2 ^ h1)
    return (int(h2) << 32) | int(h1)


def name_records(entries) -> np.ndarray:
    """[(kind, name_id, name)] → NAME_INTERN record array."""
    out = np.zeros(len(entries), NAME_INTERN_DT)
    for i, (kind, name_id, name) in enumerate(entries):
        raw = name.encode("utf-8")[:MAX_NAME_BYTES]
        out[i]["name_id"] = np.uint64(name_id)
        out[i]["kind"] = kind
        out[i]["nlen"] = len(raw)
        out[i]["name"][:len(raw)] = np.frombuffer(raw, np.uint8)
    return out


def splitmix64(x) -> np.ndarray:
    x = np.asarray(x, np.uint64)
    with np.errstate(over="ignore"):
        x = x + np.uint64(0x9E3779B97F4A7C15)
        z = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        z = z ^ (z >> np.uint64(31))
    return z


def hexid(ids) -> list:
    return [format(int(x), "016x") for x in np.asarray(ids).reshape(-1)]
