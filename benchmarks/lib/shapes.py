"""Bytes and operations the fold NEEDS for one dispatch, from shapes only.

What the algorithm must move whatever implements it (a scatter today, a
sorted merge or a kernel tomorrow): every input lane once, plus one read
and one write of each state element a lane touches. Nothing here depends
on how ``engine/step.py:fold_all`` is written, so the count stays valid
when the implementation changes.

Per TCP_CONN lane (columns of ``ingest/decode.py:ConnBatch``):
  input    13 four-byte columns + 3 one-byte flags            55 B
  service  one slab key probe (hi, lo)                          8 B
           4 counters (bytes sent, rcvd, conns, duration) r+w  32 B
  HLL      one per-service register r+w, one global r+w         4 B
  CMS      ``cms_depth`` float counters r+w                 8 B x depth
  dep      one edge key probe (4 words) + 2 counters r+w       32 B
Per RESP_SAMPLE lane (``RespBatch``):
  input    4 four-byte columns + 1 flag                        17 B
  service  one slab key probe                                   8 B
  loghist  one bucket of the current window r+w                 8 B
Operations: hashes, compares and adds — about 60 integer/float operations
a conn lane and 20 a resp lane; the fold is nowhere near the compute roof.
"""

from __future__ import annotations

CONN_OPS, RESP_OPS = 60, 20


def conn_lane_bytes(engine: dict) -> int:
    return 55 + 8 + 32 + 4 + 8 * int(engine["cms_depth"]) + 32


def resp_lane_bytes(_engine: dict) -> int:
    return 17 + 8 + 8


def fold_needs(engine: dict, conn_lanes: float, resp_lanes: float) -> dict:
    """→ bytes and operations for one dispatch of that many valid lanes."""
    return {"bytes": conn_lanes * conn_lane_bytes(engine)
            + resp_lanes * resp_lane_bytes(engine),
            "ops": conn_lanes * CONN_OPS + resp_lanes * RESP_OPS}


def least_seconds(needs: dict, peak: dict) -> tuple:
    """→ (least time on the chip, which roof bounds it)."""
    t_mem = needs["bytes"] / (peak["hbm_gb_per_s"] * 1e9)
    t_ops = needs["ops"] / (peak["bf16_tflop_per_s"] * 1e12)
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")
