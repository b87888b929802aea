"""Faults planted under the timed path: ``benchmarks/tests`` on the CPU
at a tiny size, and a fault's reading on the chip at a cell's own size
(``run.py --fault``; never part of a benchmark run).

Each breaks one guarantee the configurations state, inside the serving
process, so that the comparison can be seen to come out false:

- ``drop_half``: while armed (the measured window), half of every batch of
  conn records is left out before staging — "accepted == built".
- ``drop_resp_batch``: once while armed, one microbatch (``resp_batch``
  samples) of response samples is taken out AFTER it was accepted and
  counted and BEFORE it is staged for the fold — "every accepted record
  folded". Only the device's own fold counter can see it.
- ``alter_answer``: while armed (from the window on), an answer altered
  where it is produced — one ``nconns`` of every ``svcstate`` answer is
  raised by one.

"""

from __future__ import annotations

ARMED = False        # the parent arms a fault for the measured window only


def plant(name: str) -> None:
    if name == "drop_half":
        from gyeeta_tpu import runtime
        from gyeeta_tpu.ingest import wire
        orig = runtime.Runtime.ingest_records

        def ingest_records(self, recs):
            conn = recs.get(wire.NOTIFY_TCP_CONN)
            if ARMED and conn is not None and len(conn) > 1:
                recs[wire.NOTIFY_TCP_CONN] = conn[: len(conn) // 2]
            return orig(self, recs)

        runtime.Runtime.ingest_records = ingest_records
    elif name == "drop_resp_batch":
        from gyeeta_tpu import runtime
        orig = runtime.Runtime.ingest_records
        done = []

        def ingest_records(self, recs):
            n = orig(self, recs)
            want = int(self.cfg.resp_batch)
            if ARMED and not done and self._n_resp_raw > want:
                left = want
                while left:
                    last = self._resp_raw.pop()
                    if len(last) > left:
                        self._resp_raw.append(last[:-left])
                    left -= min(left, len(last))
                self._n_resp_raw -= want
                done.append(want)
            return n

        runtime.Runtime.ingest_records = ingest_records
    elif name == "alter_answer":
        from gyeeta_tpu import runtime
        orig_q = runtime.Runtime.query

        def query(self, req, *a, **kw):
            out = orig_q(self, req, *a, **kw)
            if ARMED and req.get("subsys") == "svcstate" \
                    and out.get("recs"):
                r = out["recs"][0]
                if "nconns" in r:
                    r["nconns"] = r["nconns"] + 1
            return out

        runtime.Runtime.query = query
    else:
        raise SystemExit(f"unknown fault {name!r}")
