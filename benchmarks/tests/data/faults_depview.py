"""A fault for ``test_rehearse_depview.py`` alone, laid over
``lib/faults.py`` in that test's throw-away copy of the benchmark (the
child imports ``lib.faults`` and arms it for the window, never disarms):

- ``alter_dep_answer``: while armed, the last edge of every
  ``svcdependency`` answer says one connection more than the first - an
  edge's ``nconn`` altered where the answer is produced, so a sorted
  top-100 is out of order and carries a count no recount gives.
"""

from __future__ import annotations

ARMED = False


def plant(name: str) -> None:
    if name != "alter_dep_answer":
        raise SystemExit(f"unknown fault {name!r}")
    from gyeeta_tpu import runtime
    orig_q = runtime.Runtime.query

    def query(self, req, *a, **kw):
        out = orig_q(self, req, *a, **kw)
        recs = out.get("recs") or []
        if ARMED and req.get("subsys") == "svcdependency" \
                and len(recs) > 1 and "nconn" in recs[-1]:
            # a copy: the answer itself stays whole in the result cache
            recs = recs[:-1] + [{**recs[-1],
                                 "nconn": recs[0]["nconn"] + 1}]
            out = {**out, "recs": recs}
        return out

    runtime.Runtime.query = query
