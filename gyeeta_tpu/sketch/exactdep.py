"""Plain reference of the service-dependency view (``svcdependency``).

A dict of ``(caller id, service id) → [nconn, bytes, caller-is-service]``
filled by replaying TCP_CONN records one by one in plain Python, counts
and bytes as Python ints (exact; the device sums bytes in float32). It
states the view's SEMANTICS — what an edge is, who the caller is, when two
half records make one flow — and shares no code with
``parallel/depgraph.py`` or ``query/api.py``, so the tests can hold one
against the other (``tests/test_depview.py``), the way ``exact.py`` backs
the sketches.

The semantics (ref ``DEPENDS_LISTENER``, ``common/gy_socket_stat.h:721``;
half pairing, ``server/gy_shconnhdlr.cc:3790``):

- the caller of a flow is its client's related listener when it has one
  (a service calling a service: a mesh edge), else the client's process
  group; the callee is the server's listener (``ser_glob_id``);
- a record that knows both ends is one flow of that edge; its bytes are
  ``bytes_sent + bytes_rcvd``;
- a record that knows one end waits for the record of the same 5-tuple
  (the post-NAT tuple where conntrack resolved one) that knows the other;
  the pair is one flow, its bytes the larger of the two reports;
- a record that knows neither end says nothing about dependencies.
"""

from __future__ import annotations

import numpy as np


def _tuple_key(r) -> bytes:
    """The 5-tuple both observers of one flow share."""
    cli = r["nat_cli"] if r["nat_cli"]["ip"].any() else r["cli"]
    ser = r["nat_ser"] if r["nat_ser"]["ip"].any() else r["ser"]
    return cli.tobytes() + ser.tobytes()


class DepViewRef:
    def __init__(self):
        self.edges: dict = {}     # (cli, ser) → [nconn, bytes, clisvc]
        self._waiting: dict = {}  # 5-tuple → the half seen so far

    def _flow(self, cli: int, clisvc: bool, ser: int, nbytes: int) -> None:
        e = self.edges.setdefault((cli, ser), [0, 0, clisvc])
        e[0] += 1
        e[1] += nbytes

    def add(self, recs: np.ndarray) -> None:
        """Replay one batch of TCP_CONN records, in order."""
        for r in recs:
            rel = int(r["cli_related_listen_id"])
            cli = rel or int(r["cli_task_aggr_id"])
            ser = int(r["ser_glob_id"])
            nbytes = int(r["bytes_sent"]) + int(r["bytes_rcvd"])
            if cli and ser:
                self._flow(cli, bool(rel), ser, nbytes)
            elif cli or ser:
                key = _tuple_key(r)
                other = self._waiting.pop(key, None)
                if other is None or bool(other[0]) == bool(cli):
                    # the first half, or the same side again (the
                    # newest report of a side stands)
                    self._waiting[key] = (cli, bool(rel), ser, max(
                        nbytes, other[3] if other else 0))
                else:
                    c = cli or other[0]
                    self._flow(c, bool(rel) if cli else other[1],
                               ser or other[2], max(nbytes, other[3]))

    # ------------------------------------------------------------ reads
    def rows(self) -> list:
        """Every edge as the view's numeric row."""
        return [{"cliid": format(c, "016x"), "serid": format(s, "016x"),
                 "clisvc": svc, "nconn": n, "bytes": b}
                for (c, s), (n, b, svc) in self.edges.items()]

    def top(self, col: str, k: int = 100) -> list:
        """The ``k`` largest values of ``col``, descending (what a sorted
        top-k must carry, whatever it does with ties)."""
        return sorted((r[col] for r in self.rows()), reverse=True)[:k]

    def by_service(self) -> dict:
        """serid → {nconn, bytes, ncallers} (``groupby serid``)."""
        out: dict = {}
        for r in self.rows():
            g = out.setdefault(r["serid"],
                               {"nconn": 0, "bytes": 0, "ncallers": 0})
            g["nconn"] += r["nconn"]
            g["bytes"] += r["bytes"]
            g["ncallers"] += 1
        return out
