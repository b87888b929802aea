"""The seeded fleet: the one general traffic generator.

Copied from ``gyeeta_tpu/sim/partha.py`` (record content: Zipf client
keys, lognormal per-service latency, Poisson gauges) and from
``chip_smoke.py:Fleet`` (one sim per socket, the record of everything
built). It reads its parameters from a configuration's ``fleet`` group and
a workload file and imports nothing of the program.

What it builds from ``--seed``, all in set-up:

- an inventory per socket (names, LISTENER_INFO, HOST_INFO),
- a POOL of distinct rounds: per socket and pool slot one buffer of
  RESP_SAMPLE + TCP_CONN frames of fixed size, replayed cyclically by the
  sender (slot ``pool`` is the CHECK round, sent once, alone in a tick),
- a pool of LISTENER_STATE + HOST_STATE sweeps per socket,
- and, beside every buffer, the columns the recount needs.

Every seed gives the same sizes and arrivals; only the content differs.
"""

from __future__ import annotations

import numpy as np

from . import proto as P

PROBE_HOST = 0             # the probe services live on socket 0's host 0
PROBE_NTASKS = 7777        # the gauge the freshness poller filters on


class Sim:
    """One socket's hosts (``sim/partha.py:ParthaSim``, the streams the
    cells use)."""

    def __init__(self, n_hosts, n_svcs, n_clients, cli_groups, zipf_a,
                 host_base, rng):
        self.n_hosts, self.n_svcs, self.n_clients = n_hosts, n_svcs, \
            n_clients
        self.cli_groups, self.zipf_a, self.host_base = cli_groups, zipf_a, \
            host_base
        self.rng = rng
        hs = np.arange(host_base, host_base + n_hosts,
                       dtype=np.uint64)[:, None]
        sv = np.arange(n_svcs, dtype=np.uint64)[None, :]
        self.glob_ids = P.splitmix64((hs << np.uint64(32))
                                     | (sv + np.uint64(1)))
        self.svc_latency_us = np.tile(
            np.geomspace(200.0, 50_000.0, n_svcs), (n_hosts, 1))
        self.cli_ips = rng.integers(0x0A000000, 0x0AFFFFFF,
                                    size=(n_clients,), dtype=np.uint32)
        self.tusec = np.uint64(1_700_000_000_000_000)
        self.n_groups = 8
        self.comm_ids = np.array(
            [P.hash_name(f"proc-{g}".encode(), P.NAME_KIND_COMM)
             for g in range(self.n_groups)], np.uint64)

    def resp_records(self, n: int) -> np.ndarray:
        r = self.rng
        host = r.integers(0, self.n_hosts, n)
        svc = r.integers(0, self.n_svcs, n)
        lat = r.lognormal(mean=0.0, sigma=0.7, size=n) \
            * self.svc_latency_us[host, svc]
        out = np.zeros(n, P.RESP_SAMPLE_DT)
        out["glob_id"] = self.glob_ids[host, svc]
        out["resp_usec"] = np.minimum(lat, 4e9).astype(np.uint32)
        out["host_id"] = (host + self.host_base).astype(np.uint32)
        return out

    def conn_records(self, n: int) -> np.ndarray:
        r = self.rng
        host = r.integers(0, self.n_hosts, n)
        svc = r.integers(0, self.n_svcs, n)
        rank = r.zipf(self.zipf_a, n)
        cli = (rank - 1) % self.n_clients
        sport = (20000 + (rank % 20000)).astype(np.uint16)
        out = np.zeros(n, P.TCP_CONN_DT)
        _put_ipv4(out["cli"], self.cli_ips[cli], sport)
        ser_ip = (0xC0A80000
                  | ((host.astype(np.uint32) + self.host_base) & 0xFFFF))
        _put_ipv4(out["ser"], ser_ip.astype(np.uint32),
                  (8000 + svc).astype(np.uint16))
        dur = (r.lognormal(1.0, 1.0, n) * 50_000).astype(np.uint64)
        out["tusec_start"] = self.tusec
        out["tusec_close"] = self.tusec + dur
        grp = (rank - 1) % self.cli_groups
        out["cli_task_aggr_id"] = P.splitmix64(
            (host.astype(np.uint64) * np.uint64(131071)
             + svc.astype(np.uint64)) * np.uint64(64)
            + grp.astype(np.uint64) + np.uint64(0xABCD))
        out["ser_glob_id"] = self.glob_ids[host, svc]
        out["ser_related_listen_id"] = out["ser_glob_id"]
        nbytes = (r.pareto(1.5, n) + 1.0) * 2000.0
        out["bytes_sent"] = np.minimum(nbytes, 2**40).astype(np.uint64)
        out["bytes_rcvd"] = np.minimum(nbytes * 9.0, 2**40).astype(np.uint64)
        out["cli_pid"] = cli.astype(np.int32) + 1000
        out["ser_pid"] = svc.astype(np.int32) + 300
        out["host_id"] = (host + self.host_base).astype(np.uint32)
        out["flags"] = 2          # accept-observed: the service host's own
        self.tusec += np.uint64(5_000_000)
        return out

    def listener_state_records(self) -> np.ndarray:
        r = self.rng
        n = self.n_hosts * self.n_svcs
        host = np.repeat(np.arange(self.n_hosts, dtype=np.uint32),
                         self.n_svcs)
        lat = self.svc_latency_us.reshape(-1)
        out = np.zeros(n, P.LISTENER_STATE_DT)
        out["glob_id"] = self.glob_ids.reshape(-1)
        # svcstate.nqry5s is the larger of this gauge and the response
        # samples the server folded in the window; the fleet reports
        # none of its own, so the column is the server's count
        out["nqrys_5s"] = 0
        out["total_resp_5sec"] = (r.poisson(200, n) * lat / 1000.0
                                  ).astype(np.uint32)
        out["nconns"] = r.poisson(50, n)
        out["nconns_active"] = np.minimum(out["nconns"], r.poisson(20, n))
        out["ntasks"] = 1 + r.integers(0, 4, n)
        out["p95_5s_resp_ms"] = (lat * 2.5 / 1000.0).astype(np.uint32)
        out["curr_kbytes_inbound"] = r.poisson(500, n)
        out["curr_kbytes_outbound"] = r.poisson(4000, n)
        out["ser_errors"] = (r.random(n) < 0.02) * r.poisson(3, n)
        out["tasks_delay_usec"] = r.poisson(100, n)
        out["host_id"] = host + self.host_base
        return out

    def host_state_records(self) -> np.ndarray:
        r = self.rng
        n = self.n_hosts
        out = np.zeros(n, P.HOST_STATE_DT)
        out["curr_time_usec"] = self.tusec
        out["ntasks"] = 100 + r.integers(0, 50, n)
        out["ntasks_issue"] = (r.random(n) < 0.1) * r.integers(1, 5, n)
        out["nlisten"] = self.n_svcs
        out["nlisten_issue"] = (r.random(n) < 0.1) * r.integers(1, 3, n)
        out["cpu_issue"] = r.random(n) < 0.05
        out["mem_issue"] = r.random(n) < 0.03
        out["host_id"] = np.arange(n, dtype=np.uint32) + self.host_base
        return out

    def listener_info_records(self) -> np.ndarray:
        n = self.n_hosts * self.n_svcs
        host = np.repeat(np.arange(self.n_hosts, dtype=np.uint32),
                         self.n_svcs)
        svc = np.tile(np.arange(self.n_svcs, dtype=np.uint32),
                      self.n_hosts)
        out = np.zeros(n, P.LISTENER_INFO_DT)
        out["glob_id"] = self.glob_ids.reshape(-1)
        ser_ip = (0xC0A80000
                  | ((host + np.uint32(self.host_base)) & 0xFFFF))
        _put_ipv4(out["addr"], ser_ip, (8000 + svc).astype(np.uint16))
        out["tusec_start"] = self.tusec - np.uint64(3_600_000_000)
        out["comm_id"] = self.comm_ids[svc % self.n_groups]
        out["cmdline_id"] = self.comm_ids[svc % self.n_groups]
        out["related_listen_id"] = out["glob_id"]
        out["pid"] = (300 + svc).astype(np.int32)
        out["is_any_ip"] = 1
        out["is_http"] = (svc % 2 == 0)
        out["host_id"] = host + self.host_base
        return out

    def host_info_records(self) -> np.ndarray:
        n = self.n_hosts
        hs = np.arange(n) + self.host_base
        mid = lambda s: P.hash_name(s.encode(),       # noqa: E731
                                    P.NAME_KIND_MISC)
        out = np.zeros(n, P.HOST_INFO_DT)
        out["host_id"] = hs
        out["ncpus"] = 8 << (hs % 3)
        out["nnuma"] = 1 + (hs % 2)
        out["ram_mb"] = 32768 << (hs % 3)
        out["swap_mb"] = 2048
        out["boot_tusec"] = self.tusec - np.uint64(86_400_000_000)
        out["kern_ver_id"] = mid("6.1.0-18-amd64")
        out["distro_id"] = mid("Debian 12")
        out["cputype_id"] = mid("EPYC-9B14")
        out["instance_id"] = [mid(f"i-{h:016x}") for h in hs]
        out["region_id"] = mid("us-east1")
        out["zone_id"] = mid("us-east1-a")
        out["virt_type"] = 1
        out["cloud_type"] = 1 + (hs % 3)
        out["is_k8s"] = (hs % 4) == 0
        return out

    def name_records(self) -> np.ndarray:
        entries = [(P.NAME_KIND_COMM, self.comm_ids[g], f"proc-{g}")
                   for g in range(self.n_groups)]
        for h in range(self.n_hosts):
            for s in range(self.n_svcs):
                entries.append((P.NAME_KIND_SVC, self.glob_ids[h, s],
                                f"svc-{s}.host-{h + self.host_base}"))
            entries.append((P.NAME_KIND_HOST, h + self.host_base,
                            f"host-{h + self.host_base}.sim"))
        misc = ["6.1.0-18-amd64", "Debian 12", "EPYC-9B14", "us-east1",
                "us-east1-a"]
        misc += [f"i-{h + self.host_base:016x}"
                 for h in range(self.n_hosts)]
        entries += [(P.NAME_KIND_MISC,
                     P.hash_name(s.encode(), P.NAME_KIND_MISC), s)
                    for s in misc]
        return P.name_records(entries)


def _put_ipv4(view: np.ndarray, ipv4: np.ndarray, port: np.ndarray) -> None:
    ip = view["ip"]
    ip[:, 10] = 0xFF
    ip[:, 11] = 0xFF
    ip[:, 12] = (ipv4 >> 24).astype(np.uint8)
    ip[:, 13] = ((ipv4 >> 16) & 0xFF).astype(np.uint8)
    ip[:, 14] = ((ipv4 >> 8) & 0xFF).astype(np.uint8)
    ip[:, 15] = (ipv4 & 0xFF).astype(np.uint8)
    view["port"] = port


class Fleet:
    """Everything a run sends, and the record of it for the recount.

    ``bufs[k]`` for socket ``k``: ``inventory`` (bytes), ``rounds``
    (``pool + 1`` buffers; the last is the check round), ``sweeps``
    (``sweep_pool + 1`` buffers; the last is the check sweep)."""

    def __init__(self, fleet: dict, workload: dict, seed: int,
                 n_probes: int, engine: dict):
        self.n_sockets = int(fleet["sockets"])
        self.n_hosts = int(fleet["hosts"])
        self.svcs_per_host = int(fleet["svcs_per_host"])
        per = self.n_hosts // self.n_sockets
        if per * self.n_sockets != self.n_hosts:
            raise ValueError("hosts must divide over the sockets")
        self.pool = int(workload["pool_rounds"])
        self.sweep_pool = int(workload["sweep_pool"])
        self.conn_per = int(workload["round"]["conn_per_socket"])
        self.resp_per = int(workload["round"]["resp_per_socket"])
        self.sims = [Sim(per, self.svcs_per_host, int(fleet["clients"]),
                         int(fleet["cli_groups_per_svc"]),
                         float(fleet["zipf_a"]), k * per,
                         np.random.default_rng([int(seed), k]))
                     for k in range(self.n_sockets)]
        self.n_svcs = self.n_hosts * self.svcs_per_host
        self.all_svc = np.concatenate(
            [s.glob_ids.reshape(-1) for s in self.sims])
        # the freshness probes: reserved services that get no conn or resp
        # traffic; marker ``seq`` is a LISTENER_STATE of probe ``seq % n``
        # whose nconns gauge is ``seq``. More probes than markers between
        # two folds, so no fold sees one service twice (a scatter's winner
        # among duplicates is not defined).
        self.probe_ids = P.splitmix64(
            np.uint64(0xFEED00000000) + np.uint64(int(seed) % 65521)
            * np.uint64(4096) + np.arange(n_probes, dtype=np.uint64))
        self.bufs = [self._build_socket(k) for k in range(self.n_sockets)]
        self._build_warm(engine)

    def _build_warm(self, engine: dict) -> None:
        """Socket 0's warm-up pieces: the fused fold is one compiled
        program per combination of sections a feed happens to hold
        (listener / host sweep frames, with or without a full conn/resp
        slab), so set-up drives every combination once, on purpose:
        ``bulk`` fills the slab to a few lanes short, ``tail`` crosses
        it, ``host`` / ``lst`` are sweep frames small enough to arrive
        with the tail in one read."""
        sim, b = self.sims[0], self.bufs[0]
        nc = int(engine["fold_k"]) * int(engine["conn_batch"])
        nr = int(engine["fold_k"]) * int(engine["resp_batch"])
        conn, resp = sim.conn_records(nc), sim.resp_records(nr)
        frames = lambda c, r: (                              # noqa: E731
            P.encode_frames(P.NOTIFY_RESP_SAMPLE, r)
            + P.encode_frames(P.NOTIFY_TCP_CONN, c))
        lst = b["listener"][0][:8]
        self.warm = {
            "bulk": frames(conn[:-8], resp[:-16]),
            "tail": frames(conn[-8:], resp[-16:]),
            "host": P.encode_frames(P.NOTIFY_HOST_STATE, b["host"][0]),
            "lst": P.encode_frames(P.NOTIFY_LISTENER_STATE, lst)}
        self.warm_n = {"host": len(b["host"][0]), "lst": len(lst)}
        self.warm_cols = {
            "bulk": (self._conn_cols(conn[:-8]), self._resp_cols(resp[:-16])),
            "tail": (self._conn_cols(conn[-8:]), self._resp_cols(resp[-16:]))}

    @staticmethod
    def _conn_cols(conn) -> dict:
        cli_ip = np.ascontiguousarray(
            conn["cli"]["ip"][:, 12:16]).view(">u4").reshape(-1)
        return {"svc": conn["ser_glob_id"].copy(),
                "cli_task": conn["cli_task_aggr_id"].copy(),
                "flow": P.flow_id(conn),
                "cli_ip": cli_ip.astype(np.uint32),
                "bytes": conn["bytes_sent"].astype(np.float32)
                .astype(np.float64)
                + conn["bytes_rcvd"].astype(np.float32).astype(np.float64)}

    @staticmethod
    def _resp_cols(resp) -> dict:
        return {"svc": resp["glob_id"].copy(),
                "usec": resp["resp_usec"].astype(np.float32)}

    def _build_socket(self, k: int) -> dict:
        sim = self.sims[k]
        linfo = sim.listener_info_records()
        names = sim.name_records()
        if len(self.probe_ids) and k == 0:
            probe = np.repeat(linfo[:1], len(self.probe_ids))
            probe["glob_id"] = probe["related_listen_id"] = self.probe_ids
            linfo = np.concatenate([linfo, probe])
            names = np.concatenate([names, P.name_records(
                [(P.NAME_KIND_SVC, i, f"bench-probe-{j}")
                 for j, i in enumerate(self.probe_ids)])])
        hinfo = sim.host_info_records()
        out = {"inventory": (
            P.encode_frames(P.NOTIFY_NAME_INTERN, names)
            + P.encode_frames(P.NOTIFY_LISTENER_INFO, linfo)
            + P.encode_frames(P.NOTIFY_HOST_INFO, hinfo)),
            "n_linfo": len(linfo), "n_hinfo": len(hinfo),
            "rounds": [], "sweeps": [], "conn": [], "resp": [],
            "listener": [], "host": []}
        for _ in range(self.pool + 1):
            resp = sim.resp_records(self.resp_per)
            conn = sim.conn_records(self.conn_per)
            out["rounds"].append(
                P.encode_frames(P.NOTIFY_RESP_SAMPLE, resp)
                + P.encode_frames(P.NOTIFY_TCP_CONN, conn))
            out["conn"].append(self._conn_cols(conn))
            out["resp"].append(self._resp_cols(resp))
        for _ in range(self.sweep_pool + 1):
            hst = sim.host_state_records()
            lst = sim.listener_state_records()
            out["sweeps"].append(
                P.encode_frames(P.NOTIFY_HOST_STATE, hst)
                + P.encode_frames(P.NOTIFY_LISTENER_STATE, lst))
            out["listener"].append(lst)
            out["host"].append(hst)
        return out

    def wire(self) -> list:
        """What the sender process needs: bytes only."""
        out = [{"inventory": b["inventory"], "rounds": b["rounds"],
                "sweeps": b["sweeps"]} for b in self.bufs]
        out[0]["warm"] = self.warm
        return out


def probe_frame(probe_ids, seq: int) -> bytes:
    """The freshness marker: a one-listener LISTENER_STATE whose
    ``nconns`` gauge is ``seq``."""
    rec = np.zeros(1, P.LISTENER_STATE_DT)
    rec["glob_id"] = probe_ids[seq % len(probe_ids)]
    rec["nconns"] = seq
    rec["ntasks"] = PROBE_NTASKS
    rec["host_id"] = PROBE_HOST
    return P.encode_frame(P.NOTIFY_LISTENER_STATE, rec)
