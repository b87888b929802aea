#!/usr/bin/env python3
"""chip_smoke.py — the served path on the chip, once, at fleet geometry.

The quickest proof that the system still starts, compiles, fits, folds,
ticks and answers correctly on a TPU. It drives the NORMAL entry points:

- this process imports no jax (one process owns a chip, and it is not
  this one): it starts ``python -m gyeeta_tpu serve --config <file it
  writes>`` as the one chip-owning child,
- feeds it over TCP with the jax-free sim agents (``sim/partha.py``,
  ``net/agent.py``) in the batched-conn shape of a relay tier: 32
  sockets, each carrying 16 hosts × 128 services,
- asks through ``python -m gyeeta_tpu query`` and the ``QueryClient``,
- compares every answer with a plain numpy recount of the same seeded
  records: exact columns must be equal, sketch columns must stay inside
  the bounds the sketches document and the accuracy tests use.

Size is fixed here, not chosen at run time: the ``fleet-50k`` geometry
(``BASELINE.json``, ``ROADMAP.md`` R1) — see ``SVC_CAPACITY`` below for
the one cut the 16 GB chip forced. The traffic: one cold window, then 5 s tick
windows of 16 full fused dispatches each (16×2048 conn + 16×4096 resp
lanes) plus the 5 s listener/host sweeps, started on a tick boundary so
that a window's events land in one window; snapshot publication on (the
default query path), WAL/history/compaction off.

    python chip_smoke.py                 one chip (what the driver runs)
    python chip_smoke.py --chips 4       serve --shards 4, same stream,
                                         same total geometry, and nothing
                                         else (run it on the 4-chip host)
    python chip_smoke.py --rehearse-cpu  tiny size on the CPU backend
                                         (with or without --chips 4);
                                         cannot report a tpu

Exit 0 and a last stdout line
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
only when every phase passed on an accelerator (or, under
``--rehearse-cpu``, on the CPU). No chip, a CPU device, a failed check,
an exception, a server that exits: non-zero and no such line.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import math
import os
import shutil
import signal
import socket
import subprocess
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 21
TICK_S = 5.0
ROUNDS = 16             # fused dispatches per window: one per round
WINDOWS = 3             # clean 5 s windows wanted after the cold one
WINDOW_TRIES = 8        # a window cut by a tick is tried again
N_SOCKETS = 32

# fleet-50k (BASELINE.json, ROADMAP R1): a 131,072-row service slab at
# ~50 % load, 50,048 hosts, 65,536 process groups, 65,536 / 524,288
# dependency pairs / edges, default sketch widths (256-bucket loghist,
# per-service HLL p=10): 5.97 GiB of state + 0.02 GiB of dep graph.
#
# THE CUT (PR 21, one halving, forced by the chip): on ONE 16 GB chip the
# service slab is 65,536 rows and the fleet 512 hosts × 64 services.
# Publishing a snapshot copies (state, dep) while the snapshot published
# before it is still referenced, so three copies are alive at once. At
# the published size the first tick of `serve` on a v5e failed in
# `publish_snapshot` with "RESOURCE_EXHAUSTED: Error allocating device
# buffer: Attempting to allocate 3.00G ... There are 1.50G free" at
# 11.99 GiB in use, peak 13.66 GiB of 15.75 GiB (my chip run, PR 21);
# halved, the run peaks at 12.3 GiB. Four chips hold the whole geometry.
# Everything else is as published.
SVC_CAPACITY = 65536
FLEET = {
    "engine": {"svc_capacity": SVC_CAPACITY, "n_hosts": 50048,
               "task_capacity": 65536},
    "runtime": {"dep_pair_capacity": 65536, "dep_edge_capacity": 524288},
    "hosts": 512, "svcs": SVC_CAPACITY // 2 // 512, "clients": 8192,
}
FLEET_4 = {         # four chips: the geometry whole, split four ways
    **FLEET,
    "engine": {**FLEET["engine"], "svc_capacity": 131072},
    "svcs": 128,
}
# the same control flow at a size the CPU backend folds in seconds
TINY = {
    "engine": {"svc_capacity": 1024, "n_hosts": 64, "task_capacity": 256,
               "conn_batch": 256, "resp_batch": 512, "fold_k": 4,
               "listener_batch": 64},
    "runtime": {"dep_pair_capacity": 1024, "dep_edge_capacity": 4096},
    "hosts": 32, "svcs": 16, "clients": 512,
}
ENGINE_DEFAULTS = {"conn_batch": 2048, "resp_batch": 4096, "fold_k": 16}
RESP_SPEC = (1.0, 1e8, 256)          # EngineCfg.resp_spec (usec)
HLL_P_SVC = 10                       # EngineCfg.hll_p_svc
CLI_GROUPS = 4                       # caller deployments per service


def log(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


class Failed(Exception):
    """A phase failed; the message says which and why."""


# ------------------------------------------------------------ geometry
def geometry(size: dict, chips: int) -> dict:
    """``size`` for ``chips`` shards: the slabs split over the shards,
    the host space and the edge capacity do not (every shard indexes
    hosts globally, and ``dep_edge_capacity`` is also the capacity the
    tick's roll-up merges every shard's edges into)."""
    eng = dict(size["engine"])
    run = dict(size["runtime"])
    if chips > 1:
        eng["svc_capacity"] //= chips
        eng["task_capacity"] //= chips
        run["dep_pair_capacity"] //= chips
    return {"engine": eng, "runtime": run}


# ------------------------------------------------------ traffic + recount
class Fleet:
    """The seeded fleet: one ``ParthaSim`` per socket, and the record of
    everything built — the recount's input."""

    def __init__(self, size: dict):
        from gyeeta_tpu.sim.partha import ParthaSim
        eng = {**ENGINE_DEFAULTS, **size["engine"]}
        per = size["hosts"] // N_SOCKETS
        assert per * N_SOCKETS == size["hosts"]
        self.sims = [ParthaSim(n_hosts=per, n_svcs=size["svcs"],
                               n_clients=size["clients"],
                               cli_groups_per_svc=CLI_GROUPS,
                               host_base=k * per, seed=SEED + k)
                     for k in range(N_SOCKETS)]
        self.n_hosts = size["hosts"]
        self.n_svcs = size["hosts"] * size["svcs"]
        lanes_c = eng["fold_k"] * eng["conn_batch"]
        lanes_r = eng["fold_k"] * eng["resp_batch"]
        assert lanes_c % N_SOCKETS == 0 and lanes_r % N_SOCKETS == 0
        self.conn_per = lanes_c // N_SOCKETS     # per socket per round
        self.resp_per = lanes_r // N_SOCKETS
        self.built = {"conn": 0, "resp": 0, "listener": 0, "host": 0,
                      "listener_info": 0, "host_info": 0}
        # the recount's columns, appended per round / per sweep
        self.conn_parts: list = []    # (svc, cli_task, flow, cli_ip, bytes)
        self.resp_parts: list = []    # (svc, resp_usec) per window
        self.resp_window: list = []   # window index of each resp part
        self.last_listener = None     # newest LISTENER_STATE sweep
        self.last_host = None         # newest HOST_STATE sweep
        self.all_svc = np.concatenate(
            [s.glob_ids.reshape(-1) for s in self.sims])

    def inventory(self, k: int) -> bytes:
        from gyeeta_tpu.ingest import wire
        sim = self.sims[k]
        linfo = sim.listener_info_records()
        hinfo = sim.host_info_records()
        self.built["listener_info"] += len(linfo)
        self.built["host_info"] += len(hinfo)
        return (sim.name_frames()
                + wire.encode_frames_chunked(wire.NOTIFY_LISTENER_INFO,
                                             linfo)
                + wire.encode_frames_chunked(wire.NOTIFY_HOST_INFO,
                                             hinfo))

    def sweep(self) -> list:
        """One 5 s LISTENER_STATE + HOST_STATE sweep of every host →
        per-socket frame bytes."""
        from gyeeta_tpu.ingest import wire
        out, lst_all, hst_all = [], [], []
        for sim in self.sims:
            hst = sim.host_state_records()
            lst = sim.listener_state_records()
            # svcstate.nqry5s is the larger of the agent's own query
            # count and the response samples the server folded in the
            # window; this fleet reports none of its own, so the column
            # is the server's count and can be recounted
            lst["nqrys_5s"] = 0
            lst_all.append(lst)
            hst_all.append(hst)
            out.append(
                wire.encode_frames_chunked(wire.NOTIFY_HOST_STATE, hst)
                + wire.encode_frames_chunked(wire.NOTIFY_LISTENER_STATE,
                                             lst))
        self.last_listener = np.concatenate(lst_all)
        self.last_host = np.concatenate(hst_all)
        self.built["listener"] += len(self.last_listener)
        self.built["host"] += len(self.last_host)
        return out

    def round(self, window: int):
        """One fused dispatch's worth of events over all sockets →
        (per-socket frame bytes, the closing bytes, records in them).

        The server dispatches at the end of the feed call in which the
        conn OR the resp lanes of a slab fill. So the last socket holds
        back a few records of each kind and sends them last, in one
        small write: both sides then fill in one feed call, and the
        dispatch is full on both — exactly one per round."""
        from gyeeta_tpu.ingest import decode, wire
        out = []
        tail_c = max(1, self.conn_per // 32)
        tail_r = 2 * tail_c
        tail = b""
        frames = lambda conn, resp: (                  # noqa: E731
            wire.encode_frames_chunked(wire.NOTIFY_RESP_SAMPLE, resp)
            + wire.encode_frames_chunked(wire.NOTIFY_TCP_CONN, conn))
        for k, sim in enumerate(self.sims):
            resp = sim.resp_records(self.resp_per)
            conn = sim.conn_records(self.conn_per)
            if k == len(self.sims) - 1:
                tail = frames(conn[-tail_c:], resp[-tail_r:])
                out.append(frames(conn[:-tail_c], resp[:-tail_r]))
            else:
                out.append(frames(conn, resp))
            # identities as the system names them (the numpy reference
            # decoder; the server runs the native one)
            cb = decode.conn_batch(conn, len(conn))
            flow = (cb.flow_hi.astype(np.uint64) << np.uint64(32)) \
                | cb.flow_lo.astype(np.uint64)
            cli_ip = np.ascontiguousarray(
                conn["cli"]["ip"][:, 12:16]).view(">u4").reshape(-1)
            self.conn_parts.append((
                conn["ser_glob_id"].copy(),
                conn["cli_task_aggr_id"].copy(), flow,
                cli_ip.astype(np.uint32),
                cb.bytes_sent.astype(np.float64)
                + cb.bytes_rcvd.astype(np.float64)))
            self.resp_parts.append((resp["glob_id"].copy(),
                                    resp["resp_usec"].astype(np.float32)))
            self.resp_window.append(window)
        self.built["conn"] += self.conn_per * len(self.sims)
        self.built["resp"] += self.resp_per * len(self.sims)
        return out, tail, tail_c, tail_r

    # -- recount ---------------------------------------------------------
    def conn_columns(self):
        cols = [np.concatenate(c) for c in zip(*self.conn_parts)]
        return dict(zip(("svc", "cli_task", "flow", "cli_ip", "bytes"),
                        cols))

    def resp_columns(self, window=None):
        parts = [p for p, w in zip(self.resp_parts, self.resp_window)
                 if window is None or w == window]
        svc = np.concatenate([p[0] for p in parts])
        val = np.concatenate([p[1] for p in parts])
        return svc, val


def hexid(ids: np.ndarray) -> list:
    return [format(int(x), "016x") for x in ids]


def group_sorted(keys: np.ndarray, vals: np.ndarray):
    """→ (unique keys, start offsets, vals sorted by (key, val))."""
    order = np.lexsort((vals, keys))
    k, v = keys[order], vals[order]
    uniq, start = np.unique(k, return_index=True)
    return uniq, start, v


def order_stat(uniq, start, v, q: float):
    """The ⌈q·n⌉-th smallest value per group — the rank a loghist
    quantile resolves (first bucket whose cumulative count reaches
    q·n) — and, where q·n sits on an integer within float32 reach, its
    lower neighbour too."""
    n = np.diff(np.append(start, len(v)))
    qn = q * n.astype(np.float64)
    hi = np.clip(np.ceil(qn - 1e-4).astype(np.int64), 1, n)
    lo = np.clip(np.ceil(qn + 1e-4).astype(np.int64), 1, n)
    return n, v[start + hi - 1], v[start + lo - 1]


# ------------------------------------------------------------------ server
class Server:
    def __init__(self, work: str, cfg: dict, chips: int, rehearse: bool):
        self.work = work
        self.port = _free_port()
        self.log_path = os.path.join(work, "server.log")
        cfg_path = os.path.join(work, "serve.json")
        with open(cfg_path, "w") as f:
            json.dump(cfg, f, indent=1)
        # size is fixed by the file this script wrote: GYT_<FIELD>
        # variables outrank a config file, so none reach the child
        env = {k: v for k, v in os.environ.items()
               if not k.startswith("GYT_")}
        env["PYTHONPATH"] = HERE + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH")
            else "")
        if rehearse:
            env["JAX_PLATFORMS"] = "cpu"
        cmd = [sys.executable, "-m", "gyeeta_tpu", "serve",
               "--config", cfg_path, "--host", "127.0.0.1",
               "--port", str(self.port),
               "--tick-interval", str(TICK_S),
               # a conn is silent while the server compiles; the smoke
               # is not a test of idle reaping
               "--idle-timeout", "0",
               "--stats-interval", "30", "--log-level", "INFO"]
        if chips > 1:
            cmd += ["--shards", str(chips)]
        self.t_spawn = time.monotonic()
        self._logf = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            cmd, cwd=HERE, env=env, stdout=self._logf,
            stderr=subprocess.STDOUT, start_new_session=True)

    def check_alive(self) -> None:
        rc = self.proc.poll()
        if rc is not None:
            raise Failed(f"the serving process exited (rc={rc})")

    async def wait_listening(self, deadline_s: float) -> None:
        t0 = time.monotonic()
        while time.monotonic() - t0 < deadline_s:
            self.check_alive()
            try:
                with socket.create_connection(("127.0.0.1", self.port),
                                              timeout=1.0):
                    return
            except OSError:
                await asyncio.sleep(0.25)
        raise Failed(f"server not listening after {deadline_s:.0f}s")

    def log_tail(self, nbytes: int = 6000) -> str:
        self._logf.flush()
        with open(self.log_path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - nbytes))
            return f.read().decode("utf-8", "replace")

    def stop(self) -> None:
        """SIGTERM (graceful drain), then SIGKILL the whole session —
        nothing this script started survives it."""
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGTERM)
                self.proc.wait(timeout=60)
            except (subprocess.TimeoutExpired, ProcessLookupError):
                pass
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        self.proc.wait(timeout=30)
        self._logf.close()


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ------------------------------------------------------------------ driver
class Smoke:
    def __init__(self, server: Server, fleet: Fleet, chips: int):
        self.srv = server
        self.fleet = fleet
        self.chips = chips
        self.qc = None
        self.conns: list = []
        self.failures: list = []
        self.last_stats: dict = {}
        self.edge_capacity = 0        # runtime.dep_edge_capacity

    def check(self, ok: bool, what: str) -> bool:
        if ok:
            log(f"ok    {what}")
        else:
            log(f"FAIL  {what}")
            self.failures.append(what)
        return bool(ok)

    async def connect(self) -> None:
        from gyeeta_tpu.ingest import wire
        from gyeeta_tpu.net.agent import QueryClient, register
        # a cold first answer waits on compiles: generous deadlines
        self.qc = QueryClient(connect_timeout=120.0, request_timeout=900.0)
        await self.qc.connect("127.0.0.1", self.srv.port)
        for k in range(N_SOCKETS):
            reader, writer, status, _hid = await register(
                "127.0.0.1", self.srv.port,
                machine_id=0xC41B5000 + k, conn_type=wire.CONN_EVENT)
            if status != wire.REG_OK:
                raise Failed(f"event conn {k}: registration status "
                             f"{status}")
            self.conns.append((reader, writer))

    async def query(self, req: dict) -> dict:
        self.srv.check_alive()
        return await self.qc.query(req)

    async def stats(self) -> dict:
        """Live counters and gauges of the serving process. A tick the
        server caught an exception in fails the smoke here, with what
        the devices held at that moment."""
        out = await self.query({"subsys": "selfstats"})
        c = self.last_stats = out["counters"]
        if c.get("tick_errors"):
            mem = {k: f"{v / 2**30:.3f} GiB" for k, v in c.items()
                   if k.startswith("device")}
            raise Failed(f"the server's tick failed ({c['tick_errors']}x"
                         f"; the exception is in the server log below); "
                         f"device memory then: {mem or 'not reported'}")
        return c

    async def send(self, bufs: list) -> None:
        for (_r, w), b in zip(self.conns, bufs):
            w.write(b)
        await asyncio.gather(*(w.drain() for _r, w in self.conns))

    async def wait_counter(self, want: dict, deadline_s: float) -> dict:
        """Poll until every counter in ``want`` reached its value —
        the ledger check, made at every step: accepted == built."""
        t0 = time.monotonic()
        c = {}
        while time.monotonic() - t0 < deadline_s:
            c = await self.stats()
            if all(c.get(k, 0) >= v for k, v in want.items()):
                over = {k: c.get(k, 0) for k, v in want.items()
                        if c.get(k, 0) != v}
                if over:
                    raise Failed(f"server accepted more than was built: "
                                 f"{over} vs {want}")
                return c
            await asyncio.sleep(0.002)
        raise Failed(f"server did not accept what was sent within "
                     f"{deadline_s:.0f}s: want {want}, have "
                     f"{ {k: c.get(k, 0) for k in want} }")

    async def wait_tick_after(self, tick: int, deadline_s: float) -> dict:
        """Poll until the server's tick number passed ``tick`` and the
        tick that did it has run to its end (its health gauges are
        written after its snapshot)."""
        t0 = time.monotonic()
        while time.monotonic() - t0 < deadline_s:
            c = await self.stats()
            if c.get("tick", 0) > tick \
                    and c.get("snapshot_tick", -1) >= tick:
                # that answer may have been computed while the tick
                # still ran; the loop reads the next request after it
                return await self.stats()
            await asyncio.sleep(0.02)
        raise Failed(f"no tick after {tick} within {deadline_s:.0f}s")

    def built_counters(self) -> dict:
        b = self.fleet.built
        return {"conn_events": b["conn"], "resp_events": b["resp"],
                "listener_records": b["listener"],
                "host_records": b["host"]}

    async def window(self, w: int, deadline_s: float) -> dict:
        """One window: the 5 s sweeps, then ROUNDS lockstep rounds of
        one full slab each. → counters' movement and whether a tick cut
        it."""
        c0 = await self.stats()
        t0 = time.monotonic()
        await self.send(self.fleet.sweep())
        await self.wait_counter(self.built_counters(), deadline_s)
        for _ in range(ROUNDS):
            bufs, tail, tail_c, tail_r = self.fleet.round(w)
            await self.send(bufs)
            want = self.built_counters()
            await self.wait_counter(
                {**want, "conn_events": want["conn_events"] - tail_c,
                 "resp_events": want["resp_events"] - tail_r},
                deadline_s)
            closer = self.conns[-1][1]
            closer.write(tail)
            await closer.drain()
            await self.wait_counter(want, deadline_s)
        c1 = await self.stats()
        d = lambda k: c1.get(k, 0) - c0.get(k, 0)      # noqa: E731
        return {"window": w, "tick": int(c0.get("tick", 0)),
                "clean": c1.get("tick", 0) == c0.get("tick", 0),
                "seconds": round(time.monotonic() - t0, 3),
                "slab_dispatches": d("slab_dispatches"),
                "slab_fill": (c1.get("engine_stage_slab_conn_occupancy"),
                              c1.get("engine_stage_slab_resp_occupancy")),
                "fold_dispatches": d("fold_dispatches"),
                "programs": d("xla_programs"),
                "compile_ms": round(d("xla_compile_ms"), 1)}


def _f32_sum_tol(n: int) -> float:
    # n float32 additions in arbitrary order
    return max(n, 1) * 2.0 ** -23 + 1e-6


async def run(args) -> dict:
    chips = args.chips
    size = TINY if args.rehearse_cpu else FLEET if chips == 1 else FLEET_4
    work = os.path.join(HERE, ".chip_smoke",
                        f"{'cpu' if args.rehearse_cpu else 'chip'}{chips}")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    cfg = geometry(size, chips)
    log(f"geometry: {json.dumps(cfg)} shards={chips} fleet="
        f"{size['hosts']} hosts x {size['svcs']} services over "
        f"{N_SOCKETS} sockets")
    if size is FLEET:
        log(f"geometry cut: svc_capacity {SVC_CAPACITY} and "
            f"{size['hosts'] * size['svcs']} live services on one chip "
            f"(fleet-50k: 131072 and 65536) — three state copies at "
            f"snapshot publication do not fit 16 GB; see SVC_CAPACITY")
    log(f"compile cache: "
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR') or '<checkout>/.jax_cache'}")
    srv = Server(work, cfg, chips, args.rehearse_cpu)
    try:
        return await _drive(srv, size, chips, args)
    except BaseException:
        log("---- server log tail ----")
        print(srv.log_tail(), flush=True)
        raise
    finally:
        srv.stop()


async def _drive(srv: Server, size: dict, chips: int, args) -> dict:
    fleet = Fleet(size)
    sm = Smoke(srv, fleet, chips)
    sm.edge_capacity = size["runtime"]["dep_edge_capacity"]
    eng = {**ENGINE_DEFAULTS, **size["engine"]}
    step_deadline = 900.0     # one step may sit behind cold compiles

    # -------------------------------------------------- start + device
    await srv.wait_listening(900.0)
    await sm.connect()
    # the CLI entry point once, the client library from here on
    cli = subprocess.run(
        [sys.executable, "-m", "gyeeta_tpu", "query", "--port",
         str(srv.port), "--timeout", "900",
         json.dumps({"subsys": "serverstatus"})],
        cwd=HERE, capture_output=True, text=True, timeout=1000,
        env={**os.environ, "PYTHONPATH": HERE})
    if cli.returncode != 0:
        raise Failed(f"`gyeeta_tpu query` failed: {cli.stderr[-800:]}")
    status = json.loads(cli.stdout)["recs"][0]
    first_answer_s = time.monotonic() - srv.t_spawn
    device = {"platform": status["platform"],
              "kind": status["devicekind"],
              "count": int(status["ndevices"])}
    c = await sm.stats()
    log(f"device (from serverstatus): {json.dumps(device)}")
    log(f"first answer {first_answer_s:.1f}s after spawn; "
        f"{c.get('xla_programs', 0)} programs, "
        f"{c.get('xla_compile_ms', 0) / 1e3:.1f}s inside the compiler "
        f"({c.get('xla_cache_hits', 0)} cache hits, "
        f"{c.get('xla_cache_misses', 0)} misses)")
    if args.rehearse_cpu:
        if device["platform"] != "cpu":
            raise Failed("--rehearse-cpu must run on the CPU backend")
    elif device["platform"] == "cpu":
        raise Failed("the serving process took the CPU backend: no "
                     "accelerator (a CPU rehearsal is --rehearse-cpu)")
    if device["count"] < chips:
        raise Failed(f"--chips {chips} on {device['count']} device(s)")

    # ------------------------------------------------------- inventory
    await sm.send([fleet.inventory(k) for k in range(N_SOCKETS)])
    await sm.wait_counter(
        {"listener_infos": fleet.built["listener_info"],
         "host_infos": fleet.built["host_info"]}, step_deadline)

    # --------------------------------------------------- the cold window
    windows = [await sm.window(0, step_deadline)]
    c = await sm.wait_tick_after(int(sm.last_stats.get("tick", 0)),
                                 step_deadline)
    first_fresh_s = time.monotonic() - srv.t_spawn
    cold = dict(programs=c.get("xla_programs", 0),
                compile_s=c.get("xla_compile_ms", 0) / 1e3,
                hits=c.get("xla_cache_hits", 0),
                misses=c.get("xla_cache_misses", 0))
    log(f"cold window: {json.dumps(windows[0])}")
    log(f"first tick over ingested events closed {first_fresh_s:.1f}s "
        f"after spawn: {cold['programs']} programs, "
        f"{cold['compile_s']:.1f}s inside the compiler "
        f"({100 * cold['compile_s'] / first_fresh_s:.0f}% of it), "
        f"persistent cache {cold['hits']} hits / {cold['misses']} misses")

    # ------------------------------------------------- the 5 s windows
    clean = 0
    for w in range(1, WINDOW_TRIES + 1):
        # start on a tick boundary: the window's events then land in
        # one 5 s window of the server's
        c = await sm.wait_tick_after(int(sm.last_stats.get("tick", 0)),
                                     step_deadline)
        win = await sm.window(w, step_deadline)
        windows.append(win)
        log(f"window {w}: {json.dumps(win)}")
        clean = clean + 1 if win["clean"] else 0
        if clean >= WINDOWS:
            break
    if clean < WINDOWS:
        # the recount below is per window: without a last window that
        # the tick left whole, no 5 s column can be compared
        raise Failed(
            f"in {WINDOW_TRIES} tries the server never took {WINDOWS} "
            f"consecutive windows of {ROUNDS} rounds inside its "
            f"{TICK_S:.0f}s tick: "
            f"{[(x['seconds'], x['clean']) for x in windows[1:]]}")
    sm.check(True, f"{WINDOWS} consecutive {TICK_S:.0f}s windows each held "
                   f"their {ROUNDS} rounds")
    good = [x for x in windows[1:] if x["clean"]]
    if chips == 1:
        sm.check(all(x["slab_dispatches"] == ROUNDS
                     and x["slab_fill"] == (1.0, 1.0) for x in good),
                 f"every clean window ran {ROUNDS} fused K-slab "
                 f"dispatches, each full on both sides: "
                 f"{[x['slab_dispatches'] for x in good]}")
    else:
        # a sharded dispatch is `chips` slabs wide: it fires when one
        # shard's lanes fill, every `chips` rounds
        sm.check(all(x["fold_dispatches"] >= ROUNDS // chips
                     for x in good),
                 f"every clean window ran >= {ROUNDS // chips} fused "
                 f"{chips}-shard dispatches: "
                 f"{[x['fold_dispatches'] for x in good]}")
    last = windows[-1]
    # the tick that closes the last window; its snapshot answers below
    c = await sm.wait_tick_after(last["tick"], step_deadline)
    warm_programs = sum(x["programs"] for x in windows[1:])
    log(f"programs compiled after the cold window, before the queries: "
        f"{warm_programs} "
        f"({'none: every fold/tick shape was seen in the cold window' if not warm_programs else 'a shape first met in a later window'})")

    # ------------------------------------------------------- the queries
    t_q = time.monotonic()
    progs_q0 = c.get("xla_programs", 0)
    answers = await _queries(sm, last)
    c = await sm.stats()
    log(f"queries: {time.monotonic() - t_q:.1f}s, "
        f"{c.get('xla_programs', 0) - progs_q0} readback programs "
        f"compiled on first use")
    absent = _absent_services(sm, fleet, answers, eng)
    _check_ledger(sm, c, fleet, absent)
    _check_answers(sm, fleet, answers, last, absent)

    # ------------------------------------------------------------ memory
    mem = {k: v for k, v in c.items() if k.startswith("device")}
    peaks = [v for k, v in mem.items() if k.endswith("_peak_bytes_in_use")]
    if mem:
        for k in sorted(mem):
            log(f"memory {k} = {mem[k] / 2**30:.3f} GiB")
        used = [v for k, v in mem.items()
                if k.endswith("_bytes_in_use") and "peak" not in k]
        if chips > 1:
            sm.check(len(used) >= chips
                     and min(used) > 0.5 * max(used),
                     f"state is spread over the {chips} devices "
                     f"(bytes_in_use min/max = "
                     f"{min(used) / 2**30:.2f}/{max(used) / 2**30:.2f} "
                     f"GiB), not placed whole on one")
    else:
        log("memory: this backend reports no memory_stats")
    log(f"totals: {c.get('xla_programs', 0)} programs, "
        f"{c.get('xla_compile_ms', 0) / 1e3:.1f}s inside the compiler, "
        f"persistent cache {c.get('xla_cache_hits', 0)} hits / "
        f"{c.get('xla_cache_misses', 0)} misses"
        + (f"; peak device bytes {max(peaks) / 2**30:.3f} GiB"
           if peaks else ""))
    assert "jax" not in sys.modules, "the smoke's parent imported jax"
    if sm.failures:
        raise Failed(f"{len(sm.failures)} check(s) failed: "
                     + "; ".join(sm.failures))
    return device


async def _queries(sm: Smoke, last: dict) -> dict:
    """Every query of the smoke. The whole-fleet svcstate pull goes
    first: its 5 s columns are only comparable while the newest snapshot
    is the one the last window's tick published."""
    n_svc = sm.fleet.n_svcs
    a = {}
    a["fleet"] = await sm.query({
        "subsys": "svcstate", "maxrecs": n_svc + 1000,
        # naming a lazy column group in the filter materialises it once
        # at slab width instead of row by row
        "filter": "{ svcstate.nqry5s >= 0 } and { svcstate.nclients >= 0 }"
                  " and { svcstate.p99resp5s >= 0 }"
                  " and { svcstate.p50resp5d >= 0 }",
        "columns": ["svcid", "hostid", "nqry5s", "nconns", "nclients",
                    "resp5s", "p95resp5s", "p99resp5s", "p50resp5d",
                    "p95resp5d"]})
    a["top100"] = await sm.query({
        "subsys": "svcstate", "maxrecs": 100,
        "filter": "{ svcstate.nconns > 45 } and { svcstate.hostid >= "
                  f"{sm.fleet.n_hosts // 2} }}",
        "sortcol": "nconns", "sortdesc": True})
    a["hoststate"] = await sm.query({
        "subsys": "hoststate", "maxrecs": sm.fleet.n_hosts + 100,
        "sortcol": "hostid", "sortdesc": False})
    a["clusterstate"] = await sm.query({"subsys": "clusterstate"})
    a["topk"] = await sm.query({"subsys": "topk", "maxrecs": 1000})
    a["dep"] = await sm.query({
        "subsys": "svcdependency", "maxrecs": n_svc + 1000,
        "aggr": ["sum(nconn) as nconn", "sum(bytes) as bytes",
                 "count(*) as ncallers"],
        "groupby": ["serid"]})
    a["dep100"] = await sm.query({
        "subsys": "svcdependency", "maxrecs": 100,
        "sortcol": "nconn", "sortdesc": True})
    a["serverstatus"] = await sm.query({"subsys": "serverstatus"})
    for k, v in a.items():
        log(f"answer {k}: {v.get('nrecs')} rows"
            + (f", snaptick {v['snaptick']}" if "snaptick" in v else ""))
    return a


def _absent_services(sm: Smoke, fleet: Fleet, a: dict, eng: dict) -> set:
    """Seeded services with no row in the service slab.

    A key finds no slot in the 16-probe slab with probability load^16
    (``engine/table.py``): at the published 50 % load that is 1.5e-5 per
    service — one in a fleet of 65,536. Such a service is retried and
    counted by every sweep, and its response samples are counted as
    unknown. Allowed: four times the expectation, rounded; each absent
    service is then held to exactly those counters."""
    got = {r["svcid"] for r in a["fleet"]["recs"]}
    absent = set(hexid(fleet.all_svc)) - got
    slab = eng["svc_capacity"]            # rows over all shards
    load = fleet.n_svcs / float(slab)
    allowed = int(4.0 * fleet.n_svcs * load ** 16 + 0.5)
    sm.check(len(absent) <= allowed and got <= set(hexid(fleet.all_svc)),
             f"svcstate: {len(got)} of {fleet.n_svcs} seeded services "
             f"have a row ({len(absent)} found no slot in the "
             f"{slab}-row slab at {100 * load:.0f}% load; {allowed} "
             f"allowed by its probe-failure odds), and no other row")
    return absent


def _check_ledger(sm: Smoke, c: dict, fleet: Fleet, absent: set) -> None:
    b = fleet.built
    sm.check(c.get("conn_events") == b["conn"]
             and c.get("resp_events") == b["resp"]
             and c.get("listener_records") == b["listener"]
             and c.get("host_records") == b["host"],
             f"events accepted == events built: conn {b['conn']}, resp "
             f"{b['resp']}, listener {b['listener']}, host {b['host']}")
    sm.check(not any(c.get(k) for k in (
        "frames_bad", "records_unknown_subtype", "conns_framing_errors")),
        "no rejected frame, no unknown subtype")
    svc_all, _ = fleet.resp_columns()
    unknown = int(np.isin(svc_all, np.array(
        [int(i, 16) for i in absent], np.uint64)).sum())
    sm.check(c.get("engine_conn_folded") == b["conn"]
             and c.get("engine_resp_folded") == b["resp"]
             and c.get("engine_resp_unknown_svc") == unknown,
             f"device fold counters == events built (conn "
             f"{c.get('engine_conn_folded')}, resp "
             f"{c.get('engine_resp_folded')}), resp for services without "
             f"a row {c.get('engine_resp_unknown_svc')} == {unknown}")
    sm.check(c.get("engine_svc_rows_live") == fleet.n_svcs - len(absent)
             and bool(c.get("engine_svc_probe_failures")) == bool(absent)
             and not c.get("engine_dep_probe_failures")
             and not c.get("engine_dep_dropped"),
             f"{fleet.n_svcs - len(absent)} live service rows, "
             f"{c.get('engine_svc_probe_failures', 0):.0f} counted insert "
             f"retries for the {len(absent)} absent, no dependency drop")
    sm.check(c.get("native_decode_available") == 1.0
             and c.get("ref_native_decoded", 0) > 0
             and not c.get("ref_fallback_decoded"),
             f"native deframer loaded and used "
             f"({c.get('ref_native_decoded', 0)} records decoded "
             f"natively, {c.get('ref_fallback_decoded', 0)} by the "
             f"Python fallback)")


def _check_answers(sm: Smoke, fleet: Fleet, a: dict, last: dict,
                   absent: set) -> None:
    n_svc = fleet.n_svcs - len(absent)
    conn = fleet.conn_columns()
    gamma = (RESP_SPEC[1] / RESP_SPEC[0]) ** (1.0 / RESP_SPEC[2])
    qtol = math.sqrt(gamma) - 1.0 + 1e-4      # half a bucket, in f32

    # ---- serverstatus
    ss = a["serverstatus"]["recs"][0]
    sm.check(ss["nsvc"] == n_svc and ss["nhosts"] == fleet.n_hosts
             and ss["connevents"] == fleet.built["conn"]
             and ss["respevents"] == fleet.built["resp"],
             f"serverstatus: nsvc {ss['nsvc']}, nhosts {ss['nhosts']}, "
             f"connevents {ss['connevents']}, respevents "
             f"{ss['respevents']} == recount")

    # ---- whole-fleet svcstate: exact columns, then sketch columns
    rows = a["fleet"]["recs"]
    sm.check(a["fleet"].get("snaptick") == last["tick"],
             f"the fleet pull was answered from the snapshot of the "
             f"tick that closed the last window (snaptick "
             f"{a['fleet'].get('snaptick')} == {last['tick']})")
    by_id = {r["svcid"]: r for r in rows}
    ids = [i for i in hexid(fleet.all_svc) if i not in absent]
    if not sm.check(len(rows) == n_svc == a["fleet"]["ntotal"]
                    and set(by_id) == set(ids),
                    f"svcstate: {len(rows)} rows, one per service"):
        return
    col = lambda name: np.array(                      # noqa: E731
        [by_id[i][name] for i in ids], np.float64)
    present = lambda u: np.array(                     # noqa: E731
        [i in by_id for i in hexid(u)], bool)
    lst = fleet.last_listener
    lst_of = dict(zip(hexid(lst["glob_id"]), range(len(lst))))
    lrow = np.array([lst_of[i] for i in ids])
    sm.check(np.array_equal(col("nconns"),
                            lst["nconns"][lrow].astype(np.float64)),
             "svcstate.nconns == the last LISTENER_STATE sweep, every "
             "service")
    # nqry5s: max(resp samples folded in the window, the sweep's gauge)
    svc_w, val_w = fleet.resp_columns(window=last["window"])
    u, cnt = np.unique(svc_w, return_counts=True)
    in_win = dict(zip(hexid(u), cnt))
    want = np.maximum(np.array([in_win.get(i, 0) for i in ids]),
                      lst["nqrys_5s"][lrow]).astype(np.float64)
    sm.check(np.array_equal(col("nqry5s"), want),
             "svcstate.nqry5s == max(window's resp samples, sweep "
             "gauge), every service")
    sm.check(np.array_equal(
        col("hostid"), lst["host_id"][lrow].astype(np.float64)),
        "svcstate.hostid == the owning host, every service")

    # sketch columns: loghist quantiles against the exact order
    # statistic they resolve (error <= half a geometric bucket)
    def quantile_check(name, q, svc, val):
        uq, start, v = group_sorted(svc, val.astype(np.float64))
        _n, hi, lo = order_stat(uq, start, v, q)
        ok = present(uq)
        uq, hi, lo = uq[ok], hi[ok], lo[ok]
        got = np.array([by_id[i][name] for i in hexid(uq)]) * 1e3
        # answers carry msec to three decimals (fieldmaps.row_to_json):
        # half a microsecond of representation on top of the bound
        err = np.minimum((np.abs(got - hi) - 0.5) / hi,
                         (np.abs(got - lo) - 0.5) / lo)
        w = int(err.argmax())
        sm.check(bool((err <= qtol).all()),
                 f"svcstate.{name}: loghist q={q} within "
                 f"{100 * qtol:.2f}% (+0.5 us of JSON rounding) of the "
                 f"exact order statistic for all {len(uq)} services "
                 f"(worst {100 * err[w]:.2f}%: {_n[ok][w]} samples, "
                 f"served {got[w]:.0f} us, exact {hi[w]:.0f})")

    svc_all, val_all = fleet.resp_columns()
    val_w = np.clip(val_w, RESP_SPEC[0], RESP_SPEC[1])
    val_all = np.clip(val_all, RESP_SPEC[0], RESP_SPEC[1])
    quantile_check("p50resp5d", 0.5, svc_all, val_all)
    quantile_check("p95resp5d", 0.95, svc_all, val_all)
    quantile_check("p95resp5s", 0.95, svc_w, val_w)
    quantile_check("p99resp5s", 0.99, svc_w, val_w)
    uq, start, v = group_sorted(svc_w, val_w.astype(np.float64))
    mean = np.add.reduceat(v, start) / np.diff(np.append(start, len(v)))
    uq, mean = uq[present(uq)], mean[present(uq)]
    got = np.array([by_id[i]["resp5s"] for i in hexid(uq)]) * 1e3
    err = (np.abs(got - mean) - 0.5) / mean
    sm.check(bool((err <= qtol).all()),
             f"svcstate.resp5s: loghist mean within {100 * qtol:.2f}% "
             f"of the exact mean (worst {100 * err.max():.2f}%)")
    # distinct clients per service (HLL p=10: 1.04/sqrt(m) = 3.25 %)
    pair = np.unique(np.stack([conn["svc"],
                               conn["cli_ip"].astype(np.uint64)]), axis=1)
    u, cnt = np.unique(pair[0], return_counts=True)
    truth = dict(zip(hexid(u), cnt))
    want = np.array([truth.get(i, 0) for i in ids], np.float64)
    got = col("nclients")
    # the accuracy tests hold a per-entity HLL to 10 % (thousands of
    # keys); a service here has a few dozen clients, where a p=10 sketch
    # loses one per register collision, so the relative bound is floored
    # at 8 clients — and the fleet as a whole must stay inside the
    # sketch's documented standard error, 1.04 / sqrt(m)
    std = 1.04 / math.sqrt(1 << HLL_P_SVC)
    err = np.abs(got - want)
    sm.check(bool((err <= np.maximum(0.1 * want, 8.0)).all())
             and float(err.sum() / max(want.sum(), 1.0)) <= std,
             f"svcstate.nclients: HLL within max(10%, 8) of the exact "
             f"distinct client count for every service (max abs "
             f"{err.max():.2f} at {want[err.argmax()]:.0f} clients), "
             f"fleet-wide error "
             f"{100 * err.sum() / max(want.sum(), 1.0):.2f}% <= "
             f"{100 * std:.2f}%")

    # ---- filtered + sorted top-100 (tie order is not compared)
    rows = a["top100"]["recs"]
    keep = (lst["nconns"] > 45) & (lst["host_id"] >= fleet.n_hosts // 2) \
        & present(lst["glob_id"])
    want = np.sort(lst["nconns"][keep].astype(np.float64))[::-1][:100]
    got = np.array([r["nconns"] for r in rows])
    sm.check(len(rows) == len(want) and np.array_equal(got, want)
             and all(r["hostid"] >= fleet.n_hosts // 2 for r in rows)
             and all(r["nconns"]
                     == lst["nconns"][lst_of[r["svcid"]]] for r in rows),
             f"svcstate filtered + sorted top-{len(want)}: the sorted "
             f"nconns column equals the recount's, every row satisfies "
             f"the filter and carries its own service's gauge")

    # ---- hoststate / clusterstate
    rows = a["hoststate"]["recs"]
    hst = fleet.last_host
    ok = len(rows) == fleet.n_hosts and all(
        r["hostid"] == h["host_id"] and r["nproc"] == h["ntasks"]
        and r["nprocissue"] == h["ntasks_issue"]
        and r["nlisten"] == h["nlisten"]
        and r["nlistissue"] == h["nlisten_issue"]
        and bool(r["cpuissue"]) == bool(h["cpu_issue"])
        and bool(r["memissue"]) == bool(h["mem_issue"])
        for r, h in zip(rows, hst[np.argsort(hst["host_id"])]))
    sm.check(ok, f"hoststate: {len(rows)} host rows == the last "
                 f"HOST_STATE sweep, column by column")
    cs = a["clusterstate"]["recs"][0]
    states = [r["state"] for r in rows]
    sm.check(cs["nhosts"] == fleet.n_hosts and all(
        cs[k] == states.count(name) for k, name in (
            ("nidle", "Idle"), ("ngood", "Good"), ("nok", "OK"),
            ("nbad", "Bad"), ("nsevere", "Severe"), ("ndown", "Down"))),
        f"clusterstate: {cs['nhosts']} hosts, per-state counts == a "
        f"recount of the hoststate answer")

    # ---- dependency graph: per-service conn counts, bytes, callers
    rows = a["dep"]["recs"]
    u, inv = np.unique(conn["svc"], return_inverse=True)
    n_conn = np.bincount(inv)
    n_bytes = np.bincount(inv, weights=conn["bytes"])
    edge = np.unique(np.stack([conn["svc"], conn["cli_task"]]), axis=1)
    ue, n_call = np.unique(edge[0], return_counts=True)
    t_conn = dict(zip(hexid(u), n_conn))
    t_bytes = dict(zip(hexid(u), n_bytes))
    t_call = dict(zip(hexid(ue), n_call))
    # the edge view is re-hashed into a fresh 16-probe slab when it is
    # read (and, on a mesh, when the tick's roll-up merges the shards'
    # edges): a key finds no slot with probability load^16
    # (engine/table.py), so at 50 % load a few edges of 262,144 are
    # missing from the answer. Allowed: four times that expectation,
    # and a service may only ever be SHORT of its recount.
    n_edges = edge.shape[1]
    load = n_edges / float(sm.edge_capacity)
    allowed = math.ceil(4.0 * n_edges * load ** 16 - 1e-9)
    off = [r for r in rows if r["nconn"] != t_conn.get(r["serid"])
           or r["ncallers"] != t_call.get(r["serid"])]
    sm.check(len(rows) == len(u) and len(off) <= allowed
             and all(r["serid"] in t_conn
                     and r["nconn"] <= t_conn[r["serid"]]
                     and r["ncallers"] <= t_call[r["serid"]]
                     for r in off),
             f"svcdependency: per-service conn counts and caller counts "
             f"== recount for {len(rows) - len(off)} of {len(u)} services "
             f"({int(n_conn.sum())} conns, {n_edges} edges at "
             f"{100 * load:.0f}% slab load: {len(off)} services short, "
             f"{allowed} allowed by the slab's probe-failure odds)")
    short = {r["serid"] for r in off}
    rel = max((abs(r["bytes"] - t_bytes[r["serid"]])
               / t_bytes[r["serid"]] for r in rows
               if r["serid"] in t_bytes and r["serid"] not in short),
              default=1.0)
    sm.check(rel <= _f32_sum_tol(int(n_conn.max())),
             f"svcdependency: per-service bytes == recount to float32 "
             f"summation error (max rel {rel:.2e})")
    rows = a["dep100"]["recs"]
    ekey = (conn["svc"].astype(np.uint64), conn["cli_task"])
    eu, ecnt = np.unique(np.stack(ekey), axis=1, return_counts=True)
    want = np.sort(ecnt)[::-1][:100].astype(np.float64)
    sm.check(np.array_equal(np.array([r["nconn"] for r in rows]), want),
             "svcdependency sorted top-100 edges: the nconn column "
             "equals the recount's")

    # ---- heavy hitters: weighted error of the top 32 flows by bytes
    rows = [r for r in a["topk"]["recs"] if r["metric"] == "bytes"]
    u, inv = np.unique(conn["flow"], return_inverse=True)
    tot = np.bincount(inv, weights=conn["bytes"])
    top = np.argsort(tot)[::-1][:32]
    got = {r["id"]: r["value"] for r in rows}
    err = sum(abs(got.get(format(int(u[i]), "016x"), 0.0) - tot[i])
              for i in top) / tot[top].sum()
    sm.check(err <= 0.02,
             f"topk: weighted error of the 32 heaviest flows vs the "
             f"exact recount {100 * err:.3f}% <= 2% "
             f"({len(rows)} flow rows served)")


def main() -> int:
    if not os.path.isdir(os.path.join(HERE, "gyeeta_tpu")):
        log(f"FAILED: no gyeeta_tpu package beside {__file__}")
        return 2
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: serve --shards 4 (run on the 4-chip host)")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny size on the CPU backend; cannot report "
                    "a tpu")
    args = ap.parse_args()
    t0 = time.monotonic()
    try:
        device = asyncio.run(run(args))
    except Failed as e:
        log(f"FAILED after {time.monotonic() - t0:.0f}s: {e}")
        return 1
    log(f"passed in {time.monotonic() - t0:.0f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
