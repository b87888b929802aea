#!/usr/bin/env python3
"""The benchmark's one command: one cell, one run, one result line.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s>
                              --trace <0|1>

This process imports no jax (one process owns a chip, and it is not this
one). It reads ``BENCHMARK.json``, the cell's workload file
(``benchmarks/workloads/<cell>.json``) and its configuration
(``benchmarks/configs/<config>.json``); starts the program's normal
``serve`` entry as the one chip-owning child (``lib/child.py``); builds the
seeded fleet (``lib/gen.py``) and hands its bytes to one sender process
(``lib/sender.py``); warms up the cell's own shapes; measures for
``--seconds``; then lets the plain recount (``lib/recount.py``) decide
``correct`` and prints the contract's last line. A cell, a configuration
or a per-layer metric is a file plus one entry in ``BENCHMARK.json``.

Set-up is everything from process start to the start of the window. It
fails (non-zero, no result line) without an accelerator; the tiny CPU
rehearsal (``--rehearse-cpu``, used by ``benchmarks/tests``) prints counts
and ``correct`` only, never a device metric.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse                                     # noqa: E402
import asyncio                                      # noqa: E402
import importlib.util                               # noqa: E402
import json                                         # noqa: E402
import multiprocessing                              # noqa: E402
import os                                           # noqa: E402
import shutil                                       # noqa: E402
import signal                                       # noqa: E402
import socket                                       # noqa: E402
import statistics                                   # noqa: E402
import subprocess                                   # noqa: E402
import sys                                          # noqa: E402

import numpy as np                                  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from lib import fresh, gen, recount, sender, shapes    # noqa: E402
from lib import proto as P                              # noqa: E402

TICK_S = 5.0
REHEARSE_TICK_S = 1.0   # the CPU rehearsal's ticks, to keep the tests short
CADENCE_TICKS = 12      # RuntimeOpts.task_age_every_ticks
TICK_PHASE_S = 0.3      # window start, after a tick has closed: the 5 s
#                         sleep of the tick loop has just begun, so a
#                         40 s window holds 7 ticks whatever a tick takes
#                         (up to 0.7 s)
PRE_ROLL_MAX_S = 7.5    # traffic begins a tick before the window opens and
#                         is sent for the window's length plus this


class Failed(Exception):
    """The run cannot give a result; the message says why."""


def log(msg: str) -> None:
    print(f"bench: [{time.monotonic() - T_PROCESS_START:7.2f}s] {msg}",
          file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# ------------------------------------------------------------------ server
class Server:
    """The chip-owning child (``chip_smoke.py:Server``)."""

    def __init__(self, work: str, cfg: dict, trace: bool, rehearse: bool,
                 fault: str):
        self.port = free_port()
        self.log_path = os.path.join(work, "server.log")
        self.trace_dir = os.path.join(work, "trace")
        cfg_path = os.path.join(work, "serve.json")
        with open(cfg_path, "w") as f:
            json.dump({"engine": cfg["engine"], "runtime": cfg["runtime"]},
                      f, indent=1)
        # size is fixed by the configuration file: GYT_<FIELD> variables
        # outrank a config file, so none reach the child
        env = {k: v for k, v in os.environ.items()
               if not k.startswith("GYT_")}
        if rehearse:
            env["JAX_PLATFORMS"] = "cpu"
        cmd = [sys.executable, os.path.join(HERE, "lib", "child.py")]
        if trace:
            os.makedirs(self.trace_dir)
            cmd += ["--bench-trace-dir", self.trace_dir]
        if fault:
            cmd += ["--bench-fault", fault]
        cmd += ["--", "--config", cfg_path, "--host", "127.0.0.1",
                "--port", str(self.port), "--tick-interval",
                str(REHEARSE_TICK_S if rehearse else TICK_S),
                "--idle-timeout", "0", "--stats-interval", "30",
                "--log-level", "INFO"]
        self._logf = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            cmd, cwd=ROOT, env=env, stdin=subprocess.PIPE,
            stdout=self._logf, stderr=subprocess.STDOUT,
            start_new_session=True)

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
                await asyncio.sleep(0.1)
        raise Failed(f"server not listening after {deadline_s:.0f}s")

    def tell(self, line: str) -> None:
        """A command for the child's stdin thread (lib/child.py)."""
        self.proc.stdin.write((line + "\n").encode())
        self.proc.stdin.flush()

    def compiles_between(self, lo: int, hi: int) -> str:
        """The programs the child's log names as compiled between two
        offsets of it (lib/child.py turns ``jax_log_compiles`` on)."""
        with open(self.log_path, "rb") as f:
            f.seek(lo)
            text = f.read(max(0, hi - lo)).decode("utf-8", "replace")
        names = [ln.split("Compiling ", 1)[1].split(" with ")[0]
                 for ln in text.splitlines()
                 if "Compiling " in ln and ln.startswith("WARNING:")]
        return "compiled: " + ", ".join(names)

    def log_tail(self, nbytes: int = 5000) -> str:
        self._logf.flush()
        with open(self.log_path, "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(0, f.tell() - nbytes))
            return f.read().decode("utf-8", "replace")

    def stop(self) -> None:
        """SIGTERM, then SIGKILL the whole session: nothing survives."""
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGTERM)
                self.proc.wait(timeout=20)
            except (subprocess.TimeoutExpired, ProcessLookupError):
                pass
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        self.proc.wait(timeout=30)
        if self.proc.stdin:
            self.proc.stdin.close()
        self._logf.close()


class SenderProc:
    """The sender process and its command pipe."""

    def __init__(self, spec: dict):
        ctx = multiprocessing.get_context("spawn")
        self.pipe, child = ctx.Pipe()
        self.proc = ctx.Process(target=sender.main, args=(child, spec),
                                daemon=True)
        self.proc.start()
        child.close()

    async def call(self, cmd: str, *args, timeout: float = 600.0):
        self.pipe.send((cmd, args))
        t0 = time.monotonic()
        while not self.pipe.poll():
            if not self.proc.is_alive():
                raise Failed(f"the sender process died in {cmd!r}")
            if time.monotonic() - t0 > timeout:
                raise Failed(f"the sender did not finish {cmd!r} in "
                             f"{timeout:.0f}s")
            await asyncio.sleep(0.01)
        status, reply = self.pipe.recv()
        if status != "ok":
            raise Failed(f"sender {cmd!r}: {reply}")
        return reply

    def stop(self) -> None:
        if self.proc.is_alive():
            self.proc.terminate()
        self.proc.join(timeout=10)
        if self.proc.is_alive():
            self.proc.kill()
            self.proc.join(timeout=10)
        self.pipe.close()


# ------------------------------------------------------------------ driver
class Run:
    def __init__(self, srv: Server, fleet, cfg: dict, wl: dict, seed: int):
        self.srv, self.fleet, self.cfg, self.wl = srv, fleet, cfg, wl
        self.seed = seed
        self.qc = P.QueryClient()
        self.snd = None
        self.last: dict = {}
        self.markers: list = []

    async def stats(self) -> dict:
        """Counters, gauges and stage timings of the serving process."""
        self.srv.check_alive()
        out = await self.qc.query({"subsys": "selfstats"}, timeout=900.0)
        c = out["counters"]
        c["_t"] = time.monotonic()
        c["_timings"] = {r["stage"]: (r["count"], r["totalms"])
                         for r in out.get("timings", [])}
        self.last = c
        if c.get("tick_errors"):
            raise Failed(f"the server's tick failed "
                         f"({c['tick_errors']}x); see the server log")
        return c

    async def wait_counters(self, want: dict, deadline_s: float) -> dict:
        t0 = time.monotonic()
        while True:
            c = await self.stats()
            if all(c.get(k, 0) >= v for k, v in want.items()):
                return c
            if time.monotonic() - t0 > deadline_s:
                return c
            await asyncio.sleep(0.01)

    async def wait_tick_after(self, tick: int, deadline_s: float) -> dict:
        """Until the server's tick number passed ``tick`` and that tick
        published its snapshot and ran to its end."""
        t0 = time.monotonic()
        while time.monotonic() - t0 < deadline_s:
            c = await self.stats()
            if c.get("tick", 0) > tick \
                    and c.get("snapshot_tick", -1) >= tick:
                return await self.stats()
            await asyncio.sleep(0.02)
        raise Failed(f"no tick after {tick} within {deadline_s:.0f}s")

    def built(self, totals: dict) -> dict:
        return recount.built_counters(self.fleet, totals)

    async def warm_variants(self) -> None:
        """Drive every fused-fold program the window can meet, once, on
        purpose (lib/gen.py:_build_warm): the sweep sections alone, and
        each with a slab that fills in the same feed. A tick that cuts a
        step flushes the part-filled slab, so a step that did not end in
        exactly one slab dispatch is made again."""
        snd = self.snd
        # the one-microbatch flush: a few lanes, alone, for a tick to fold
        for _try in range(3):
            c = await self.stats()
            t = await snd.call("warm_once", ["tail"])
            await self.wait_counters(self.built(t), 1100.0)
            c1 = await self.wait_tick_after(int(c.get("tick", 0)), 1100.0)
            if c1.get("fold_dispatches", 0) > c.get("fold_dispatches", 0) \
                    and c1.get("slab_dispatches", 0) \
                    == c.get("slab_dispatches", 0):
                break
        else:
            raise Failed("warm-up never saw a tick fold a lone microbatch")
        for sect in (["host"], ["lst"], ["host", "lst"]):
            t = await snd.call("warm_once", sect)
            await self.wait_counters(self.built(t), 1100.0)
        for sect in ([], ["host"], ["lst"], ["host", "lst"]):
            for _try in range(4):
                c = await self.stats()
                before = c.get("slab_dispatches", 0)
                t = await snd.call("warm_once", ["bulk"])
                c = await self.wait_counters(self.built(t), 1100.0)
                mid = c.get("slab_dispatches", 0)
                t = await snd.call("warm_once", sect + ["tail"])
                c = await self.wait_counters(self.built(t), 1100.0)
                if mid == before and c.get("slab_dispatches", 0) == mid + 1:
                    break
            else:
                raise Failed(f"warm-up could not fill a slab together "
                             f"with {sect} in four tries")

    def requests(self) -> list:
        """The cell's dashboard queries, placeholders filled."""
        dash = self.wl.get("dashboards")
        if not dash:
            return []
        text = json.dumps(dash["queries"])
        text = text.replace('"$HOSTS_PLUS"', str(self.fleet.n_hosts + 100))
        text = text.replace("$HALF_HOSTS", str(self.fleet.n_hosts // 2))
        return json.loads(text)

    def probe_req(self) -> dict:
        return {"subsys": "svcstate", "maxrecs": 4096,
                "filter": f"{{ svcstate.nprocs = {gen.PROBE_NTASKS} }}",
                "columns": ["svcid", "nconns"]}

    def sample(self) -> tuple:
        """The host range of the sketch pull, drawn from the seed."""
        n = int(self.wl["check"].get("sample_hosts") or self.fleet.n_hosts)
        n = min(n, self.fleet.n_hosts)
        h0 = (self.seed * 2654435761) % (self.fleet.n_hosts - n + 1)
        return h0, h0 + n

    def check_requests(self) -> dict:
        f = self.fleet
        h0, h1 = self.sample()
        return {
            "fleet_sketch": {
                "subsys": "svcstate", "maxrecs": f.n_svcs + 1000,
                # naming a lazy column group in the filter materialises
                # it once at slab width instead of row by row
                "filter": f"{{ svcstate.hostid >= {h0} }} and "
                          f"{{ svcstate.hostid < {h1} }} and "
                          "{ svcstate.nqry5s >= 0 } and "
                          "{ svcstate.nclients >= 0 } and "
                          "{ svcstate.p99resp5s >= 0 } and "
                          "{ svcstate.p50resp5d >= 0 }",
                "columns": ["svcid", "hostid", "nqry5s", "nclients",
                            "resp5s", "p95resp5s", "p99resp5s",
                            "p50resp5d", "p95resp5d"]},
            "fleet_exact": {
                "subsys": "svcstate", "maxrecs": f.n_svcs + 1000,
                "columns": ["svcid", "hostid", "nconns"]},
            "top100": {
                "subsys": "svcstate", "maxrecs": 100,
                "filter": "{ svcstate.nconns > 45 } and "
                          f"{{ svcstate.hostid >= {f.n_hosts // 2} }}",
                "sortcol": "nconns", "sortdesc": True},
            "hoststate": {"subsys": "hoststate",
                          "maxrecs": f.n_hosts + 100,
                          "sortcol": "hostid", "sortdesc": False},
            "clusterstate": {"subsys": "clusterstate"},
            "topk": {"subsys": "topk", "maxrecs": 1000},
            "dep": {"subsys": "svcdependency", "maxrecs": f.n_svcs + 1000,
                    "aggr": ["sum(nconn) as nconn", "sum(bytes) as bytes",
                             "count(*) as ncallers"],
                    "groupby": ["serid"]},
            "dep100": {"subsys": "svcdependency", "maxrecs": 100,
                       "sortcol": "nconn", "sortdesc": True},
            "serverstatus": {"subsys": "serverstatus"},
        }

    # -------------------------------------------------- window's clients
    async def poller(self, qc, period: float, stop: list,
                     polls: list) -> None:
        req = self.probe_req()
        due = time.monotonic()
        while due < stop[0]:
            d = due - time.monotonic()
            if d > 0:
                await asyncio.sleep(d)
            a = await qc.query(req, timeout=60.0)
            gauge = max((int(r["nconns"]) for r in a.get("recs", [])),
                        default=0)
            polls.append((int(a.get("snaptick", -1)), gauge,
                          time.monotonic()))
            due = max(due + period, time.monotonic())

    async def dashboard(self, qc, k: int, reqs: list, think: float,
                        stop: list, out: list) -> None:
        i = k                       # clients start on different queries
        while time.monotonic() < stop[0]:
            q = reqs[i % len(reqs)]
            i += 1
            t = time.monotonic()
            try:
                a = await qc.query(q["req"], timeout=60.0)
            except (P.ProtoError, asyncio.TimeoutError, TimeoutError,
                    ConnectionError) as e:
                out.append({"name": q["name"], "t": t, "ms": None,
                            "answer": None, "error": str(e)})
                return              # the connection is out of step
            out.append({"name": q["name"], "t": t,
                        "ms": (time.monotonic() - t) * 1e3, "answer": a})
            await asyncio.sleep(think)


async def run_cell(args, cell: dict, cfg: dict, wl: dict,
                   work: str) -> dict:
    trace = bool(args.trace)
    seed = int(args.seed)
    probe = wl.get("probe")
    loop = asyncio.get_running_loop()
    srv = Server(work, cfg, trace, args.rehearse_cpu, args.fault)
    snd = None
    try:
        log(f"cores: {os.cpu_count()}; cell {cell['name']} seed {seed} "
            f"seconds {args.seconds} trace {args.trace}")
        t_b = time.monotonic()
        fleet = await loop.run_in_executor(
            None, lambda: gen.Fleet(cfg["fleet"], wl, seed,
                                    int(probe["services"]) if probe else 0,
                                    cfg["engine"]))
        run = Run(srv, fleet, cfg, wl, seed)
        snd = run.snd = SenderProc({
            "host": "127.0.0.1", "port": srv.port, "bufs": fleet.wire(),
            "machine_base": 0xC41B5000})
        log(f"fleet built in {time.monotonic() - t_b:.1f}s: "
            f"{fleet.n_sockets} sockets, {fleet.n_svcs} services, pool "
            f"{fleet.pool} rounds of {fleet.conn_per}+{fleet.resp_per} "
            f"per socket")

        # ---------------------------------------------- start + device
        await srv.wait_listening(1100.0)
        await run.qc.connect("127.0.0.1", srv.port)
        ss = (await run.qc.query({"subsys": "serverstatus"},
                                 timeout=1100.0))["recs"][0]
        device = {"platform": ss["platform"], "kind": ss["devicekind"],
                  "count": int(ss["ndevices"])}
        log(f"device {json.dumps(device)} "
            f"{time.monotonic() - T_PROCESS_START:.1f}s after start")
        if args.rehearse_cpu:
            if device["platform"] != "cpu":
                raise Failed("--rehearse-cpu must run on the CPU backend")
        elif device["platform"] == "cpu":
            raise Failed("the serving process took the CPU backend: no "
                         "accelerator")
        if device["count"] < int(cell["chips"]) and not args.rehearse_cpu:
            raise Failed(f"cell asks for {cell['chips']} chip(s), "
                         f"jax sees {device['count']}")

        # -------------------------------------------- inventory, warm-up
        ids = (await snd.call("connect"))["host_ids"]
        if fleet.n_sockets == fleet.n_hosts and ids != list(range(len(ids))):
            raise Failed("direct agents were not given host ids in order")
        await snd.call("inventory")
        want = {"listener_infos": sum(b["n_linfo"] for b in fleet.bufs),
                "host_infos": sum(b["n_hinfo"] for b in fleet.bufs)}
        c = await run.wait_counters(want, 600.0)
        if any(c.get(k, 0) != v for k, v in want.items()):
            raise Failed(f"inventory not taken: want {want}")
        log("inventory taken")
        # the sweep first: it creates every service row (a response
        # sample for a service without a row is counted unknown)
        totals = await snd.call("once", "sweep", 0)
        await run.wait_counters(run.built(totals), 1100.0)
        await run.warm_variants()
        log(f"fold variants warm: {run.last.get('xla_programs')} programs, "
            f"{run.last.get('xla_compile_ms', 0) / 1e3:.1f}s in the compiler")
        if probe:
            run.markers += (await snd.call(
                "markers_once", fleet.probe_ids))["markers"]
        for r in range(int(wl["warmup_rounds"])):
            totals = await snd.call("once", "round", r % fleet.pool)
        c = await run.wait_counters(run.built(totals), 1100.0)
        c = await run.wait_tick_after(int(c.get("tick", 0)), 1100.0)
        log("first tick over traffic closed")
        reqs = run.requests()
        # the window's own queries only: what the comparison alone asks
        # (the whole-fleet pulls, two svcdependency views of ~5 s each)
        # is first asked after the window, outside set-up
        warm = [q["req"] for q in reqs]
        if probe:
            warm.append(run.probe_req())
        for q in warm:
            await run.qc.query(q, timeout=1100.0)
        log("queries warm")
        # the tick's maintenance cadences (process-group and API ageing,
        # every 12th tick) compile at their first use: a server is warm
        # once tick 12 has closed, and so is one more tick after the
        # first queries
        c = await run.stats()
        c = await run.wait_tick_after(
            max(int(c.get("tick", 0)), CADENCE_TICKS - 1), 1100.0)

        # ------------------------------------------------------ window
        seconds = float(args.seconds)
        clients = []
        n_dash = int(wl["dashboards"]["clients"]) if reqs else 0
        for k in range(n_dash + (1 if probe else 0)):
            qc = P.QueryClient(machine_id=0x51C00000 + k)
            await qc.connect("127.0.0.1", srv.port)
            clients.append(qc)
        rate = wl.get("rate_events_per_s")
        per_round = fleet.n_sockets * (fleet.conn_per + fleet.resp_per)
        period = per_round / float(rate) if rate else None
        # every seed has the same arrivals: socket k's writes are due at
        # the k/n-th part of each period (the seed changes the content)
        phase = (np.arange(fleet.n_sockets) / fleet.n_sockets).tolist()
        # Traffic and clients start right after a tick closes and run one
        # whole tick before the window opens (the first tick after idle
        # reads twice the lag of the rest); the window opens at a fixed
        # phase after the NEXT tick closes, so every run of a cell holds
        # the same number of ticks.
        c = await run.stats()
        c = await run.wait_tick_after(int(c.get("tick", 0)), 60.0)
        t_send = time.monotonic() + 0.05
        send_s = seconds + PRE_ROLL_MAX_S
        window = asyncio.ensure_future(snd.call("window", {
            "t0": t_send, "seconds": send_s,
            "period_s": period, "phase": phase,
            "sweep_period_s": wl.get("sweep_period_s"),
            "marker_period_s": probe["marker_period_ms"] / 1e3
            if probe else None,
            "probe_ids": fleet.probe_ids}))
        polls: list = []
        dash_log: list = []
        tasks = []
        stop = [float("inf")]          # the clients' end, set with t1
        if probe:
            tasks.append(asyncio.ensure_future(run.poller(
                clients[-1], probe["poll_period_ms"] / 1e3, stop, polls)))
        for k in range(n_dash):
            tasks.append(asyncio.ensure_future(run.dashboard(
                clients[k], k, reqs, wl["dashboards"]["think_ms"] / 1e3,
                stop, dash_log)))
        c = await run.wait_tick_after(int(c.get("tick", 0)), 60.0)
        t0 = time.monotonic() + TICK_PHASE_S
        t1 = stop[0] = t0 + seconds
        if t1 > t_send + send_s - 0.2:
            raise Failed(f"the tick before the window closed "
                         f"{t0 - t_send:.1f}s after the traffic began: the "
                         f"traffic would end inside the window")
        await asyncio.sleep(max(0.0, t0 - time.monotonic()))
        c0 = await run.stats()
        log_at = [os.path.getsize(srv.log_path)]
        setup_s = c0["_t"] - T_PROCESS_START
        log(f"window starts: {c0.get('xla_programs')} programs "
            f"({c0.get('xla_cache_hits', 0)} cache hits, "
            f"{c0.get('xla_cache_misses', 0)} misses), "
            f"{c0.get('xla_compile_ms', 0) / 1e3:.1f}s in the compiler")
        if args.fault:
            srv.tell("arm")
        if trace:
            tsec = min(float(wl["trace_seconds"]), seconds * 0.8)
            srv.tell(f"trace {max(0.0, (seconds - tsec) / 2.0):.3f} "
                     f"{tsec:.3f}")
        await asyncio.sleep(max(0.0, t1 - time.monotonic()))
        c1 = await run.stats()
        log_at.append(os.path.getsize(srv.log_path))
        if args.fault in ("drop_half", "drop_resp_batch"):
            srv.tell("disarm")
        wres = await window
        await asyncio.gather(*tasks)
        for qc in clients:
            qc.close()
        run.markers += wres["markers"]
        window_s = c1["_t"] - c0["_t"]
        events = sum(c1.get(k, 0) - c0.get(k, 0)
                     for k in ("conn_events", "resp_events"))
        log(f"window {window_s:.3f}s: "
            f"{c1.get('tick', 0) - c0.get('tick', 0):.0f} ticks, "
            f"{events} events, "
            f"{c1.get('xla_programs', 0) - c0.get('xla_programs', 0)} "
            f"programs compiled in it")

        # ------------------------------ after the window: drain, check
        num = recount.Numbers()
        totals = {k: wres[k] for k in ("rounds", "n_sweeps", "last_sweep",
                                       "seq", "warm")}
        want = run.built(totals)
        c = await run.wait_counters(want, 60.0)    # a minute past the close
        lost = {k: (c.get(k, 0), v) for k, v in want.items()
                if c.get(k, 0) != v}
        # what never came is counted once, above; the waits that follow
        # are for what is sent from here on
        short = {k: v - c.get(k, 0) for k, v in want.items()}
        num.add("accepted_off", len(lost), 0, f"(accepted, sent): {lost}")
        num.add("programs_in_window",
                c1.get("xla_programs", 0) - c0.get("xla_programs", 0), 0,
                srv.compiles_between(*log_at))
        log("drained")
        c = await run.wait_tick_after(int(c.get("tick", 0)), 60.0)
        peak = max([v for k, v in c.items()
                    if k.endswith("_peak_bytes_in_use")], default=0)
        # the check round: alone in one tick window, so the 5 s columns
        # can be recounted; tried again if a tick cut it
        check_tick = None
        for _try in range(3):
            tick = int(c.get("tick", 0))
            totals = await snd.call("once", "sweep", fleet.sweep_pool)
            totals = await snd.call("once", "round", fleet.pool)
            c = await run.wait_counters(
                {k: v - short[k] for k, v in run.built(totals).items()},
                60.0)
            cut = int(c.get("tick", 0)) != tick
            c = await run.wait_tick_after(tick, 60.0)
            if not cut:
                check_tick = tick
                break
            c = await run.wait_tick_after(int(c.get("tick", 0)), 60.0)
        answers = {}
        t_q = time.monotonic()
        q_s = {}
        for name, q in run.check_requests().items():
            t_1 = time.monotonic()
            answers[name] = await run.qc.query(q, timeout=120.0)
            q_s[name] = round(time.monotonic() - t_1, 2)
        c2 = await run.stats()
        log(f"check queries {time.monotonic() - t_q:.1f}s: {q_s}")
        mem = {k: v for k, v in c2.items() if k.startswith("device")}
        peak = max([peak] + [v for k, v in mem.items()
                             if k.endswith("_peak_bytes_in_use")])
        await snd.call("close")
        run.qc.close()
    except BaseException:
        log("---- server log tail ----")
        print(srv.log_tail(), file=sys.stderr, flush=True)
        raise
    finally:
        # the trace is written by the child: give it its time
        if trace and srv.proc.poll() is None:
            done = os.path.join(srv.trace_dir, "trace_done.json")
            t_w = time.monotonic()
            while not os.path.exists(done) \
                    and time.monotonic() - t_w < 120.0 \
                    and srv.proc.poll() is None:
                time.sleep(0.1)
        srv.stop()
        if snd is not None:
            snd.stop()

    # ------------------ the program's state is freed: now the reference
    t_r = time.monotonic()
    recount.compare(num, fleet, totals, answers, c2, cfg,
                    {"check_tick": check_tick, "sample": run.sample()})
    dash_in = [d for d in dash_log if t0 <= d["t"] < t1]
    if reqs or probe:
        recount.window_answers(num, fleet, dash_in, polls, run.markers)
    log(f"recount {time.monotonic() - t_r:.1f}s")

    # --------------------------------------------------------- metrics
    e2e = {"setup_s": setup_s,
           "ingest_events_per_s": events / window_s}
    lat = [d["ms"] for d in dash_in if d["ms"] is not None]
    q_failed = sum(d["ms"] is None for d in dash_in)
    if reqs:
        # an error or timeout is a failed query: it takes the worst
        # place in the order
        ordered = sorted(lat) + [float("inf")] * q_failed
        if len(ordered) >= 2:
            cuts = statistics.quantiles(ordered, n=100, method="inclusive")
            e2e["query_p50_ms"], e2e["query_p90_ms"] = cuts[49], cuts[89]
            e2e["query_p95_ms"], e2e["query_p99_ms"] = cuts[94], cuts[98]
    fr = fresh.summary(run.markers, polls, t0, t1) if probe else {}
    if "fresh_lag_ms" in fr:
        e2e["fresh_lag_ms"] = fr["fresh_lag_ms"]
    late = sorted(wres["late_s"])
    client = {
        "gen_blocked_share": 100.0 * sum(wres["blocked_s"])
        / (fleet.n_sockets * wres["elapsed_s"]),
        "gen_late_ms": 1e3 * late[int(0.95 * (len(late) - 1))]
        if late else None,
        "fresh_lag_max_ms": fr.get("fresh_lag_max_ms"),
        "fresh_ticks": fr.get("ticks"),
        "fresh_lags_ms": fr.get("lags_ms"),
        "query_p95_ms": e2e.get("query_p95_ms"),
        "query_p99_ms": e2e.get("query_p99_ms"),
        "queries": len(dash_in), "polls": len(polls)}
    tr = None
    if trace:
        tr = reduce_trace(srv.trace_dir)
    ctx = MetricCtx(c0, c1, window_s, client, tr, cfg, device, peak)
    return {"num": num, "e2e": e2e, "ctx": ctx, "device": device,
            "peak": peak, "trace": tr, "attempted":
            int(events + len(dash_in) + len(polls)),
            "failed": int(q_failed + len(lost)), "client": client}


def reduce_trace(trace_dir: str) -> dict:
    """``lib/trace_reduce.py`` in a process of its own (it imports jax,
    this one must not), on the CPU backend, after the child has gone."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "lib", "trace_reduce.py"),
         trace_dir], capture_output=True, text=True, env=env, timeout=300)
    if p.returncode != 0:
        raise Failed(f"trace reduction failed: {p.stdout[-400:]} "
                     f"{p.stderr[-800:]}")
    out = p.stdout.strip().splitlines()[-1]
    with open(os.path.join(trace_dir, "reduced.json"), "w") as f:
        f.write(out)
    tr = json.loads(out)
    if "busy_s" in tr:
        log(f"trace: window from the {tr['window_from']}, "
            f"{tr['window_s']:.6f}s, busy {tr['busy_s']:.6f}s; device ops "
            f"outside it {tr['busy_outside_s'][0]:.6f}s before, "
            f"{tr['busy_outside_s'][1]:.6f}s after")
        if tr["window_from"] != "marker":
            log("WARNING: the trace holds no bench_window marker of "
                "lib/child.py: its window is the device ops' own span")
    return tr


class MetricCtx:
    """What a per-layer reader (``benchmarks/metrics/<name>.py``) sees."""

    def __init__(self, c0, c1, window_s, client, trace, cfg, device, peak):
        self.c0, self.c1, self.window_s = c0, c1, window_s
        self.client, self.trace, self.cfg = client, trace, cfg
        self.device, self.peak_bytes = device, peak
        self.shapes = shapes

    def counter(self, name: str):
        """A counter's movement over the window (None: never written)."""
        if name not in self.c1:
            return None
        return self.c1[name] - self.c0.get(name, 0)

    def at_start(self, name: str):
        return self.c0.get(name)

    def timing(self, stage: str):
        """→ (count, total ms) of a selfstats stage over the window."""
        a = self.c1["_timings"].get(stage)
        if a is None:
            return None
        b = self.c0["_timings"].get(stage, (0, 0.0))
        n, ms = a[0] - b[0], a[1] - b[1]
        return (n, ms) if n > 0 else None

    def events(self) -> float:
        return sum(self.c1.get(k, 0) - self.c0.get(k, 0)
                   for k in ("conn_events", "resp_events"))

    def peaks(self) -> dict:
        """The chip's published peaks; an unknown chip is an error."""
        table = load_json(os.path.join(HERE, "lib", "peaks.json"))
        if self.device["kind"] not in table:
            raise Failed(f"device kind {self.device['kind']!r} is not in "
                         f"benchmarks/lib/peaks.json")
        return table[self.device["kind"]]

    def modules(self, prefix: str):
        """→ (executions, device seconds) of the traced programs whose
        name starts with ``prefix`` and that start inside the traced
        window, each with its whole duration; None where the trace has
        none."""
        if not self.trace or not self.trace.get("modules"):
            return None
        rows = [m for m in self.trace["modules"] if m[0].startswith(prefix)]
        if not rows:
            return None
        return sum(m[1] for m in rows), sum(m[2] for m in rows)

    def module_window_s(self, prefix: str) -> float:
        """Device seconds inside the traced window of the programs whose
        name starts with ``prefix``: what a share of the window counts."""
        inside = (self.trace or {}).get("module_window_s", {})
        return sum(s for k, s in inside.items() if k.startswith(prefix))


def read_metric(name: str, ctx: MetricCtx):
    path = os.path.join(HERE, "metrics", name + ".py")
    if not os.path.exists(path):
        raise Failed(f"per-layer metric {name!r} has no reader at {path}")
    spec = importlib.util.spec_from_file_location("metric_" + name.replace(
        ".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny CPU rehearsal (tests): counts and correct "
                    "only, no device metric")
    ap.add_argument("--fault", default="", help="tests and fault "
                    "readings: plant a fault of lib/faults.py under the "
                    "timed path")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "gyeeta_tpu")):
        log(f"FAILED: no gyeeta_tpu package beside {HERE}")
        return 2
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        log(f"FAILED: no cell {args.workload!r} in BENCHMARK.json")
        return 2
    cell = cells[args.workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(os.path.join(ROOT, cfg_entry["file"]))
    wl = load_json(os.path.join(HERE, "workloads", cell["name"] + ".json"))
    work = os.path.join(HERE, ".work", cell["name"])
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    t0 = time.monotonic()
    try:
        res = asyncio.run(run_cell(args, cell, cfg, wl, work))
    except Failed as e:
        log(f"FAILED after {time.monotonic() - t0:.0f}s: {e}")
        return 1
    if "jax" in sys.modules:
        log("FAILED: the benchmark's parent imported jax")
        return 1

    # ------------------------------------------------------ result line
    num, ctx = res["num"], res["ctx"]
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in bench[kind]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        if kind == "end_to_end":
            v = res["e2e"].get(m["name"])
        else:
            v = read_metric(m["name"], ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {**res["device"], "memory_peak_bytes": int(res["peak"])}
    out = {"correct": num.correct, "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics, "device": device}
    tr = res["trace"]
    if args.trace and tr:
        if not tr.get("busy_s") and not args.rehearse_cpu:
            log("FAILED: the traced window shows no operation on the "
                "device")
            return 1
        if tr.get("busy_s"):
            device["busy_s"] = tr["busy_s"]
            device["window_s"] = tr["window_s"]
            out["breakdown"] = {
                "device_ops": [[n[:100], t] for n, t in
                               tr["device_ops"][:10]],
                "idle_gaps": [[n[:100], t] for n, t in
                              tr["idle_gaps"][:10]]}
    if args.rehearse_cpu:
        # a rehearsal has no device numbers: counts and correct only
        out["metrics"] = {}
        out["rehearsal"] = {"events": ctx.events(), **res["client"]}
    out["checks"] = num.table()
    log(f"client {json.dumps(res['client'])}")
    log(f"e2e {json.dumps(res['e2e'])}")
    for line in num.lines():
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
