"""The load generator's sender process: plain sockets, pre-built bytes.

Started by ``run.py`` with ``multiprocessing`` (spawn). It holds every
agent socket of the cell in one asyncio loop, obeys commands that arrive
over a pipe, and keeps the send log: what was sent how often (the
recount's multiplicities), when each freshness marker left, how late each
scheduled write started and how long the writers waited in ``drain()``.
It builds nothing but the freshness markers; all other bytes arrive
pre-built from the parent. Clocks are ``time.monotonic()``, which all
processes of a host share.
"""

from __future__ import annotations

import asyncio
import time

from . import gen
from . import proto as P


class Sender:
    def __init__(self, spec: dict):
        self.spec = spec
        self.bufs = spec["bufs"]
        self.n = len(self.bufs)
        self.conns: list = []
        self.pool = len(self.bufs[0]["rounds"]) - 1
        self.sweep_pool = len(self.bufs[0]["sweeps"]) - 1
        # cumulative: rounds[k][slot], sweeps sent and the last slot sent
        self.rounds = [[0] * (self.pool + 1) for _ in range(self.n)]
        self.n_sweeps = [0] * self.n
        self.last_sweep = [-1] * self.n
        self.next_round = [0] * self.n
        self.next_sweep = [0] * self.n
        self.seq = 0                   # last freshness marker sent
        self.warm = {k: 0 for k in self.bufs[0].get("warm", {})}

    async def connect(self) -> dict:
        ids = []
        for k in range(self.n):
            reader, writer, hid = await P.register(
                self.spec["host"], self.spec["port"],
                self.spec["machine_base"] + k, P.CONN_EVENT)
            self.conns.append((reader, writer))
            ids.append(hid)
        return {"host_ids": ids}

    async def _write_all(self, pick) -> None:
        for k, (_r, w) in enumerate(self.conns):
            w.write(pick(k))
        await asyncio.gather(*(w.drain() for _r, w in self.conns))

    async def inventory(self) -> dict:
        await self._write_all(lambda k: self.bufs[k]["inventory"])
        return {}

    def _round(self, k: int, slot: int) -> bytes:
        self.rounds[k][slot] += 1
        return self.bufs[k]["rounds"][slot]

    def _sweep(self, k: int, slot: int) -> bytes:
        self.n_sweeps[k] += 1
        self.last_sweep[k] = slot
        return self.bufs[k]["sweeps"][slot]

    async def once(self, what: str, slot: int) -> dict:
        """One buffer on every socket: a warm-up or the check round."""
        pick = self._round if what == "round" else self._sweep
        await self._write_all(lambda k: pick(k, slot))
        return self.totals()

    async def warm_once(self, names: list) -> dict:
        """Socket 0's named warm-up pieces, in ONE write."""
        w = self.conns[0][1]
        for n in names:
            self.warm[n] += 1
        w.write(b"".join(self.bufs[0]["warm"][n] for n in names))
        await w.drain()
        return self.totals()

    def totals(self) -> dict:
        return {"rounds": self.rounds, "n_sweeps": self.n_sweeps,
                "last_sweep": self.last_sweep, "seq": self.seq,
                "warm": self.warm}

    # ------------------------------------------------------------ window
    async def window(self, w: dict) -> dict:
        """Drive the cell's traffic from ``t0`` to ``t0 + seconds``."""
        t0, t_end = w["t0"], w["t0"] + w["seconds"]
        late: list = []
        blocked = [0.0] * self.n
        markers: list = []
        drains = [asyncio.ensure_future(self._discard(r))
                  for r, _w in self.conns]
        tasks = []
        for k in range(self.n):
            if w["period_s"] is None:
                tasks.append(self._flood(k, t0, t_end, blocked))
            else:
                tasks.append(self._paced(
                    k, t0 + w["phase"][k] * w["period_s"], t_end,
                    w["period_s"], blocked, late))
            if w["sweep_period_s"]:
                tasks.append(self._sweeps(
                    k, t0 + w["phase"][k] * w["sweep_period_s"], t_end,
                    w["sweep_period_s"]))
        if w["marker_period_s"]:
            tasks.append(self._markers(t0, t_end, w["marker_period_s"],
                                       w["probe_ids"], markers))
        try:
            await asyncio.gather(*tasks)
            await asyncio.gather(*(wr.drain() for _r, wr in self.conns))
        finally:
            for d in drains:
                d.cancel()
            await asyncio.gather(*drains, return_exceptions=True)
        return {**self.totals(), "late_s": late, "blocked_s": blocked,
                "markers": markers,
                "elapsed_s": time.monotonic() - t0}

    @staticmethod
    async def _discard(reader) -> None:
        """The server may write to an agent (admission control, capture
        control); a relay would act on it, this generator only reads."""
        while await reader.read(65536):
            pass

    async def _sleep_until(self, t: float) -> None:
        d = t - time.monotonic()
        if d > 0:
            await asyncio.sleep(d)

    async def _flood(self, k, t0, t_end, blocked) -> None:
        w = self.conns[k][1]
        await self._sleep_until(t0)
        while time.monotonic() < t_end:
            slot = self.next_round[k] % self.pool
            self.next_round[k] += 1
            w.write(self._round(k, slot))
            t = time.monotonic()
            await w.drain()
            blocked[k] += time.monotonic() - t

    async def _paced(self, k, t_first, t_end, period, blocked, late) -> None:
        w = self.conns[k][1]
        due = t_first
        while due < t_end:
            await self._sleep_until(due)
            t = time.monotonic()
            late.append(t - due)
            slot = self.next_round[k] % self.pool
            self.next_round[k] += 1
            w.write(self._round(k, slot))
            await w.drain()
            blocked[k] += time.monotonic() - t
            due += period

    async def _sweeps(self, k, t_first, t_end, period) -> None:
        w = self.conns[k][1]
        due = t_first
        while due < t_end:
            await self._sleep_until(due)
            slot = self.next_sweep[k] % self.sweep_pool
            self.next_sweep[k] += 1
            w.write(self._sweep(k, slot))
            await w.drain()
            due += period

    async def _markers(self, t0, t_end, period, probe_ids, markers) -> None:
        w = self.conns[0][1]
        due = t0
        while due < t_end:
            await self._sleep_until(due)
            self.seq += 1
            w.write(gen.probe_frame(probe_ids, self.seq))
            markers.append((self.seq, time.monotonic()))
            due += period

    async def markers_once(self, probe_ids) -> dict:
        """One marker per probe outside a window (warm-up: creates the
        probe rows)."""
        w = self.conns[0][1]
        out = []
        for _ in range(len(probe_ids)):
            self.seq += 1
            w.write(gen.probe_frame(probe_ids, self.seq))
            out.append((self.seq, time.monotonic()))
        await w.drain()
        return {"markers": out}

    async def close(self) -> dict:
        for _r, w in self.conns:
            w.close()
        for _r, w in self.conns:
            try:
                await w.wait_closed()
            except (ConnectionError, OSError):
                pass
        return {}


def main(pipe, spec: dict) -> None:
    """Process entry: ``(command, args)`` in, ``("ok", reply)`` or
    ``("err", text)`` out, until ``close``."""
    loop = asyncio.new_event_loop()
    sender = Sender(spec)
    try:
        while True:
            cmd, args = pipe.recv()
            try:
                reply = loop.run_until_complete(
                    getattr(sender, cmd)(*args))
                pipe.send(("ok", reply))
            except Exception as e:      # reported to the parent, which fails
                pipe.send(("err", f"{type(e).__name__}: {e}"))
            if cmd == "close":
                return
    except (EOFError, KeyboardInterrupt):
        pass
    finally:
        loop.close()
