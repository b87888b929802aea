"""Wait for what a test needs instead of sleeping a fixed time: a fixed
sleep is too short on a loaded machine and wasted on an idle one."""

import asyncio
import time


async def until(cond, timeout: float = 20.0, what: str = "condition"):
    """Poll ``cond()`` on the running loop until it holds; fail at the
    deadline."""
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        await asyncio.sleep(0.005)


def until_sync(cond, timeout: float = 20.0, what: str = "condition"):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.005)


def counter(rt, name: str) -> int:
    return int(rt.stats.counters.get(name, 0))


def fed_bytes(rt) -> int:
    """Bytes handed to ``rt.feed`` so far (its span rows, while the ring
    holds them all: a test's few hundred spans)."""
    return sum(r["nrec"] for r in rt.spans.rows(last=1 << 20)
               if r["name"] == "feed")


async def send_sweep_fed(rt, agent, **kw) -> None:
    """One sweep of a ``collect=True`` agent, fed to its last byte: which
    frame ends such a sweep depends on the machine, so count bytes. The
    agent's announce (it ends with HOST_INFO) has to be in first."""
    await until(lambda: counter(rt, "host_infos") >= 1, what="announce")
    buf = agent.build_sweep(**kw)
    want = fed_bytes(rt) + len(buf)
    agent._writer.write(buf)
    await agent._writer.drain()
    await until(lambda: fed_bytes(rt) >= want, what="the sweep's bytes")


async def sweeps_fed(rt, n: int) -> None:
    """Wait until the runtime has been fed ``n`` whole agent sweeps, all
    told. A conn's bytes are fed in order and a ``NetAgent`` sweep
    (without ``collect=True``) ends with its one CPU_MEM_STATE record,
    so the count of those is the count of sweeps fed to the end."""
    await until(lambda: counter(rt, "cpumem_records") >= n,
                what=f"{n} sweeps")
