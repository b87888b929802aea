"""What this process compiled, what it loaded, and what the device holds.

:class:`XlaMonitor` listens to jax's own monitoring events and keeps
four counters in a ``Stats`` registry, so they ride ``selfstats``,
``/metrics`` and the cadence log like every other counter:

- ``xla_programs``     — programs handed to the backend (one per
  distinct traced program; a persistent-cache hit counts, it is still
  a program this process needed)
- ``xla_compile_ms``   — wall time inside those calls (compile, or the
  cache read where it hit)
- ``xla_cache_hits`` / ``xla_cache_misses`` — the persistent
  compilation cache's answer per program (``utils/xlacache.py``
  caches every program, so hits + misses == programs)

A compile inside the serving window shows as ``xla_programs`` moving
between two ticks. Listeners are process-wide in jax and cannot be
removed singly, so create ONE monitor per process, before the first
compile, and :meth:`attach` the runtime's registry once it exists.

:func:`device_gauges` reads ``memory_stats()`` of every local device
(None on the CPU backend — then no gauge is written).
"""

from __future__ import annotations

import jax

from gyeeta_tpu.utils.selfstats import Stats

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_EVENT_COUNTERS = {
    "/jax/compilation_cache/cache_hits": "xla_cache_hits",
    "/jax/compilation_cache/cache_misses": "xla_cache_misses",
}


class XlaMonitor:
    def __init__(self):
        # until a runtime exists (state init compiles too) counts land
        # in a registry of our own; attach() carries them over
        self.stats = Stats()
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event: str, **_kw) -> None:
        name = _EVENT_COUNTERS.get(event)
        if name is not None:
            self.stats.bump(name)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == _BACKEND_COMPILE:
            self.stats.bump("xla_programs")
            self.stats.bump("xla_compile_ms", round(secs * 1e3, 3))

    def attach(self, stats: Stats) -> None:
        """Count into ``stats`` from now on, starting from what was
        counted so far."""
        old, self.stats = self.stats, stats
        for k, v in old.export()[0].items():
            stats.bump(k, v)


def device_info() -> dict:
    """The backend jax took, as jax reports it — the start-up log line
    and the three ``serverstatus`` columns."""
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "devicekind": devs[0].device_kind,
            "ndevices": len(devs)}


def device_gauges() -> dict:
    """``device<i>_bytes_in_use`` / ``device<i>_peak_bytes_in_use`` for
    every local device whose backend reports them."""
    out = {}
    for d in jax.local_devices():
        ms = d.memory_stats()
        if not ms:
            continue
        for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
            if k in ms:
                out[f"device{d.id}_{k}"] = float(ms[k])
    return out
