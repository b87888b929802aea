"""Staged table probes of the window that overflowed their residue and
took the full-width path (unknown keys, or a slab above its sizing load):
the movement of the device counter ``engine_probe_fallbacks``
(engine/table.py:lookup_counted). Nothing to read on a program without the
staged probe. Layer: fold, device."""


def read(ctx):
    return ctx.counter("engine_probe_fallbacks")
