"""Seconds inside the XLA compiler (or its cache reads) before the window:
``xla_compile_ms`` (obs/xlamon.py) at the window's start. Layer: entry /
start-up."""


def read(ctx):
    ms = ctx.at_start("xla_compile_ms")
    return None if ms is None else ms / 1e3
