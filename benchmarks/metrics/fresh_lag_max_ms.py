"""The largest per-tick freshness lag of the window (lib/fresh.py).
Layer: tick."""


def read(ctx):
    return ctx.client.get("fresh_lag_max_ms")
