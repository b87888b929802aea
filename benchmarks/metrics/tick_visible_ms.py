"""Mean milliseconds from ``run_tick`` entry to the snapshot swap in
``publish_snapshot`` (the ``tick_visible`` interval, runtime.py): the part
of a tick that delays what a dashboard can see; the rest of ``tick_ms``
only holds the loop. Layer: tick."""


def read(ctx):
    t = ctx.timing("tick_visible")
    return None if t is None else t[1] / t[0]
