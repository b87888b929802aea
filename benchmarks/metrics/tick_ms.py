"""Mean host milliseconds of one ``tick`` (runtime.py:_run_tick) in the
window. Layer: tick."""


def read(ctx):
    t = ctx.timing("tick")
    return None if t is None else t[1] / t[0]
