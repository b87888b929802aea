"""Mean host milliseconds of one ``tick.flush`` in the window: the tick
folding what was staged but not yet dispatched (runtime.py:_run_tick). An
enqueue, unless a staging buffer is still in use. Layer: tick."""


def read(ctx):
    t = ctx.timing("tick.flush")
    return None if t is None else t[1] / t[0]
