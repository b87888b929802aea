"""Mean host milliseconds of one ``tick.td_drain`` in the window: the
bounded digest-stage drain of a tick, one blocking pressure readback per
iteration, so it absorbs the device time of the folds queued before it
(runtime.py:_run_tick). Layer: tick."""


def read(ctx):
    t = ctx.timing("tick.td_drain")
    return None if t is None else t[1] / t[0]
