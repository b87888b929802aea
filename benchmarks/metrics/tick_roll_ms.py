"""Mean host milliseconds of one ``tick.roll`` in the window: the window-
tick readback (a device sync), dependency ageing and the registry sweeps
(runtime.py:_run_tick). Layer: tick."""


def read(ctx):
    t = ctx.timing("tick.roll")
    return None if t is None else t[1] / t[0]
