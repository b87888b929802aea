"""Mean host milliseconds of one ``snapshot_publish`` in the window.
Layer: tick."""


def read(ctx):
    t = ctx.timing("snapshot_publish")
    return None if t is None else t[1] / t[0]
