"""Calls of ``feed`` (the count of stage ``feed``) per conn/resp slab
dispatched in the window (counter ``slab_dispatches``): how many runs of
socket bytes the serving edge hands the runtime while one slab fills.
One read of a socket is one call, so it falls as the reads grow. Layer:
socket edge."""


def read(ctx):
    t = ctx.timing("feed")
    slabs = ctx.counter("slab_dispatches")
    if t is None or not slabs:
        return None
    return t[0] / slabs
