"""Mean host milliseconds of one ``slab_wait`` in the window: the serving
loop blocked in ``block_until_ready`` on the fold that last consumed a
staging buffer, before it may decode into it again
(runtime.py:_dispatch_fused). Near the slab fold's device time where the
device sets the pace, near 0 where the host does. Layer: staging +
dispatch."""


def read(ctx):
    t = ctx.timing("slab_wait")
    return None if t is None else t[1] / t[0]
