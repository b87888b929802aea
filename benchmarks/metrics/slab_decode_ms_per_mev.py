"""Host milliseconds in ``slab_decode`` (runtime.py:_dispatch_fused: the
columnar decode of staged records into the staging buffers, section
builders included) per million events taken in over the window. Layer:
deframe / decode."""


def read(ctx):
    t = ctx.timing("slab_decode")
    ev = ctx.events()
    if t is None or ev <= 0:
        return None
    return t[1] / (ev / 1e6)
