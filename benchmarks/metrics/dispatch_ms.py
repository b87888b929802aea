"""Mean host milliseconds of one ``fold_dispatch`` (staging + enqueue of
one fused fold; an enqueue, not a device completion). Layer: staging +
dispatch."""


def read(ctx):
    t = ctx.timing("fold_dispatch")
    return None if t is None else t[1] / t[0]
