"""Mean host milliseconds of one ``query_render`` in the window: a result-
cache miss (query/snapshot.py:_render) - column compute, device to host,
row assembly. The first reader of a fresh snapshot also waits here for the
device to finish the publish copy. Layer: query."""


def read(ctx):
    t = ctx.timing("query_render")
    return None if t is None else t[1] / t[0]
