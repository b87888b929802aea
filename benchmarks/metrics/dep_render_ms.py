"""Mean host milliseconds of one ``dep_render`` in the window: rows to
answer for one ``svcdependency`` request over the built view
(query/snapshot.py:_render, inside ``query_render``) - filter, sort or
group, cut to ``maxrecs``, strings for the rows returned. Nothing where the
program has no such span. Layer: query."""


def read(ctx):
    t = ctx.timing("dep_render")
    return None if t is None else t[1] / t[0]
