"""Mean server-side milliseconds of one ``query`` in the window
(net/qexec.py; every query, the freshness polls included). Layer:
query."""


def read(ctx):
    t = ctx.timing("query")
    return None if t is None else t[1] / t[0]
