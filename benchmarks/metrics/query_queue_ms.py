"""Mean milliseconds of one ``query_queue`` interval in the window: request
decoded on the loop (net/server.py:_query_loop_inner) to the first line of
the worker that runs it (net/qexec.py:_call) - admission queue + executor
hand-off. Every query, the freshness polls included. Layer: query."""


def read(ctx):
    t = ctx.timing("query_queue")
    return None if t is None else t[1] / t[0]
