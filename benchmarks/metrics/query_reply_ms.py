"""Mean milliseconds of one ``query_reply`` interval in the window: answer
computed on the worker (net/qexec.py:_call) to its last frame drained by
the loop (net/server.py:_query_loop_inner) - the wait for the loop, the
JSON encode and the write. Every query, the freshness polls included.
Layer: query."""


def read(ctx):
    t = ctx.timing("query_reply")
    return None if t is None else t[1] / t[0]
