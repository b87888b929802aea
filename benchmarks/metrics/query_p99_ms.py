"""99th percentile of the window's dashboard queries, client side: the
queries that met a tick. About 565 queries a window leave it five samples,
and it swings by a quarter from run to run (PERF.md section 2). Layer:
query."""


def read(ctx):
    return ctx.client.get("query_p99_ms")
