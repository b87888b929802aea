"""95th percentile of the window's dashboard queries, client side. A tick
holds the serving thread about a twentieth of the time, so the 95th
percentile sits on the edge between the queries that met a tick and those
that did not: it swings by 15-24 % from run to run (PERF.md section 2), too
wide for a bound; the 90th is the end-to-end metric. Layer: query."""


def read(ctx):
    return ctx.client.get("query_p95_ms")
