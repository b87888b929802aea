"""Events taken in over the window per conn/resp lane dispatched: events /
(``slab_dispatches`` x ``fold_k`` x (``conn_batch`` + ``resp_batch``)).
``slab_dispatches`` counts the fused dispatches that carried a conn/resp
slab (``fold_dispatches`` also counts the sweep-only ones). Layer: staging
+ dispatch."""


def read(ctx):
    n = ctx.counter("slab_dispatches")
    if not n:
        return None
    e = ctx.cfg["engine"]
    lanes = e["fold_k"] * (e["conn_batch"] + e["resp_batch"])
    return 100.0 * ctx.events() / (n * lanes)
