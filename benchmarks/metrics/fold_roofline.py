"""The least time the chip could take for one dispatch (bytes and
operations from shapes, lib/shapes.py, over the peaks of lib/peaks.json)
as a share of the fold program's measured device time. Memory-bound by
the count in lib/shapes.py. Layer: fold, device."""

FOLD_MODULE = "jit_fn"


def read(ctx):
    m = ctx.modules(FOLD_MODULE)
    n = ctx.counter("fold_dispatches")
    if m is None or not m[0] or not m[1] or not n:
        return None
    conn = ctx.counter("conn_events") / n
    resp = ctx.counter("resp_events") / n
    needs = ctx.shapes.fold_needs(ctx.cfg["engine"], conn, resp)
    least, _bound = ctx.shapes.least_seconds(needs, ctx.peaks())
    return 100.0 * least / (m[1] / m[0])
