"""The least time the chip could take for one build of the dependency
view (one read of the edge slab's eight columns and one write of what the
program returns, lib/depshapes.py, over the peaks of lib/peaks.json) as a
share of the program's measured device time (modules
``jit_dep_edges_snapshot``). Memory-bound. Layer: query."""

from lib import depshapes       # benchmarks/ is on the harness's path

MODULE = "jit_dep_edges_snapshot"


def read(ctx):
    m = ctx.modules(MODULE)
    if m is None or not m[0] or not m[1]:
        return None
    needs = depshapes.view_needs(ctx.cfg["runtime"])
    least, _bound = ctx.shapes.least_seconds(needs, ctx.peaks())
    return 100.0 * least / (m[1] / m[0])
