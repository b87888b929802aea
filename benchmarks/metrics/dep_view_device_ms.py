"""Device milliseconds of one build of the dependency view, from the
trace: the ``XLA Modules`` events of ``jit_dep_edges_snapshot``
(query/readback.py) over their number. Layer: query."""

MODULE = "jit_dep_edges_snapshot"


def read(ctx):
    m = ctx.modules(MODULE)
    return None if m is None or not m[0] else 1e3 * m[1] / m[0]
