"""Mean host milliseconds of one ``dep_view`` in the window: one build of
a snapshot's dependency-edge columns (query/api.py:dep_edges_view) - the
device program over the edge slab and the readback of its eight columns.
The first reader of a fresh snapshot also waits here for the folds queued
ahead of the program. Nothing where the program has no such span. Layer:
query."""


def read(ctx):
    t = ctx.timing("dep_view")
    return None if t is None else t[1] / t[0]
