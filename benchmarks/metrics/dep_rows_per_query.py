"""Rows whose id and name strings were rendered per ``svcdependency``
render of the window: counter ``dep_rows_materialised``
(query/api.py:dep_cols_from_edges) over the count of stage ``dep_render``.
At most ``maxrecs`` (100) where strings are made for the rows returned; the
slab's width where they are made before the filter and the sort. Nothing
where the program never wrote the counter. Layer: query."""


def read(ctx):
    rows = ctx.counter("dep_rows_materialised")
    t = ctx.timing("dep_render")
    if rows is None or t is None:
        return None
    return rows / t[0]
