"""Builds of the dependency view per tick of the window: counter
``dep_view_builds`` (query/api.py:dep_edges_view) over the movement of the
``tick`` gauge. 1 is right: every request of a snapshot shares one build;
3 would mean the three dependency requests of the cycle each build their
own. Nothing where the program never wrote the counter. Layer: query."""


def read(ctx):
    builds = ctx.counter("dep_view_builds")
    ticks = ctx.counter("tick")
    if builds is None or not ticks:
        return None
    return builds / ticks
