"""Host milliseconds in stage ``edge_rx`` (net/server.py:_EventConn: the
frame-boundary scan over a read's bytes and the move of the trailing
partial frame to the front of the conn's receive buffer; it never
contains ``feed``) per million events taken in over the window. None
where the program has no such span. Layer: socket edge."""


def read(ctx):
    t = ctx.timing("edge_rx")
    ev = ctx.events()
    if t is None or ev <= 0:
        return None
    return t[1] / (ev / 1e6)
