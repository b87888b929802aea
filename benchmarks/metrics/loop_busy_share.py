"""Share of the window the serving loop's thread was held by its own
top-level spans: ``feed`` (socket read to dispatch), ``tick``,
``tick_push`` and ``query_encode`` (obs/spans.py; the stages nest under
none of each other). What is left is the loop waiting for input, or in
code no span covers (frame reads, admission). Layer: socket edge."""

STAGES = ("feed", "tick", "tick_push", "query_encode")


def read(ctx):
    if ctx.timing("feed") is None:      # a program without these spans
        return None
    held = [ctx.timing(s) for s in STAGES]
    ms = sum(t[1] for t in held if t)
    return 100.0 * ms / (ctx.window_s * 1e3)
