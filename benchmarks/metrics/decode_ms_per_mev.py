"""Host milliseconds in ``deframe`` + ``decode_fold`` (utils/selfstats.py
stage timings) per million events taken in over the window. Layer:
deframe / decode."""


def read(ctx):
    ev = ctx.events()
    stages = [ctx.timing(s) for s in ("deframe", "decode_fold")]
    stages = [s for s in stages if s]
    if not stages or ev <= 0:
        return None
    return sum(ms for _n, ms in stages) / (ev / 1e6)
