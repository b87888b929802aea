"""Peak device memory (``device<i>_peak_bytes_in_use``, fullest chip) in
GiB: three state copies at a publish (ROADMAP S6). Layer: tick."""


def read(ctx):
    return ctx.peak_bytes / 2**30 if ctx.peak_bytes else None
