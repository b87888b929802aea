"""95th percentile of (actual - due) start of the generator's scheduled
writes: a starved generator must not read as a fast server. Layer: load
generator."""


def read(ctx):
    return ctx.client.get("gen_late_ms")
