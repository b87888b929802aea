"""Mean host milliseconds of one ``fold_h2d`` in the window: the
``jax.device_put`` of a fused fold's sections (``shard_args`` lives here;
runtime.py:_dispatch_fused). Layer: staging + dispatch."""


def read(ctx):
    t = ctx.timing("fold_h2d")
    return None if t is None else t[1] / t[0]
