"""Arrays handed to ``jax.device_put`` per fused dispatch in the window:
counter ``h2d_arrays`` / counter ``fold_dispatches``
(runtime.py:_dispatch_fused, span ``fold_h2d``). 1 where every dispatch
carries its sections as one packed block; a slab fold of separate columns
would read 22. None where the program never wrote the counter. Layer:
staging + dispatch."""


def read(ctx):
    arrays = ctx.counter("h2d_arrays")
    dispatches = ctx.counter("fold_dispatches")
    if arrays is None or not dispatches:
        return None
    return arrays / dispatches
