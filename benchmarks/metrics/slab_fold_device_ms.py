"""Device milliseconds of one fused fold that holds the conn/resp slab, from
the trace: the ``XLA Modules`` events of ``jit_fn_connresp*``
(runtime.py:fold_all_name) over their number. The tick's flush runs the
same function at the one-microbatch shape. Layer: fold, device."""

MODULE = "jit_fn_connresp"


def read(ctx):
    m = ctx.modules(MODULE)
    return None if m is None or not m[0] else 1e3 * m[1] / m[0]
