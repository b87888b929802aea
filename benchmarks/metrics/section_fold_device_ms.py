"""Device milliseconds of one fused fold WITHOUT the conn/resp slab (a
listener sweep, a freshness marker), from the trace: the ``XLA Modules``
events of ``jit_fn_sections*`` (runtime.py:fold_all_name) over their
number. It costs as much for one record as for 4,096. Layer: fold,
device."""

MODULE = "jit_fn_sections"


def read(ctx):
    m = ctx.modules(MODULE)
    return None if m is None or not m[0] else 1e3 * m[1] / m[0]
