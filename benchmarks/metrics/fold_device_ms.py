"""Device milliseconds of one fused fold program, from the trace: the
``XLA Modules`` events of ``jit_fn`` (runtime.py:_get_fold_all — the only
jitted function of that name) over their number. Layer: fold, device."""

FOLD_MODULE = "jit_fn"


def read(ctx):
    m = ctx.modules(FOLD_MODULE)
    return None if m is None or not m[0] else 1e3 * m[1] / m[0]
