"""Share of the traced window the device spent in section-only folds: the
device seconds of ``jit_fn_sections*`` (runtime.py:fold_all_name) over the
traced window. Layer: fold, device."""

MODULE = "jit_fn_sections"
NAMED = "jit_fn_"       # a program that names its fold variants


def read(ctx):
    t = ctx.trace
    if not t or not t.get("window_s") or ctx.modules(NAMED) is None:
        return None
    m = ctx.modules(MODULE)
    return 100.0 * (m[1] if m else 0.0) / t["window_s"]
