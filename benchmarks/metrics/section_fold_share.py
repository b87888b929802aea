"""Share of the traced window the device spent in section-only folds: the
device seconds of ``jit_fn_sections*`` (runtime.py:fold_all_name) inside
the window over the window (the ``bench_window`` marker's interval, on the
trace's clock). Layer: fold, device."""

MODULE = "jit_fn_sections"
NAMED = "jit_fn_"       # a program that names its fold variants


def read(ctx):
    t = ctx.trace
    if not t or not t.get("window_s") or ctx.modules(NAMED) is None:
        return None
    return 100.0 * ctx.module_window_s(MODULE) / t["window_s"]
