"""1 - (union of device-op intervals) / traced window, both over the
``bench_window`` marker's interval on the trace's clock (lib/trace_reduce.py
clips the ops to it). Layer: device."""


def read(ctx):
    t = ctx.trace
    if not t or not t.get("busy_s") or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
