"""device.idle_share: 1 - the union of device-operation intervals over the
traced window, as the mean over the cell's chips, in percent."""
from benchmarks.chip import trace as trace_mod


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.device_names() or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace_mod.mean_busy_s(tr) / tr.window_s)
