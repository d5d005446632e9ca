"""device.idle_in_control_share: the share of the chips' idle time in the
traced window that falls inside the coordinator's ``drain``,
``replicate`` and ``refill`` spans, in percent."""
from benchmarks.chip import spans


def read(ctx):
    return None if ctx.trace is None else spans.idle_in_control_share(
        ctx.trace)
