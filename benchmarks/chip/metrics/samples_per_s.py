"""samples_per_s: batch size x the distinct batches whose last commit falls
in the window, over the window's seconds (host clock at the coordinator)."""
from benchmarks.chip import window


def read(ctx):
    return window.samples_per_s(ctx.result.commit_times, ctx.t_open,
                                ctx.seconds, ctx.cell["batch"])
