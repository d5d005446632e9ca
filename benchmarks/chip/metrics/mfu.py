"""mfu: 3 x the forward FLOPs per sample (the configuration's count; the
recompute in the fused step does not count) x the window's samples/s, over
the chips' bf16 peak, in percent."""
from benchmarks.chip import window


def read(ctx):
    if ctx.peaks is None:
        return None
    rate = window.samples_per_s(ctx.result.commit_times, ctx.t_open,
                                ctx.seconds, ctx.cell["batch"])
    flops = 3.0 * ctx.reference.forward_flops_per_sample(**ctx.cell["spec"])
    return 100.0 * flops * rate / (ctx.chips * ctx.peaks["flops_per_s"])
