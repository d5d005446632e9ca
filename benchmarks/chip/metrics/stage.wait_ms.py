"""stage.wait_ms: the workers' ``ftp.w{d}.wait`` seconds (waiting for an
activation or a gradient) in the segments done in the window, summed over
workers, per batch committed in the window."""
from benchmarks.chip import spans


def read(ctx):
    return spans.stage_ms_per_batch(ctx, "wait_s")
