"""control_point.refill_ms: mean, over the window's control points, of
the ``ftp.coord.refill`` span: the end of the control work (the next
segment's planning and messages) to the next segment's first commit."""
from benchmarks.chip import spans


def read(ctx):
    return spans.mean_control_ms(ctx, "refill_s")
