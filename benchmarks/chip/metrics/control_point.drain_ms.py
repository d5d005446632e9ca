"""control_point.drain_ms: mean, over the window's control points (those
``control_point_ms`` averages), of the ``ftp.coord.drain`` span: the
segment's last commit to its last ``seg_done``."""
from benchmarks.chip import spans


def read(ctx):
    return spans.mean_control_ms(ctx, "drain_s")
