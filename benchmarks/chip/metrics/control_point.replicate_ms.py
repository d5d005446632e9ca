"""control_point.replicate_ms: mean, over the window's control points, of
the ``ftp.coord.replicate`` span: the Sec. III-E replication round, from
the ``replicate`` messages to the last ack and the durable sync."""
from benchmarks.chip import spans


def read(ctx):
    return spans.mean_control_ms(ctx, "replicate_s")
