"""stage.replicate_ms: the wall time of every ``ftp.w{d}.replicate`` span
in the traced window (the Sec. III-E rounds each worker runs inside its
segment, and any drained replication), summed over workers, per batch
committed in the traced span."""
from benchmarks.chip import spans


def read(ctx):
    if ctx.trace is None or not ctx.traced_batches:
        return None
    total = spans.worker_span_s(ctx.trace, "replicate")
    return 1000.0 * total / len(ctx.traced_batches) if total > 0 else None
