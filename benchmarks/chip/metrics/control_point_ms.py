"""control_point_ms: mean gap, over the window's control points, from the
last commit of a segment to the first commit of the next one: the drain,
the control work (replication) and the refill of the pipeline."""
from benchmarks.chip import window


def read(ctx):
    gaps = window.control_point_gaps(
        ctx.result.commit_times, ctx.batches,
        [ctx.cell["chain_every"], ctx.cell["global_every"]])
    return 1000.0 * sum(gaps) / len(gaps) if gaps else None
