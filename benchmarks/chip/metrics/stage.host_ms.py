"""stage.host_ms: the workers' host seconds in the segments done in the
window (each segment's wall time less its ``fwd``/``step`` and ``wait``
spans: sends, data, stash, dispatch), summed over workers, per batch
committed in the window."""
from benchmarks.chip import spans


def read(ctx):
    return spans.stage_ms_per_batch(ctx, "host_s")
