"""recover.load_s: the union of each survivor's first ``fwd`` and first
``step`` span after the ``ftp.coord.recover`` span ends, from the trace:
loading the survivors' new stage programs, with their first batch."""
from benchmarks.chip import spans


def read(ctx):
    return None if ctx.trace is None else spans.recovery_load_s(ctx.trace)
