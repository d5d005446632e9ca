"""recover.probe_s: the ``ftp.coord.probe`` span that decided the
recovery (Sec. III-F: every worker probed, the dead one waited out to the
deadline), from the trace."""
from benchmarks.chip import spans


def read(ctx):
    return None if ctx.trace is None else spans.recovery_probe_s(ctx.trace)
