"""recover.detect_s: ``KILL worker`` event to ``failure detected``: the
fault timer (Sec. III-F) and the probe."""
from benchmarks.chip import window


def read(ctx):
    rec = window.recovery(ctx.result.events, ctx.result.commit_times)
    return None if rec is None else rec["detect_s"]
