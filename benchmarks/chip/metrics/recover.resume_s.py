"""recover.resume_s: ``recovered`` event to the first commit after it:
loading the survivors' programs, the redistribution hand-off and the
refill of the shorter pipeline."""
from benchmarks.chip import window


def read(ctx):
    rec = window.recovery(ctx.result.events, ctx.result.commit_times)
    return None if rec is None else rec["resume_s"]
