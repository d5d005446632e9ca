"""recover_s: the coordinator's ``KILL worker`` event to the first commit
after the ``recovered`` event, both on the coordinator's clock."""
from benchmarks.chip import window


def read(ctx):
    rec = window.recovery(ctx.result.events, ctx.result.commit_times)
    return None if rec is None else rec["recover_s"]
