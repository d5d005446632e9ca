"""stage_step.device_ms: device time of every stage's jitted forward
(``fwd_out``) and fused step (``step_fn``) programs in the traced window,
summed over stages and chips, per batch committed in the traced span."""

PROGRAMS = ("fwd_out", "step_fn")


def read(ctx):
    tr = ctx.trace
    if tr is None or not ctx.traced_batches:
        return None
    total = sum(ev.dur for d in tr.device_names() for ev in tr.modules(d)
                if any(p in ev.name for p in PROGRAMS))
    return 1000.0 * total / len(ctx.traced_batches) if total > 0 else None
