"""The program's own spans in a traced window, and the counters that share
their clock readings.

The live runtime writes ``jax.profiler.TraceAnnotation`` spans
(``repro/runtime/spans.py``) into the profiler's trace, on the device
planes' clock: ``ftp.w{d}.<kind>`` in worker ``d``'s thread (``fwd``,
``step``, ``wait``, ``replicate``, ``refit``) and ``ftp.coord.<kind>`` in
the coordinator's (``drain``, ``replicate``, ``refill``, ``probe``,
``recover``). Worker ``d`` runs on chip ``d % n`` of the host's ``n``
(``stage_executor.stage_device``). The workers' intervals are also counted
in ``LiveResult.stage_stats`` on the coordinator's clock; a program
without them (an older commit) leaves every function here returning None
or nothing.
"""
from __future__ import annotations

import bisect
import re

from benchmarks.chip import trace as trace_mod

WORKER = re.compile(r"^ftp\.w(\d+)\.(\w+)$")
COORD = "ftp.coord."
PROGRAMS = {"fwd": "fwd_out", "step": "step_fn"}   # span kind -> program
NO_SPAN = "host, no span"


# ------------------------------ records ------------------------------

def stage_ms_per_batch(ctx, key: str) -> float | None:
    """Milliseconds of ``key`` over the workers' segments done in the
    window, summed over workers, per batch committed in the window."""
    stats = getattr(ctx.result, "stage_stats", None)
    if not stats or not ctx.batches:
        return None
    t0, t1 = ctx.t_open, ctx.t_open + ctx.seconds
    total = sum(s[key] for s in stats if t0 < s["t_done"] <= t1)
    return 1000.0 * total / len(ctx.batches)


# ------------------------------ the trace ------------------------------

def program_spans(trace) -> list:
    """The ``ftp.*`` events of the trace's host threads, by start."""
    return sorted((ev for ev in trace.host if ev.name.startswith("ftp.")),
                  key=lambda ev: ev.start)


def worker_spans(trace) -> dict[int, list]:
    """{chip: [(event, dev, kind)]} of the workers' spans."""
    n = len(trace.device_names())
    out: dict[int, list] = {}
    if n == 0:
        return out
    for ev in program_spans(trace):
        m = WORKER.match(ev.name)
        if m:
            dev = int(m.group(1))
            out.setdefault(dev % n, []).append((ev, dev, m.group(2)))
    return out


def worker_span_s(trace, kind: str) -> float:
    """Seconds of every worker's ``ftp.w{d}.<kind>`` spans in the trace,
    summed over workers."""
    return sum(ev.dur for ev in program_spans(trace)
               if (m := WORKER.match(ev.name)) and m.group(2) == kind)


def coordinator_spans(trace, names=None) -> list:
    return [ev for ev in program_spans(trace) if ev.name.startswith(COORD)
            and (names is None or ev.name in names)]


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _attribute(gaps, layers) -> dict[str, float]:
    """Seconds of ``gaps`` under each label. ``layers`` lists span lists
    of [(start, end, name)], the first taking precedence; within one list
    the span that began last (the innermost) names the time."""
    edges = [(s, 1, -1, None) for s, e in gaps]
    edges += [(e, 0, -1, None) for s, e in gaps]
    for rank, spans in enumerate(layers):
        for i, (s, e, name) in enumerate(spans):
            if e > s:
                edges.append((s, 1, rank, (s, i, name)))
                edges.append((e, 0, rank, (s, i, name)))
    edges.sort(key=lambda t: (t[0], t[1]))     # ends before starts
    active = [set() for _ in layers]
    out: dict[str, float] = {}
    in_gap, prev = 0, None
    for x, start, rank, span in edges:
        if in_gap and prev is not None and x > prev:
            label = next((max(act)[2] for act in active if act), NO_SPAN)
            out[label] = out.get(label, 0.0) + x - prev
        prev = x
        if rank < 0:
            in_gap += 1 if start else -1
        elif start:
            active[rank].add(span)
        else:
            active[rank].discard(span)
    return out


def idle_by_span(trace) -> list:
    """[[label, seconds]] of the chips' idle time, longest first: each
    moment a chip is idle is put down to the innermost span of a worker
    on that chip, else to the coordinator's span, else to the host with
    no span. A worker's ``wait`` names the time only where no worker on
    the chip has another span open: where workers share a chip, the one
    waiting is held up by the one at work. Labels read "chip<c> <span
    name>"."""
    workers = worker_spans(trace)
    coord = [(ev.start, ev.end, ev.name) for ev in coordinator_spans(trace)]
    totals: dict[str, float] = {}
    for d in trace.device_names():
        c = trace_mod.chip_of(d)
        mine = workers.get(c, ())
        work = [(ev.start, ev.end, ev.name) for ev, _, kind in mine
                if kind != "wait"]
        wait = [(ev.start, ev.end, ev.name) for ev, _, kind in mine
                if kind == "wait"]
        for label, secs in _attribute(trace_mod.idle_gaps(trace, d),
                                      [work, wait, coord]).items():
            key = f"chip{c} {label}"
            totals[key] = totals.get(key, 0.0) + secs
    return [[k, v] for k, v in
            sorted(totals.items(), key=lambda kv: -kv[1])]


def programs_in_spans(trace) -> dict[int, float]:
    """{chip: share of its ``fwd_out``/``step_fn`` program events that
    start inside a ``fwd``/``step`` span of a worker on that chip}: how
    well the host spans and the device events share a clock."""
    workers = worker_spans(trace)
    out = {}
    for d in trace.device_names():
        c = trace_mod.chip_of(d)
        spans = trace_mod.union((ev.start, ev.end)
                                for ev, _, kind in workers.get(c, ())
                                if kind in PROGRAMS)
        mods = [ev for ev in trace.modules(d)
                if any(p in ev.name for p in PROGRAMS.values())]
        if not mods:
            continue
        starts = [s for s, _ in spans]
        hit = 0
        for ev in mods:
            i = bisect.bisect_right(starts, ev.start) - 1
            hit += i >= 0 and ev.start <= spans[i][1]
        out[c] = hit / len(mods)
    return out


# ------------------------------ the recovery ------------------------------

def _recovery_span(trace):
    recs = coordinator_spans(trace, ("ftp.coord.recover",))
    return recs[0] if recs else None


def recovery_probe_s(trace) -> float | None:
    """The ``ftp.coord.probe`` span that decided the first recovery: the
    last probe to end before its ``ftp.coord.recover`` span starts."""
    rec = _recovery_span(trace)
    if rec is None:
        return None
    probes = [ev for ev in coordinator_spans(trace, ("ftp.coord.probe",))
              if ev.end <= rec.start]
    return probes[-1].dur if probes else None


def recovery_load_s(trace) -> float | None:
    """Seconds covered by the survivors' first ``fwd`` and first ``step``
    spans after the first ``ftp.coord.recover`` span ends: the loads of
    their new stage programs, with those first batches."""
    rec = _recovery_span(trace)
    if rec is None:
        return None
    first: dict[tuple, tuple] = {}
    for ev in program_spans(trace):
        m = WORKER.match(ev.name)
        if m and m.group(2) in PROGRAMS and ev.start >= rec.end:
            first.setdefault((m.group(1), m.group(2)), (ev.start, ev.end))
    return _length(trace_mod.union(first.values())) if first else None
