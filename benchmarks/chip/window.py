"""Arithmetic of the measured window over a run's commit stamps and
protocol events (``LiveResult.commit_times`` and ``LiveResult.events``,
both seconds on the coordinator's ``time.monotonic()`` clock).
"""
from __future__ import annotations


def in_window(commit_times: dict, t_open: float, seconds: float) -> list:
    """Distinct batches whose last commit falls in (t_open, t_open +
    seconds]. A batch re-run after a recovery is one key of
    ``commit_times`` and so counts once."""
    t_close = t_open + seconds
    return sorted(b for b, t in commit_times.items() if t_open < t <= t_close)


def samples_per_s(commit_times: dict, t_open: float, seconds: float,
                  batch: int) -> float:
    return batch * len(in_window(commit_times, t_open, seconds)) / seconds


def rate_between(commit_times: dict, t0: float, t1: float,
                 batch: int) -> float | None:
    """Samples per second committed in (t0, t1], or None for an empty
    interval."""
    if t1 <= t0:
        return None
    n = sum(1 for t in commit_times.values() if t0 < t <= t1)
    return batch * n / (t1 - t0)


def _first_event(events: list, prefix: str, after: float) -> float | None:
    for t, text in events:
        if t >= after and text.startswith(prefix):
            return t
    return None


def recovery(events: list, commit_times: dict) -> dict | None:
    """Split of the first recovery: ``kill`` -> ``failure detected`` ->
    ``recovered`` -> the first commit after it. Returns None where the run
    holds no complete recovery."""
    t_kill = _first_event(events, "KILL worker", 0.0)
    if t_kill is None:
        return None
    t_detect = _first_event(events, "failure detected", t_kill)
    t_rec = (None if t_detect is None
             else _first_event(events, "recovered", t_detect))
    if t_rec is None:
        return None
    later = [t for t in commit_times.values() if t > t_rec]
    if not later:
        return None
    t_resume = min(later)
    return {"t_kill": t_kill, "detect_s": t_detect - t_kill,
            "protocol_s": t_rec - t_detect, "resume_s": t_resume - t_rec,
            "recover_s": t_resume - t_kill, "t_resume": t_resume}


def control_point_gaps(commit_times: dict, batches: list,
                       cadences: list) -> list[float]:
    """For each control point k among ``batches`` (a multiple of one of
    the ``cadences``, with batch k - 1 also in the window): the seconds
    from batch k - 1's commit to batch k's."""
    inside = set(batches)
    return [commit_times[k] - commit_times[k - 1] for k in sorted(inside)
            if k - 1 in inside and any(c > 0 and k % c == 0
                                       for c in cadences)]
