"""Reduction of a JAX profiler trace to device intervals, busy time and
the breakdown of a traced run.

A trace is read once into plain tuples (``Trace``); every per-layer
reader works on those, so the arithmetic can be checked on a small
recorded trace without a chip.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

ABSOLUTE_NS = 1e17     # event times above this are nanoseconds since the
#                        epoch; below it they count from the session start
CHIP_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")   # not the profiler's
#                        own "/device:CUSTOM:..." planes


@dataclasses.dataclass
class Event:
    name: str
    start: float          # seconds from the window's start
    dur: float            # seconds

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    window_s: float
    devices: dict         # plane name -> {line name -> [Event]}
    host: list            # [Event] of every host thread

    def device_names(self) -> list[str]:
        return sorted(self.devices, key=chip_of)

    def ops(self, device: str) -> list[Event]:
        """The device's operations: its "XLA Ops" line, or its programs
        where it has no such line."""
        lines = self.devices[device]
        return lines.get("XLA Ops") or lines.get("XLA Modules") or []

    def modules(self, device: str) -> list[Event]:
        return self.devices[device].get("XLA Modules", [])


def chip_of(device: str) -> int:
    """The chip number of a device plane's name."""
    return int(CHIP_PLANE.match(device).group(2))


def from_profile(profile, window_s: float, start_epoch_ns: int) -> Trace:
    """``profile`` is a ``jax.profiler.ProfileData``. Times are moved so
    that the window starts at 0 and cut to it."""
    planes = list(profile.planes)
    latest = max((ev.start_ns for pl in planes for ln in pl.lines
                  for ev in ln.events), default=0.0)
    origin = start_epoch_ns if latest > ABSOLUTE_NS else 0

    def events(line):
        out = []
        for ev in line.events:
            s = (ev.start_ns - origin) / 1e9
            e = s + ev.duration_ns / 1e9
            s, e = max(s, 0.0), min(e, window_s)
            if e > s or (ev.duration_ns == 0 and 0 <= s <= window_s):
                out.append(Event(ev.name, s, e - s))
        return out

    devices, host = {}, []
    for pl in planes:
        if CHIP_PLANE.match(pl.name):
            devices[pl.name] = {ln.name: events(ln) for ln in pl.lines}
        elif pl.name.startswith("/host:"):
            for ln in pl.lines:
                host.extend(events(ln))
    return Trace(window_s=window_s, devices=devices, host=host)


def load(log_dir: str, window_s: float, start_epoch_ns: int) -> Trace:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return from_profile(ProfileData.from_file(paths[-1]), window_s,
                        start_epoch_ns)


# ------------------------------ arithmetic ------------------------------

def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted (start, end) pairs covering the given intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(trace: Trace, device: str) -> float:
    return sum(e - s for s, e in union((ev.start, ev.end)
                                       for ev in trace.ops(device)))


def mean_busy_s(trace: Trace) -> float:
    names = trace.device_names()
    if not names:
        return 0.0
    return sum(busy_s(trace, d) for d in names) / len(names)


def idle_gaps(trace: Trace, device: str) -> list[tuple[float, float]]:
    """The device's idle intervals inside the window."""
    gaps, t = [], 0.0
    for s, e in union((ev.start, ev.end) for ev in trace.ops(device)):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if trace.window_s > t:
        gaps.append((t, trace.window_s))
    return gaps


def short(name: str) -> str:
    """An operation's HLO name without its operands and attributes."""
    return name.split(" = ", 1)[0]


def ops_with_module(trace: Trace, device: str) -> list[tuple]:
    """(operation, the program it ran in or None) for each of the
    device's operations, the program being the module event that covers
    the operation's start."""
    mods = sorted(trace.modules(device), key=lambda ev: ev.start)
    out, i = [], 0
    for op in sorted(trace.ops(device), key=lambda ev: ev.start):
        while i < len(mods) and mods[i].end < op.start:
            i += 1
        inside = i < len(mods) and mods[i].start <= op.start
        out.append((op, mods[i] if inside else None))
    return out


def host_activity(trace: Trace, s: float, e: float) -> str:
    """Name of the host event that covers most of [s, e], or "no host
    event" where none overlaps it."""
    best, best_overlap = "no host event", 0.0
    for ev in trace.host:
        ov = min(e, ev.end) - max(s, ev.start)
        if ov > best_overlap:
            best, best_overlap = ev.name, ov
    return best


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device operations that took most time (summed over devices,
    named by program and HLO name), and the longest idle gaps, each named
    by the host event that overlaps it most."""
    per_op: dict[str, float] = {}
    for d in trace.device_names():
        for op, mod in ops_with_module(trace, d):
            name = short(op.name) if mod is None else \
                f"{mod.name}/{short(op.name)}"
            per_op[name] = per_op.get(name, 0.0) + op.dur
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = []
    for d in trace.device_names():
        for s, e in idle_gaps(trace, d):
            gaps.append((e - s, chip_of(d), s, e))
    gaps.sort(reverse=True)
    named = [[f"chip{i} {host_activity(trace, s, e)}", g]
             for g, i, s, e in gaps[:top]]
    return {"device_ops": [[n, v] for n, v in ops], "idle_gaps": named}
