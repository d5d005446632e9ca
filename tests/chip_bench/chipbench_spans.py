"""What the live runtime's spans and counters saw during a benchmark run
at a CPU size, recorded underneath the harness, and the checks made on
it."""
import threading
import time

import pytest

from benchmarks.chip import run
from benchmarks.chip import trace as trace_mod
from repro.runtime import live, spans, transport


class Recorder:
    """Installs itself with ``monkeypatch``. Keys are (worker thread,
    segment id): a rehearsal and the measured run reuse segment ids."""

    def __init__(self, mp):
        self.walls = {}       # key -> seconds from entering
        #                       Worker._run_segment to sending seg_done
        entered = {}          # worker thread -> when it entered
        self.done = {}        # key -> the seg_done payload
        self.spans = []       # (thread, name, stats, seconds)
        self.traces = []      # every trace the harness loaded
        rec = self

        class Recording(spans.Span):
            __slots__ = ("name", "stats")

            def __init__(self, name, into=None, key="", **stats):
                super().__init__(name, into, key, **stats)
                self.name, self.stats = name, stats

            def close(self):
                dt = super().close()
                rec.spans.append((threading.current_thread(), self.name,
                                  self.stats, dt))
                return dt

        mp.setattr(live, "Span", Recording)
        run_segment = live.Worker._run_segment

        def timed_segment(worker, spec):
            entered[worker] = time.perf_counter()
            return run_segment(worker, spec)
        mp.setattr(live.Worker, "_run_segment", timed_segment)
        send = transport.Transport.send

        def recording_send(tp, src, dst, kind, payload=None, **kw):
            if kind == "seg_done":
                t = time.perf_counter()
                worker = threading.current_thread()
                key = (worker, payload["seg_id"])
                rec.walls[key] = t - entered[worker]
                rec.done[key] = payload
            return send(tp, src, dst, kind, payload, **kw)
        mp.setattr(transport.Transport, "send", recording_send)
        load = trace_mod.load

        def recording_load(*a, **kw):
            tr = load(*a, **kw)
            rec.traces.append(tr)
            return tr
        mp.setattr(trace_mod, "load", recording_load)

    def check_counters(self) -> None:
        """Each segment's busy, wait and host seconds add up to its wall
        time, and its batch times (Eq. 1's input) are its fwd and step
        spans' durations, batch by batch."""
        assert self.done
        per_batch = {}
        for thread, name, stats, dt in self.spans:
            if name.endswith((".fwd", ".step")):
                b = per_batch.setdefault((thread, stats["seg"]), {})
                b[stats["batch"]] = b.get(stats["batch"], 0.0) + dt
        for key, p in self.done.items():
            assert p["wait_s"] >= 0 and p["host_s"] >= 0
            assert p["busy_s"] + p["wait_s"] + p["host_s"] == pytest.approx(
                self.walls[key], abs=1e-3)
            spans_of = sorted(per_batch.get(key, {}).values())
            assert spans_of == pytest.approx(p["batch_times"], abs=1e-12)
            assert sum(spans_of) == pytest.approx(p["busy_s"], abs=1e-9)

    def names(self) -> list[str]:
        """The program's span names in the traced window."""
        (tr,) = self.traces
        return [ev.name for ev in tr.host if ev.name.startswith("ftp.")]


def run_traced(cell, seconds, cache_dir, seed=5):
    return run.run_cell(cell, seed, seconds, True, require_chip=False,
                        cache_dir=cache_dir)
