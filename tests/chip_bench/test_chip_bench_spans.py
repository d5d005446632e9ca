"""The program's spans in a trace and the readers built on them, on small
recorded traces: the workers' counters and replication rounds, the idle
time put down to spans, and the recovery's probe and program loads."""
import math
import types

import pytest

from benchmarks.chip import registry, spans
from benchmarks.chip import trace as trace_mod

MS = 1_000_000_000          # picoseconds in a millisecond


def _events(rows, first_id):
    """``events`` entries and their metadata for [(name, start ms, end
    ms)], one metadata id per distinct name."""
    ids, evs, meta = {}, [], []
    for name, s, e in rows:
        if name not in ids:
            ids[name] = first_id + len(ids)
            meta.append(f'event_metadata {{ key: {ids[name]} value {{ id: '
                        f'{ids[name]} name: "{name}" }} }}')
        evs.append(f"events {{ metadata_id: {ids[name]} offset_ps: "
                   f"{round(s * MS)} duration_ps: {round((e - s) * MS)} }}")
    return "\n".join(evs), "\n".join(meta)


def _chip(i, modules, ops):
    mod_ev, mod_meta = _events(modules, 10)
    op_ev, op_meta = _events(ops, 50)
    return (f'planes {{ id: {i + 1} name: "/device:TPU:{i}"\n'
            f'lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0\n{mod_ev} }}\n'
            f'lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0\n{op_ev} }}\n'
            f'{mod_meta}\n{op_meta} }}\n')


def _host(rows):
    ev, meta = _events(rows, 100)
    return (f'planes {{ id: 9 name: "/host:CPU"\n'
            f'lines {{ id: 1 name: "python" timestamp_ns: 0\n{ev} }}\n'
            f'{meta} }}\n')


def _trace(text, window_ms=10.0):
    from jax.profiler import ProfileData
    return trace_mod.from_profile(ProfileData.from_text_proto(text),
                                  window_s=window_ms * 1e-3,
                                  start_epoch_ns=0)


# Two chips, workers 0 and 2 on chip 0 and worker 1 on chip 1 (d % 2).
# Chip 0 idles in [3, 5] and [8, 10] ms, chip 1 in [0, 1], [2, 6], [7, 10].
STEADY = (
    _chip(0, [("jit_fwd_out(1)", 0, 3), ("jit_step_fn(2)", 5, 8)],
          [("fusion.1", 0, 3), ("fusion.2", 5, 8)])
    + _chip(1, [("jit_fwd_out(3)", 1, 2), ("jit_step_fn(4)", 6, 7)],
            [("fusion.1", 1, 2), ("fusion.2", 6, 7)])
    + _host([("ftp.w0.fwd", 0, 3.2), ("ftp.w0.wait", 3.2, 4.5),
             ("ftp.w2.replicate", 3.5, 4.0), ("ftp.w0.step", 4.5, 8.5),
             ("ftp.w1.fwd", 1.5, 2.5), ("ftp.w1.step", 5.5, 7.5),
             ("ftp.coord.drain", 8.4, 9.0), ("ftp.coord.replicate", 9.0, 9.5),
             ("ftp.coord.refill", 9.5, 10.0), ("PjitFunction(step_fn)",
                                               5.0, 5.1)]))

# One chip; a transient probe, the probe that found worker 1 dead, the
# recovery, then the survivors' spans.
KILL = (
    _chip(0, [("jit_fwd_out(1)", 0.5, 0.6)], [("fusion.1", 0.5, 0.6)])
    + _host([("ftp.coord.probe", 0.2, 0.4), ("ftp.w0.wait", 0.0, 2.0),
             ("ftp.w2.fwd", 0.5, 0.9), ("ftp.coord.probe", 1.0, 1.5),
             ("ftp.coord.recover", 1.5, 2.0), ("ftp.w0.fwd", 2.1, 3.0),
             ("ftp.w2.fwd", 3.2, 4.0), ("ftp.w2.step", 4.1, 5.0),
             ("ftp.w0.step", 4.8, 6.0), ("ftp.w2.wait", 5.0, 6.2),
             ("ftp.w0.fwd", 6.0, 6.5), ("ftp.w2.step", 6.5, 7.0)]))

NO_SPANS = _chip(0, [("jit_step_fn(2)", 2, 4)], [("fusion.2", 2, 4)]) \
    + _host([("PjitFunction(step_fn)", 1.0, 2.0)])


@pytest.fixture
def steady_trace():
    return _trace(STEADY)


@pytest.fixture
def kill_trace():
    return _trace(KILL)


def _read(metric, **ctx):
    return registry.load_reader(metric).read(types.SimpleNamespace(**ctx))


def test_worker_spans_sit_on_their_chips(steady_trace):
    by_chip = spans.worker_spans(steady_trace)
    assert sorted((dev, kind) for _, dev, kind in by_chip[0]) == [
        (0, "fwd"), (0, "step"), (0, "wait"), (2, "replicate")]
    assert sorted((dev, kind) for _, dev, kind in by_chip[1]) == [
        (1, "fwd"), (1, "step")]
    assert [ev.name for ev in spans.coordinator_spans(steady_trace)] == [
        "ftp.coord.drain", "ftp.coord.replicate", "ftp.coord.refill"]


def test_replicate_reader_sums_the_workers_rounds(steady_trace):
    # worker 2's round over [3.5, 4.0] ms; the coordinator's replicate
    # span is not a worker's
    assert math.isclose(spans.worker_span_s(steady_trace, "replicate"),
                        0.5e-3)
    assert math.isclose(_read("stage.replicate_ms", trace=steady_trace,
                              traced_batches=[1, 2]), 0.25)
    assert _read("stage.replicate_ms", trace=steady_trace,
                 traced_batches=[]) is None


def test_idle_by_span(steady_trace):
    got = {k: round(v * 1e3, 9) for k, v in spans.idle_by_span(steady_trace)}
    assert got == {
        # [3, 5]: fwd to 3.2, wait, worker 2's replicate (the innermost
        # span) over [3.5, 4.0], wait, then step from 4.5
        "chip0 ftp.w0.fwd": 0.2, "chip0 ftp.w0.wait": 0.8,
        "chip0 ftp.w2.replicate": 0.5,
        # [8, 10]: the worker's step wins over the drain where both run
        "chip0 ftp.w0.step": 1.0, "chip0 ftp.coord.drain": 0.5,
        "chip0 ftp.coord.replicate": 0.5, "chip0 ftp.coord.refill": 0.5,
        "chip1 host, no span": 4.9, "chip1 ftp.w1.fwd": 0.5,
        "chip1 ftp.w1.step": 1.0, "chip1 ftp.coord.drain": 0.6,
        "chip1 ftp.coord.replicate": 0.5, "chip1 ftp.coord.refill": 0.5}
    table = spans.idle_by_span(steady_trace)
    assert table[0][0] == "chip1 host, no span"
    assert math.isclose(sum(v for _, v in table), 12e-3)


def test_idle_of_a_shared_chip_goes_to_the_worker_at_work(kill_trace):
    got = {k: round(v * 1e3, 9) for k, v in spans.idle_by_span(kill_trace)}
    # worker 2 waits on worker 0's step and forward from 5.0 to 6.2 ms
    assert got.get("chip0 ftp.w2.wait", 0.0) == 0.0
    assert got["chip0 ftp.w0.step"] == 1.2
    # worker 0's wait names [0, 2] ms less worker 2's forward, less the
    # 0.1 ms the chip ran
    assert got["chip0 ftp.w2.fwd"] == 0.3 + 0.8
    assert got["chip0 ftp.w0.wait"] == 1.6


def test_programs_in_spans(steady_trace):
    # chip 1's forward program starts at 1 ms, before its span at 1.5
    assert spans.programs_in_spans(steady_trace) == {0: 1.0, 1: 0.5}


def test_recovery_probe_and_loads(kill_trace):
    assert math.isclose(spans.recovery_probe_s(kill_trace), 0.5e-3)
    # first fwd and step of workers 0 and 2 after 2.0 ms: [2.1, 3.0],
    # [3.2, 4.0], [4.1, 5.0] and [4.8, 6.0]; later spans do not count
    assert math.isclose(spans.recovery_load_s(kill_trace), 3.6e-3)
    assert math.isclose(_read("recover.probe_s", trace=kill_trace), 0.5e-3)
    assert math.isclose(_read("recover.load_s", trace=kill_trace), 3.6e-3)


def test_trace_readers_find_nothing_without_spans(steady_trace):
    bare = _trace(NO_SPANS)
    for metric in ("recover.probe_s", "recover.load_s"):
        assert _read(metric, trace=None) is None
        assert _read(metric, trace=bare) is None
    # a steady trace holds no recovery
    assert _read("recover.probe_s", trace=steady_trace) is None
    assert _read("recover.load_s", trace=steady_trace) is None
    assert spans.idle_by_span(bare) == [
        ["chip0 host, no span", pytest.approx(8e-3)]]
    assert spans.programs_in_spans(bare) == {0: 0.0}


def test_replicate_reader_finds_nothing_without_spans():
    bare = _trace(NO_SPANS)
    assert _read("stage.replicate_ms", trace=None,
                 traced_batches=[1, 2]) is None
    assert _read("stage.replicate_ms", trace=bare,
                 traced_batches=[1, 2]) is None
    # a kill trace's workers run no round
    assert _read("stage.replicate_ms", trace=_trace(KILL),
                 traced_batches=[1, 2]) is None


def _records():
    stats = [{"seg_id": i, "dev": d, "t_done": t, "nb": 5,
              "busy_s": 0.5, "wait_s": 0.25 * (d + 1), "host_s": 0.125}
             for i, t in enumerate((0.9, 1.5, 3.0, 3.1)) for d in (0, 1)]
    return types.SimpleNamespace(stage_stats=stats)


def test_stage_readers_sum_the_window_per_batch():
    # segments done at 1.5 and 3.0 s fall in (1.0, 3.0]: 2 segments x
    # (0.25 + 0.5) s of wait over 11 batches
    ctx = dict(result=_records(), batches=list(range(10, 21)),
               t_open=1.0, seconds=2.0)
    assert math.isclose(_read("stage.wait_ms", **ctx), 1000 * 1.5 / 11)
    assert math.isclose(_read("stage.host_ms", **ctx), 1000 * 0.5 / 11)


@pytest.mark.parametrize("metric", ["stage.wait_ms", "stage.host_ms"])
def test_record_readers_find_nothing_in_an_older_program(metric):
    # a LiveResult without the span counters, as an older commit returns
    older = types.SimpleNamespace(commit_times={10: 1.0})
    ctx = dict(result=older, batches=list(range(10, 21)), t_open=1.0,
               seconds=2.0)
    assert _read(metric, **ctx) is None
    ctx["result"] = types.SimpleNamespace(stage_stats=[])
    assert _read(metric, **ctx) is None
