"""Spans and the counters that share their clock readings: the worker's
seg_done counters, ``LiveResult.stage_stats`` and ``.control_points``, the
running totals in ``Run.status()``, and the spans in a profiler trace."""
import glob
import os

import pytest

from repro.runtime.spans import Span

B, CHAIN_EVERY, WORKERS = 30, 5, 3


def test_span_adds_its_duration_to_the_counter():
    counts = {}
    with Span("ftp.test", counts, "x_s", batch=3) as sp:
        pass
    assert sp.dt >= 0 and counts == {"x_s": sp.dt}
    again = Span("ftp.test", counts, "x_s").open()
    dt = again.close()
    assert counts["x_s"] == pytest.approx(sp.dt + dt)
    assert Span("ftp.test").open().close() >= 0     # no counter


def test_span_closes_when_its_block_raises():
    counts = {}
    with pytest.raises(KeyError):
        with Span("ftp.test", counts, "x_s"):
            raise KeyError("x")
    assert counts["x_s"] >= 0


def _run(tmp_path=None, drained=True):
    """A small MLP run on the queue transport, traced by the profiler
    where ``tmp_path`` is given. ``drained``: every replication point also
    hosts a re-partition (one that adopts nothing, the partition being
    static), so the pipeline drains there; else every point is an
    in-segment round and the run is one segment."""
    import jax

    from repro.run import RunConfig, start_run
    from repro.runtime.live import LiveConfig
    from repro.runtime.protocol import ProtocolConfig
    from repro.runtime.workload import WorkloadSpec

    cfg = RunConfig(
        workload=WorkloadSpec(kind="mlp", seed=0, num_layers=6),
        live=LiveConfig(
            num_workers=WORKERS, num_batches=B, lr=0.1,
            protocol=ProtocolConfig(chain_every=CHAIN_EVERY,
                                    global_every=2 * CHAIN_EVERY,
                                    repartition_first_at=(
                                        CHAIN_EVERY if drained else 10_000),
                                    repartition_every=(
                                        CHAIN_EVERY if drained else 10_000),
                                    detect_timeout=2.0),
            static_partition=True),
        transport="queue")
    if tmp_path is not None:
        jax.profiler.start_trace(str(tmp_path))
    try:
        run = start_run(cfg)
        res = run.wait(timeout=300)
    finally:
        if tmp_path is not None:
            jax.profiler.stop_trace()
    return run, res


@pytest.mark.live
@pytest.mark.parametrize("drained", [True, False],
                         ids=["drained", "in-segment"])
def test_counters_of_a_run(drained):
    run, res = _run(drained=drained)
    chain = run.status()["chains"][0]
    rounds = list(range(CHAIN_EVERY, B, CHAIN_EVERY))
    for dev, tot in chain["stages"].items():
        mine = [s for s in res.stage_stats if s["dev"] == dev]
        assert tot["batches"] == B
        for k in ("busy_s", "wait_s", "host_s"):
            assert tot[k] == pytest.approx(sum(s[k] for s in mine))
            assert tot[k] >= 0
    assert {s["dev"] for s in res.stage_stats} == set(range(WORKERS))
    if not drained:
        # one segment: no control point drains, every round runs in it
        # and its ack reports the worker's counters since the last one
        assert res.control_points == [] and res.drains == 0
        assert res.replications_inline == len(rounds)
        assert chain["control"]["replications_inline"] == len(rounds)
        assert chain["control"]["drains"] == 0
        assert len(res.stage_stats) == (len(rounds) + 1) * WORKERS
        assert sum(s["nb"] for s in res.stage_stats) == B * WORKERS
        return
    assert res.drains == len(rounds) and res.replications_inline == 0
    points = [cp["batch"] for cp in res.control_points]
    assert points == rounds
    for cp in res.control_points:
        k = cp["batch"]
        assert cp["t"] == res.commit_times[k]
        split = cp["drain_s"] + cp["replicate_s"] + cp["refill_s"]
        # the three spans lie inside the gap between the two commits
        assert 0 < split <= res.commit_times[k] - res.commit_times[k - 1] \
            + 1e-3
        assert cp["replicate_s"] > 0 and cp["refill_s"] > 0
    segments = B // CHAIN_EVERY
    assert len(res.stage_stats) == segments * WORKERS
    assert chain["control"]["points"] == len(points)
    assert chain["control"]["refill_s"] == pytest.approx(
        sum(cp["refill_s"] for cp in res.control_points))


def _spans(log_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("ftp."):
                    stats = {k: v for k, v in ev.stats}
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns, stats))
    return out


@pytest.mark.live
@pytest.mark.parametrize("drained", [True, False],
                         ids=["drained", "in-segment"])
def test_spans_reach_the_trace_and_do_not_nest(tmp_path, drained):
    _, res = _run(tmp_path, drained=drained)
    spans = _spans(str(tmp_path))
    names = {n for n, *_ in spans}
    for d in range(WORKERS):
        assert {f"ftp.w{d}.fwd", f"ftp.w{d}.step",
                f"ftp.w{d}.replicate"} <= names
        # the start-up round, then one per replication point
        assert [n for n, *_ in spans].count(f"ftp.w{d}.replicate") \
            == B // CHAIN_EVERY
    steps = [s for n, *_, s in spans if n == "ftp.w0.step"]
    assert sorted(s["batch"] for s in steps) == list(range(B))
    if drained:
        assert {"ftp.coord.drain", "ftp.coord.replicate",
                "ftp.coord.refill"} <= names
        assert {s["seg"] for s in steps} \
            == set(range(1, B // CHAIN_EVERY + 1))
    else:
        # the run's end is its only drain, and nothing refills
        assert "ftp.coord.refill" not in names
        assert [n for n, *_ in spans].count("ftp.coord.drain") == 1
        assert {s["seg"] for s in steps} == {1}
    # within one thread (a worker's prefix, or the coordinator's) spans
    # follow one another
    for prefix in [f"ftp.w{d}." for d in range(WORKERS)] + ["ftp.coord."]:
        mine = sorted((s, e) for n, s, e, _ in spans
                      if n.startswith(prefix))
        assert all(e0 <= s1 for (_, e0), (s1, _) in zip(mine, mine[1:]))
    assert len([n for n, *_ in spans if n == "ftp.coord.refill"]) \
        == len(res.control_points)
