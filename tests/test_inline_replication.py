"""Replication inside the running segment (docs/protocol.md §11): a control
point that only replicates does not drain the pipeline.

Equivalence: a run whose every replication point drains (each also hosts
a re-partition that adopts nothing) and a run that replicates inside the
segment train the same losses and fill the chain and global replica
stores with the same messages at every generation. The cut: the 1F1B
schedule of fewer batches is a prefix of the longer one, so a segment can
be cut short at a control point on demand (a stop, a joiner), and a kill
in the middle of a long segment recovers from the in-segment replicas.
"""
import random
import threading
import time

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.checkpoint.manifest import RunManifest
from repro.core import schedule as sched
from repro.run import Run, RunConfig
from repro.runtime import live as live_mod
from repro.runtime.devices import DeviceSpec, uniform_bandwidth
from repro.runtime.live import LiveConfig, VerticalSyncStash, Worker
from repro.runtime.protocol import ProtocolConfig
from repro.runtime.transport import FaultSpec
from repro.runtime.workload import WorkloadSpec
from test_overlap import (_by_generation, _chain_and_data, _digest,
                          _fixed_profile, _recorded_run)

NEVER = 10_000


def _cfg(n, ce, ge, nb, drained, **kw):
    """Spec capacities and a fixed profile: every decision is a function
    of the config. ``drained``: a re-partition is due at every chain
    point and the partition is static, so each point drains and adopts
    nothing."""
    every = ce if drained else NEVER
    d = dict(num_workers=n, num_batches=nb, lr=0.1,
             protocol=ProtocolConfig(chain_every=ce, global_every=ge,
                                     repartition_first_at=every,
                                     repartition_every=every,
                                     detect_timeout=1.0),
             device_specs=[DeviceSpec(f"d{i}", 1.0) for i in range(n)],
             bandwidth=uniform_bandwidth(n, 1e9), profile=_fixed_profile(),
             capacity_source="spec", static_partition=True)
    d.update(kw)
    return LiveConfig(**d)


# ========================= in-segment == drained =========================

@pytest.mark.live
@pytest.mark.parametrize("n", [2, 3, 4])
@pytest.mark.parametrize("ce,gmul", [(2, 1), (3, 2)])
def test_in_segment_stores_match_drained_at_every_generation(n, ce, gmul):
    """The same weights, replicated at the same cadence to the same peers,
    with the same bytes and stamps: every committed generation of the
    global store and of every worker's chain store holds exactly the
    drained run's messages, and every batch trains to the same loss."""
    chain, data = _chain_and_data(num_batches=8)
    nb = 4 * ce * gmul + 1
    runs = {}
    for drained in (True, False):
        with pytest.MonkeyPatch.context() as mp:
            runs[drained] = _recorded_run(
                chain, data, _cfg(n, ce, ce * gmul, nb, drained), mp)
    (res_d, gstore_d, chains_d), (res_i, gstore_i, chains_i) = \
        runs[True], runs[False]
    points = (nb - 1) // ce
    assert (res_d.drains, res_d.replications_inline) == (points, 0)
    assert (res_i.drains, res_i.replications_inline) == (0, points)

    np.testing.assert_array_equal(res_i.losses, res_d.losses)
    gens = _by_generation(gstore_d.history)
    assert _by_generation(gstore_i.history) == gens
    assert sorted(gens) == list(range(0, nb, ce * gmul))
    assert sorted(chains_i) == sorted(chains_d)
    for dev in chains_d:
        assert _by_generation(chains_i[dev].history) \
            == _by_generation(chains_d[dev].history), dev
    assert gstore_i.batches() == gstore_d.batches()
    for j in gstore_d.batches():
        assert _digest(gstore_i.get(j)[1]) == _digest(gstore_d.get(j)[1])


# =============================== the cut ================================

@pytest.mark.parametrize("n", [2, 3, 4])
@pytest.mark.parametrize("cut", [1, 2, 5, 9])
def test_a_shorter_schedule_is_a_prefix_up_to_its_end(n, cut):
    """Each stage's 1F1B schedule for ``cut`` batches is the longer
    schedule up to the forward of batch ``cut``, then backwards only: a
    stage that has not forwarded batch ``cut`` can take up the shorter
    schedule at the op it has reached."""
    for stage in range(n):
        longer = list(sched.stage_schedule(stage, n, cut + 7))
        shorter = list(sched.stage_schedule(stage, n, cut))
        k = next(i for i, op in enumerate(longer)
                 if op.kind == "fwd" and op.batch == cut)
        assert shorter[:k] == longer[:k]
        assert {op.kind for op in shorter[k:]} <= {"bwd"}
        sched.validate_schedule(shorter, stage, n)


def _cut_pipeline(n, nb, every, cut_after, rng):
    """The workers' cut protocol over any interleaving: stages take ready
    ops in random order; after ``cut_after`` ops stage 0 cuts at the first
    point after its next unforwarded batch, and each other stage learns
    it at a random later moment (at the latest when it has nothing else
    to do). Returns each stage's executed ops, the end, and the newest
    batch any stage had stepped when stage 0 cut (-1 if none had)."""
    plans = [list(sched.stage_schedule(s, n, nb)) for s in range(n)]
    done = [[] for _ in range(n)]
    acts, grads = set(), set()           # (stage, batch) that may run
    end, told, steps, stepped = nb, [False] * n, 0, -1
    while any(len(done[s]) < len(plans[s]) for s in range(n)):
        if end == nb and steps >= cut_after:
            fwds = sum(op.kind == "fwd" for op in done[0])
            end = min([p for p in range(every, nb, every) if p > fwds]
                      + [nb])
            stepped = max([o.batch for ops in done for o in ops
                           if o.kind == "bwd"] + [-1])
            told[0] = True
            plans[0] = list(sched.stage_schedule(0, n, end))
        ready = []
        for s in range(n):
            if len(done[s]) == len(plans[s]):
                continue
            op = plans[s][len(done[s])]
            if op.kind == "fwd":
                ok = s == 0 or (s, op.batch) in acts
            else:
                ok = (s == n - 1 and any(o.kind == "fwd" and o.batch ==
                                         op.batch for o in done[s])) \
                    or (s, op.batch) in grads
            if ok:
                ready.append(s)
        late = [s for s in range(n) if told[0] and not told[s]]
        if late and (not ready or rng.random() < 0.3):
            s = rng.choice(late)
            told[s] = True
            assert all(not (o.kind == "fwd" and o.batch >= end)
                       for o in done[s]), "a stage ran past the cut"
            plans[s] = list(sched.stage_schedule(s, n, end))
            continue
        assert ready, "the pipeline wedged"
        s = rng.choice(ready)
        op = plans[s][len(done[s])]
        done[s].append(op)
        steps += 1
        if op.kind == "fwd" and s < n - 1:
            acts.add((s + 1, op.batch))
        if op.kind == "bwd" and s > 0:
            grads.add((s - 1, op.batch))
    return done, end, stepped


@settings(max_examples=60, deadline=None)
@given(n=st.integers(2, 4), nb=st.integers(2, 30), every=st.integers(1, 6),
       cut_after=st.integers(0, 120), seed=st.integers(0, 2 ** 16))
def test_a_cut_completes_under_any_interleaving(n, nb, every, cut_after,
                                                seed):
    """No interleaving of the stages wedges a cut or runs a batch past it:
    every stage runs each batch before the cut forward and backward once,
    in a valid 1F1B order. No round at or after the cut has run in the
    segment: a round at ``p`` runs after a stage's step of batch
    ``p - 1``, and no stage had stepped batch ``end - 1`` when stage 0
    cut."""
    done, end, stepped = _cut_pipeline(n, nb, every, cut_after,
                                       random.Random(seed))
    assert end == nb or stepped < end - 1
    for s, ops in enumerate(done):
        assert [o.batch for o in ops if o.kind == "fwd"] == list(range(end))
        assert [o.batch for o in ops if o.kind == "bwd"] == list(range(end))
        sched.validate_schedule(ops, s, n)


def _long_run(n=3, **kw):
    return RunConfig(
        workload=WorkloadSpec(kind="mlp", seed=0, num_layers=8),
        live=LiveConfig(num_workers=n, num_batches=20_000, lr=0.02,
                        protocol=ProtocolConfig(
                            chain_every=5, global_every=10,
                            repartition_first_at=10 ** 9,
                            repartition_every=10 ** 9, detect_timeout=2.0),
                        **kw),
        transport="queue")


def _wait_committed(run, batch, timeout=120.0):
    deadline = time.monotonic() + timeout
    while run.status()["chains"].get(0, {}).get("progress", {}).get(
            "last_committed", -1) < batch:
        assert time.monotonic() < deadline, "the run stopped committing"
        time.sleep(0.002)


@pytest.mark.live
@pytest.mark.parametrize("n", [2, 4])
def test_stop_cuts_a_long_segment_at_a_control_point(n):
    """``Run.stop()`` in a 20,000-batch segment returns within seconds,
    at a control point, with the rounds before it run inside it."""
    run = Run(_long_run(n)).start()
    _wait_committed(run, 37)
    t0 = time.monotonic()
    run.stop()
    res = run.wait(timeout=60)
    assert time.monotonic() - t0 < 5.0
    b = _stop_batch(res)
    assert b % 5 == 0 and 40 <= b < 200
    assert len(res.loss_log) == b and not np.isnan(res.losses[:b]).any()
    assert res.drains == 1 and res.replications_inline == b // 5 - 1


def _stop_batch(res):
    (b,) = [int(e.split("@batch ")[1]) for _, e in res.events
            if e.startswith("stop requested")]
    return b


class _OnlyAcksAndCuts(tuple):
    """A ``FaultSpec.protect`` that spares every message kind except the
    in-segment ``replicated`` acks and the ``cut``."""

    def __contains__(self, kind):
        return kind not in ("replicated", "cut")


@pytest.mark.live
def test_lost_acks_and_cuts_stall_neither_rounds_nor_stop(tmp_path):
    """A third of the ``replicated`` acks and ``cut`` messages lost on the
    wire: later acks still close the rounds whose acks were lost, so the
    durable manifest advances at the global rounds, and ``Run.stop()``
    still ends a 20,000-batch segment within seconds."""
    run = Run(_long_run(3, run_dir=str(tmp_path), fault=FaultSpec(
        drop=0.3, seed=7, protect=_OnlyAcksAndCuts()))).start()
    _wait_committed(run, 150)
    # rounds close within a few rounds of the commits: global round 100
    # (trained batches [0, 100)) is on disk
    assert RunManifest.try_load(str(tmp_path)).last_committed >= 99
    t0 = time.monotonic()
    run.stop()
    res = run.wait(timeout=60)
    assert time.monotonic() - t0 < 5.0
    b = _stop_batch(res)
    assert b % 5 == 0 and len(res.loss_log) == b
    assert res.transport_stats["dropped"] > 0
    assert [e for _, e in res.events if "(in-segment): only" in e]
    assert res.drains == 1 and res.replications_inline == b // 5 - 1


@pytest.mark.live
def test_a_lone_stage_is_cut_for_a_rejoin_and_a_stop():
    """A kill leaves one stage of two, which awaits nothing from a peer:
    it still reads the cut between its ops, so the scheduled rejoin is
    admitted at a control point within an interval or so of its commit,
    and ``Run.stop()`` ends the segment within seconds."""
    run = Run(_long_run(2, kill=(1, 10), rejoin=(1, 30),
                        join_wait=30)).start()
    deadline = time.monotonic() + 120
    while run.status()["chains"].get(0, {}).get("membership", {}).get(
            "admissions", 0) < 1:
        assert time.monotonic() < deadline, "the rejoin was not admitted"
        time.sleep(0.01)
    _wait_committed(run, run.status()["chains"][0]["progress"][
        "last_committed"] + 20)
    t0 = time.monotonic()
    run.stop()
    res = run.wait(timeout=60)
    assert time.monotonic() - t0 < 5.0
    assert [r["failed"] for r in res.recoveries] == [[1]]
    (adm,) = res.admissions
    assert adm["batch"] % 5 == 0 and 30 < adm["batch"] < 200
    assert _stop_batch(res) % 5 == 0 and not np.isnan(
        res.losses[:_stop_batch(res)]).any()


@pytest.mark.live
def test_inline_and_drain_counters_count_what_the_run_did():
    """Rounds at re-partition points drain, the rest run in the segment,
    and the final collect drains too: the counters on the result and in
    ``status()`` agree with the events."""
    chain, data = _chain_and_data(num_batches=8)
    cfg = _cfg(3, 4, 8, 30, False, collect_final=True,
               protocol=ProtocolConfig(chain_every=4, global_every=8,
                                       repartition_first_at=8,
                                       repartition_every=16,
                                       detect_timeout=1.0))
    coord = live_mod.Coordinator(chain, lambda b: data[b % len(data)], cfg)
    res = coord.run()
    inline = [e for _, e in res.events if "(in-segment)" in e]
    assert [int(e.split("@batch ")[1].split()[0]) for e in inline] \
        == [4, 12, 20, 24, 28]
    assert res.replications_inline == 5
    assert res.drains == 3                   # 8, 16 and the final collect
    control = coord.chain_status()["control"]
    assert (control["replications_inline"], control["drains"]) == (5, 3)
    assert res.final_flats is not None


@pytest.mark.live
def test_kill_mid_segment_recovers_from_in_segment_replicas(monkeypatch):
    """A worker killed in the middle of a segment that never drained:
    the survivors install its layers from its newest in-segment replica,
    byte for byte the slice it held at that round's batch."""
    versions, installs = {}, []
    lock = threading.Lock()
    push, install = VerticalSyncStash.push, Worker.install

    def recording_push(stash, version, buf):
        if threading.current_thread().name == "worker-1":
            with lock:
                versions[version] = buf
        return push(stash, version, buf)

    def recording_install(worker, layer_range, flats, version=0):
        if worker.stash is not None:
            with lock:
                installs.append((worker.dev, version, dict(flats)))
        return install(worker, layer_range, flats, version)

    monkeypatch.setattr(VerticalSyncStash, "push", recording_push)
    monkeypatch.setattr(Worker, "install", recording_install)
    chain, data = _chain_and_data(num_batches=8)
    cfg = _cfg(3, 4, 8, 30, False, kill=(1, 10),
               protocol=ProtocolConfig(chain_every=4, global_every=8,
                                       repartition_first_at=NEVER,
                                       repartition_every=NEVER,
                                       detect_timeout=0.5))
    coord = live_mod.Coordinator(chain, lambda b: data[b % len(data)], cfg)
    res = coord.run()
    assert [r["failed"] for r in res.recoveries] == [[1]]
    assert res.drains == 0 and res.replications_inline > 2
    assert not np.isnan(res.losses).any()
    restart = res.recoveries[0]["restart"]
    a, e = res.partitions[0][1][0] + 1, res.partitions[0][1][1]
    dead = coord.layout.slice(a, e)
    taken = {j: flat for dev, v, flats in installs if v == restart
             for j, flat in flats.items() if a <= j <= e}
    assert sorted(taken) == list(range(a, e + 1))
    # one in-segment round's slice, the newest the dead worker shipped
    matches = [v for v, buf in versions.items()
               if all(np.array_equal(np.asarray(taken[j]),
                                     np.asarray(dead.view(buf, j)))
                      for j in taken)]
    assert matches and all(v % 4 == 0 and v >= 8 for v in matches)
