"""Live multi-worker runtime: protocol equivalence with the simulator,
async-semantics parity with the sequential oracle, fault recovery, and the
replication/stash plumbing.
"""
import threading
import time

import jax
import numpy as np
import pytest

from repro.checkpoint.replication_store import LayerReplicaStore
from repro.core import schedule as sched
from repro.core.partition import uniform_partition
from repro.optim.sgd import sgd_init, sgd_update
from repro.runtime.devices import DeviceSpec, uniform_bandwidth
from repro.runtime.live import (Coordinator, LiveConfig, VerticalSyncStash,
                                run_live_training)
from repro.runtime.protocol import ProtocolConfig
from repro.runtime.semantics import AsyncTrainingExecutor
from repro.runtime.simulator import PipelineSimulator, SimConfig
from repro.runtime.transport import FaultSpec, Transport
from repro.runtime.workload import classification_batches, mlp_chain

KEY = jax.random.PRNGKey(0)


def _chain_and_data(num_layers=8, num_batches=8, batch=16):
    chain = mlp_chain(KEY, num_layers=num_layers)
    data = classification_batches("mlp", num_batches, batch=batch, seed=0)
    return chain, data


def _quiet_protocol(**kw):
    """Cadences beyond the horizon: a pure 1F1B run, no control events."""
    d = dict(chain_every=10_000, global_every=10_000,
             repartition_first_at=10_000, repartition_every=10_000,
             detect_timeout=2.0)
    d.update(kw)
    return ProtocolConfig(**d)


# ===================== vertical-sync stash (pure) ========================

class TestVerticalSyncStash:
    @pytest.mark.parametrize("n", [2, 3, 4, 5])
    def test_holds_exactly_the_versions_the_schedule_demands(self, n):
        """Following core/schedule.py's 1F1B op order at every stage, each
        forward's vertical-sync version is present EXACTLY (no fallback),
        retention never exceeds n+1 (the semantics executor's ring depth),
        and in-flight batches never span more than stash_depth(stage, n)
        distinct versions (the paper's n - i concurrent trainings)."""
        B = 24
        for stage in range(n):
            stash = VerticalSyncStash({"w": 0}, version=0)
            ops = list(sched.stage_schedule(stage, n, B))
            next_fwd = [None] * (len(ops) + 1)
            for i in range(len(ops) - 1, -1, -1):
                next_fwd[i] = (ops[i].batch if ops[i].kind == "fwd"
                               else next_fwd[i + 1])
            in_flight = {}
            for i, op in enumerate(ops):
                if op.kind == "fwd":
                    v = sched.version_for_batch(op.batch, n)
                    assert v in stash.versions, (stage, op, stash.versions)
                    in_flight[op.batch] = v
                    assert len(set(in_flight.values())) <= \
                        sched.stash_depth(stage, n)
                else:
                    in_flight.pop(op.batch)
                    stash.push(op.batch + 1, {"w": op.batch + 1})
                    nf = next_fwd[i + 1]
                    stash.prune(float("inf") if nf is None
                                else sched.version_for_batch(nf, n))
            assert stash.high_water <= n + 1

    def test_get_never_newer(self):
        s = VerticalSyncStash({"w": 0}, version=3)
        s.push(7, {"w": 7})
        assert s.get(5)["w"] == 0       # falls back to OLDER version 3
        assert s.get(7)["w"] == 7
        assert s.get(1)["w"] == 0       # post-drain: oldest available

    def test_prune_keeps_newest(self):
        s = VerticalSyncStash({"w": 0})
        s.push(1, {"w": 1})
        s.push(2, {"w": 2})
        s.prune(float("inf"))
        assert list(s.versions) == [2]


class TestProtocolConfig:
    def test_global_points_present_when_not_aligned_with_chain(self):
        p = ProtocolConfig(chain_every=15, global_every=20)
        pts = p.control_points(45)
        assert 20 in pts and 40 in pts and 15 in pts and 30 in pts
        assert p.replication_due(20) == (False, True)
        assert p.replication_due(30) == (True, False)
        assert p.replication_due(60) == (True, True)

    def test_control_points_static_drops_repartition(self):
        p = ProtocolConfig(chain_every=50, global_every=100,
                           repartition_first_at=10, repartition_every=100)
        assert 10 in p.control_points(300)
        assert 10 not in p.control_points(300, dynamic=False)


class TestLayerReplicaStore:
    def test_keeps_freshest_and_covers(self):
        st = LayerReplicaStore()
        st.put(0, 5, "a")
        st.put(0, 3, "stale")          # older put must not clobber
        st.put(1, 7, "b")
        assert st.get(0) == (5, "a")
        assert st.batches() == {0: 5, 1: 7}
        assert not st.covers(3)
        st.put(2, 1, "c")
        assert st.covers(3)

    def test_put_many_and_nbytes_on_packed_buffers(self):
        st = LayerReplicaStore()
        st.put_many(4, {0: np.zeros(10, np.float32),
                        1: np.zeros(6, np.float32)})
        assert st.batches() == {0: 4, 1: 4}
        assert st.nbytes() == 4 * (10 + 6)
        st.put_many(2, {0: np.zeros(99, np.float32)})   # stale: ignored
        assert st.get(0)[0] == 4 and st.nbytes() == 4 * (10 + 6)

    def test_nbytes_dedupes_across_tiers(self):
        """A layer snapshotted at the same batch into BOTH tiers is one
        logical replica: the deduped total counts it once, per-tier totals
        count their own copies, and nbytes_report surfaces the overlap
        (the old single-number nbytes double-counted exactly this)."""
        st = LayerReplicaStore()
        snap = np.zeros(10, np.float32)
        st.put(0, 5, snap, tier=LayerReplicaStore.GLOBAL)
        st.put(0, 5, snap, tier=LayerReplicaStore.CHAIN)
        st.put(1, 5, np.zeros(6, np.float32), tier=LayerReplicaStore.CHAIN)
        assert st.nbytes(LayerReplicaStore.GLOBAL) == 40
        assert st.nbytes(LayerReplicaStore.CHAIN) == 40 + 24
        assert st.nbytes() == 40 + 24                  # layer 0 counted once
        rep = st.nbytes_report()
        assert rep["per_tier"] == {"global": 40, "chain": 64}
        assert rep["deduped"] == 64 and rep["duplicated"] == 40

    def test_tiers_track_freshness_independently(self):
        """Different batches in different tiers are distinct snapshots:
        get() returns the freshest across tiers, and the deduped total
        keeps both (they hold different data)."""
        st = LayerReplicaStore()
        st.put(0, 4, np.zeros(10, np.float32), tier=LayerReplicaStore.CHAIN)
        st.put(0, 8, np.zeros(10, np.float32), tier=LayerReplicaStore.GLOBAL)
        assert st.get(0)[0] == 8
        assert st.get(0, tier=LayerReplicaStore.CHAIN)[0] == 4
        assert st.batches() == {0: 8}
        assert st.nbytes() == 80                       # two real snapshots
        assert st.covers(1) and not st.covers(2)


class TestTransport:
    def test_kill_isolates_node(self):
        t = Transport()
        for n in (0, 1):
            t.register(n)
        assert t.send(0, 1, "x", {})
        assert t.recv(1, timeout=0.1).kind == "x"
        t.kill(1)
        assert not t.send(0, 1, "x", {})
        assert not t.send(1, 0, "x", {})
        assert t.recv(1, timeout=0.05) is None
        assert t.stats["to_dead"] == 2

    def test_drop_respects_protect(self):
        t = Transport(FaultSpec(drop=1.0, protect=("ctl",), seed=0))
        t.register(0)
        t.register(1)
        assert not t.send(0, 1, "data", {})
        assert t.send(0, 1, "ctl", {})

    def test_delay_delivers_late(self):
        t = Transport(FaultSpec(delay=0.05))
        t.register(0)
        t.register(1)
        t.send(0, 1, "x", {})
        assert t.recv(1, timeout=0.01) is None
        assert t.recv(1, timeout=0.5).kind == "x"


# ========================= live training runs ============================

@pytest.mark.live
def test_steady_state_matches_async_semantics_oracle():
    """With no control events, the live pipeline's per-batch losses follow
    the sequential async-semantics executor (same 1F1B order, vertical-sync
    versions, SGD updates) — threads + message passing change nothing."""
    chain, data = _chain_and_data()
    B, n = 18, 3
    lr = 0.1

    def update_fn(params, grads, opt):
        return sgd_update(params, grads, opt, lr=lr, momentum=0.0,
                          weight_decay=0.0)

    ex = AsyncTrainingExecutor(
        loss_fn=chain.loss_fn, num_stages=n,
        assignment=list(uniform_partition(chain.num_layers, n).counts),
        update_fn=update_fn, opt_state=sgd_init(chain.params))
    _, ref_losses = ex.run([p for p in chain.params],
                           [data[b % len(data)] for b in range(B)])

    res = run_live_training(chain, data, LiveConfig(
        num_workers=n, num_batches=B, protocol=_quiet_protocol(),
        lr=lr, momentum=0.0, weight_decay=0.0))
    np.testing.assert_allclose(res.losses, np.asarray(ref_losses),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.live
def test_compiled_and_uncompiled_hot_paths_agree():
    """The jitted fused StageExecutor step (fwd recompute + bwd +
    kernels/fused_sgd update in one compiled call) reproduces the legacy
    eager vjp + sgd_update path batch-for-batch, momentum and weight decay
    on — the whole pipeline, not just one stage."""
    chain, data = _chain_and_data()
    B = 14
    kw = dict(num_workers=3, num_batches=B, protocol=_quiet_protocol(),
              lr=0.1, momentum=0.9, weight_decay=4e-5)
    fused = run_live_training(chain, data, LiveConfig(compiled=True, **kw))
    chain2, data2 = _chain_and_data()
    eager = run_live_training(chain2, data2, LiveConfig(compiled=False, **kw))
    np.testing.assert_allclose(fused.losses, eager.losses, rtol=1e-4,
                               atol=1e-5)


@pytest.mark.live
def test_aggregation_cadence_trains_on_packed_buffers():
    """§III-C weight aggregation (version-mean + counter bump) on the
    packed representation: training completes and losses drop. (Aggregation
    pushes mean versions ahead of what forwards pin, so the n+1
    vertical-sync retention bound intentionally does not apply here.)"""
    chain, data = _chain_and_data()
    res = run_live_training(chain, data, LiveConfig(
        num_workers=3, num_batches=18, protocol=_quiet_protocol(),
        lr=0.1, aggregate_every=4))
    assert not np.isnan(res.losses).any()
    assert float(np.median(res.losses[-5:])) \
        < 0.8 * float(np.median(res.losses[:3]))


@pytest.mark.live
def test_replication_does_not_perturb_training():
    """Replication pauses snapshot weights but must not change the math:
    same losses with and without the §III-E cadence."""
    chain, data = _chain_and_data()
    B = 16
    quiet = run_live_training(chain, data, LiveConfig(
        num_workers=3, num_batches=B, protocol=_quiet_protocol(), lr=0.1))
    chain2, data2 = _chain_and_data()
    noisy = run_live_training(chain2, data2, LiveConfig(
        num_workers=3, num_batches=B,
        protocol=_quiet_protocol(chain_every=4, global_every=8), lr=0.1))
    np.testing.assert_allclose(noisy.losses, quiet.losses, rtol=1e-5,
                               atol=1e-6)


@pytest.mark.live
def test_replication_store_holds_cadence_snapshots():
    chain, data = _chain_and_data()
    B = 20
    cfg = LiveConfig(num_workers=3, num_batches=B,
                     protocol=_quiet_protocol(chain_every=5, global_every=10),
                     lr=0.1)
    coord = Coordinator(chain, lambda b: data[b % len(data)], cfg)
    res = coord.run()
    # global store: every layer present, freshest snapshot is the last
    # global cadence point (batch 10; batch 20 == horizon is never reached)
    assert coord.global_store.covers(chain.num_layers)
    assert set(coord.global_store.batches().values()) == {10}
    # chain replicas: worker i+1 holds stage i's layers @ last chain point
    part = uniform_partition(chain.num_layers, 3)
    for s in range(3):
        holder = coord.workers[(s + 1) % 3]
        a, e = part.ranges[s]
        for j in range(a, e + 1):
            assert holder.replicas.has(j)
            assert holder.replicas.get(j)[0] == 15
    # version retention stayed within the vertical-sync bound
    for dev, hw in res.stash_high_water.items():
        assert hw <= 3 + 1, (dev, hw)


@pytest.mark.live
def test_kill_worker_recovers_with_redistributed_weights():
    """Kill worker 1 mid-run: the run completes ALL batches on 2 survivors
    and the loss stays continuous (no reset to untrained level)."""
    chain, data = _chain_and_data()
    B = 36
    res = run_live_training(chain, data, LiveConfig(
        num_workers=3, num_batches=B,
        protocol=ProtocolConfig(chain_every=10, global_every=20,
                                repartition_first_at=5,
                                repartition_every=15, detect_timeout=0.4),
        lr=0.1, kill=(1, 16)))
    assert not np.isnan(res.losses).any()
    assert len(res.recoveries) == 1
    assert res.recoveries[0]["failed"] == [1]
    assert len(res.final_partition) == 2
    restart = res.recoveries[0]["restart"]
    untrained = float(np.median(res.losses[:3]))
    post = float(np.median(res.losses[restart:restart + 5]))
    assert post < 0.7 * untrained, (post, untrained)


@pytest.mark.live
def test_kill_last_worker_recovers_via_central_chain_replica():
    """The LAST stage's chain replica lives on the central node (§III-E);
    killing it exercises the Algorithm-1 special case."""
    chain, data = _chain_and_data()
    res = run_live_training(chain, data, LiveConfig(
        num_workers=3, num_batches=24,
        protocol=ProtocolConfig(chain_every=8, global_every=16,
                                repartition_first_at=4,
                                repartition_every=100, detect_timeout=0.4),
        lr=0.1, kill=(2, 10)))
    assert not np.isnan(res.losses).any()
    assert len(res.recoveries) == 1 and res.recoveries[0]["failed"] == [2]
    assert len(res.final_partition) == 2


@pytest.mark.live
def test_failure_right_after_repartition_uses_global_backstop():
    """A kill AFTER a re-partition but BEFORE the next chain cadence means
    chain replicas still cover the old slices; recovery must fall back to
    the central global store instead of leaving layers unserved."""
    chain, data = _chain_and_data()
    specs = [DeviceSpec("c", 1.0), DeviceSpec("a", 1.0),
             DeviceSpec("slow", 4.0)]
    res = run_live_training(chain, data, LiveConfig(
        num_workers=3, num_batches=24,
        protocol=ProtocolConfig(chain_every=15, global_every=20,
                                repartition_first_at=5,
                                repartition_every=10_000,
                                detect_timeout=0.4),
        lr=0.1, device_specs=specs, bandwidth=uniform_bandwidth(3, 1e9),
        capacity_source="spec", kill=(1, 7)))
    assert not np.isnan(res.losses).any()
    assert len(res.recoveries) == 1
    assert len(res.partitions) >= 3          # repart @5, then recovery
    assert len(res.final_partition) == 2


@pytest.mark.live
def test_kill_at_segment_boundary_detected_in_next_segment():
    """A worker that dies right as a segment drains (its seg_done already
    sent) must not stall the control plane: replication logs the ack
    shortfall and the next segment's heartbeat monitor runs recovery."""
    chain, data = _chain_and_data()
    res = run_live_training(chain, data, LiveConfig(
        num_workers=3, num_batches=20,
        protocol=ProtocolConfig(chain_every=10, global_every=20,
                                repartition_first_at=5,
                                repartition_every=10_000,
                                detect_timeout=0.4),
        lr=0.1, kill=(2, 9)))
    assert not np.isnan(res.losses).any()
    assert len(res.recoveries) == 1 and res.recoveries[0]["failed"] == [2]


@pytest.mark.live
def test_kill_at_boundary_before_repartition_recovers():
    """The nastiest §III-F window: the victim dies at the LAST batch of a
    segment (its seg_done already sent, so in-segment detection cannot
    fire) and a RE-PARTITION is due at the very next control point. The
    redistribution must fail fast on the corpse's heartbeat silence and
    hand over to recovery — not wedge for segment_timeout, not install
    stale backstop weights, not crash the run."""
    chain, data = _chain_and_data()
    specs = [DeviceSpec("central", 1.0), DeviceSpec("peer", 1.0),
             DeviceSpec("slow", 4.0)]
    profile = chain.measure_profile(data[0], repeats=2)
    res = run_live_training(chain, data, LiveConfig(
        num_workers=3, num_batches=20,
        protocol=ProtocolConfig(chain_every=10_000, global_every=10_000,
                                repartition_first_at=5,
                                repartition_every=10_000,
                                detect_timeout=0.4),
        lr=0.1, device_specs=specs, bandwidth=uniform_bandwidth(3, 1e9),
        profile=profile, capacity_source="spec", kill=(1, 4),
        segment_timeout=30.0))
    assert not np.isnan(res.losses).any()
    assert len(res.recoveries) == 1 and res.recoveries[0]["failed"] == [1]
    assert len(res.final_partition) == 2
    # no stale-weight swap: post-recovery losses keep improving
    restart = res.recoveries[0]["restart"]
    untrained = float(np.median(res.losses[:3]))
    post = float(np.median(res.losses[restart:restart + 5]))
    assert post < 0.9 * untrained, (post, untrained)


@pytest.mark.live
def test_post_recovery_partition_matches_simulator_prediction():
    """Acceptance: the live runtime's post-failure partition equals what
    PipelineSimulator predicts for the same failure on the same device
    specs — both sides run the SAME runtime/protocol.py decisions."""
    chain, data = _chain_and_data()
    specs = [DeviceSpec("central", 1.0), DeviceSpec("peer", 1.0),
             DeviceSpec("slow", 4.0)]
    bw = uniform_bandwidth(3, 1e9)       # compute-bound partitions
    profile = chain.measure_profile(data[0], repeats=2)
    B = 30
    proto = ProtocolConfig(chain_every=10, global_every=20,
                           repartition_first_at=5, repartition_every=15,
                           detect_timeout=0.4)

    live = run_live_training(chain, data, LiveConfig(
        num_workers=3, num_batches=B, protocol=proto, lr=0.1,
        device_specs=specs, bandwidth=bw, profile=profile,
        capacity_source="spec", kill=(1, 12)))

    sim = PipelineSimulator(SimConfig(
        devices=specs, profile=profile, bandwidth=bw, num_batches=B,
        chain_every=proto.chain_every, global_every=proto.global_every,
        repartition_first_at=proto.repartition_first_at,
        repartition_every=proto.repartition_every))
    pred = sim.run(fail=(1, 15))

    assert len(live.recoveries) == 1
    live_points = [tuple(int(p) for p in pts) for _, pts in live.partitions]
    sim_points = [tuple(int(p) for p in pts) for _, pts in pred.partitions]
    assert live_points[-1] == sim_points[-1]
    # the recovery decision itself matches the simulator's
    assert tuple(int(p) for p in live.recoveries[0]["partition"]) \
        == sim_points[-1]


@pytest.mark.live
def test_heartbeat_loss_does_not_corrupt_training():
    """Dropped heartbeats at worst trigger the transient-stall path
    (probe -> ALL_NORMAL -> restart segment); training still completes and
    no worker is evicted."""
    chain, data = _chain_and_data()
    fault = FaultSpec(drop=0.7, seed=3,
                      protect=("act", "grad", "segment", "seg_done",
                               "commit", "loss", "replicate", "replicated",
                               "chain_put", "global_put", "fetch_req",
                               "fetch_res", "repart", "recover", "ready",
                               "probe", "probe_ack", "stop"))
    res = run_live_training(chain, data, LiveConfig(
        num_workers=3, num_batches=12,
        protocol=_quiet_protocol(detect_timeout=0.6), lr=0.1, fault=fault))
    assert not np.isnan(res.losses).any()
    assert not res.recoveries                 # nobody was (wrongly) evicted


@pytest.mark.live
def test_emulated_heterogeneity_repartitions_away_from_slow_worker():
    """A sleep-emulated 6x-slower device ends up with the fewest layers
    after dynamic re-partition on MEASURED capacities (paper Fig. 5)."""
    chain, data = _chain_and_data(num_layers=9)
    specs = [DeviceSpec("c", 1.0), DeviceSpec("a", 1.0),
             DeviceSpec("slow", 6.0)]
    res = run_live_training(chain, data, LiveConfig(
        num_workers=3, num_batches=16,
        protocol=_quiet_protocol(repartition_first_at=8,
                                 repartition_every=10_000),
        lr=0.1, device_specs=specs, bandwidth=uniform_bandwidth(3, 1e9),
        emulate_capacity=True, capacity_source="measured"))
    assert not np.isnan(res.losses).any()
    final = np.diff(np.concatenate([[-1], np.asarray(res.final_partition)]))
    assert final[2] <= min(final[0], final[1])
    assert res.capacities[2] > 2.0            # measured it as slow


# ===================== worker errors and device placement =================

@pytest.mark.live
def test_worker_exception_ends_run_with_its_error(monkeypatch):
    """A worker thread that raises (a compile refusal, device OOM, a bug)
    ends the run with that error at once: its heartbeat stops and the
    coordinator raises ``WorkerError`` instead of waiting out
    ``segment_timeout`` and restarting around a silent worker."""
    from repro.runtime import stage_executor
    from repro.runtime.live import WorkerError

    boom = RuntimeError("refused by the compiler")
    real_step = stage_executor.StageExecutor.step
    calls = []

    def failing_step(self, *a, **kw):
        calls.append(1)
        if len(calls) == 5:
            raise boom
        return real_step(self, *a, **kw)

    monkeypatch.setattr(stage_executor.StageExecutor, "step", failing_step)
    chain, data = _chain_and_data()
    t0 = time.monotonic()
    with pytest.raises(WorkerError) as info:
        run_live_training(chain, data, LiveConfig(
            num_workers=3, num_batches=12, protocol=_quiet_protocol(),
            lr=0.1, segment_timeout=120.0))
    assert time.monotonic() - t0 < 30.0
    assert info.value.__cause__ is boom
    assert not any(t.name.startswith("worker-") and t.is_alive()
                   for t in threading.enumerate())


@pytest.mark.live
def test_workers_keep_their_buffers_on_their_own_device(monkeypatch):
    """Worker ``dev`` holds its stage buffers on ``jax.devices()[dev % n]``
    (the host's virtual devices here), and training matches the same run
    with every worker pinned to device 0. A §III-F kill redistributes
    between devices and every survivor stays on its own. (Where a run
    resumes after a kill depends on what had committed when the failure
    was detected, so only the losses before the kill are compared there.)"""
    from repro.runtime import live

    devices = jax.devices()
    assert len(devices) >= 4

    def run(kill=None):
        chain, data = _chain_and_data()
        return run_live_training(chain, data, LiveConfig(
            num_workers=4, num_batches=20,
            protocol=_quiet_protocol(chain_every=5, global_every=10,
                                     detect_timeout=0.4),
            lr=0.1, kill=kill))

    spread, killed = run(), run(kill=(1, 12))
    monkeypatch.setattr(live, "stage_device", lambda dev: devices[0])
    pinned = run()
    own = {d: [devices[d].id] for d in range(4)}
    assert spread.stage_devices == own
    assert killed.stage_devices == own
    assert all(ids == [devices[0].id]
               for ids in pinned.stage_devices.values())
    np.testing.assert_allclose(spread.losses, pinned.losses, rtol=1e-6)
    assert [r["failed"] for r in killed.recoveries] == [[1]]
    assert len(killed.final_partition) == 3
    assert np.isfinite(killed.losses).all()
    np.testing.assert_allclose(killed.losses[:12], spread.losses[:12],
                               rtol=1e-6)
