"""Chain fleets (``runtime/fleet.py``): aggregation math, the barrier
decision, per-chain partition independence, the redesigned multi-chain
Run API (nested status schema + versioned fleet manifests), and the
degrade-to-M-1 / re-admission fault path.
"""
import json

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.partition import solve_fleet_partitions, solve_partition
from repro.core.stash import tree_mean
from repro.run import _ARG_MAP, Run, RunConfig, start_run
from repro.runtime.fleet import (FleetAggregator, FleetConfig,
                                 FleetCoordinator, fleet_average,
                                 layer_aggregate_op)
from repro.runtime.live import LiveConfig
from repro.runtime.protocol import ProtocolConfig, aggregation_ready
from repro.runtime.workload import WorkloadSpec


# --------------------------------------------------------------------------
# aggregation math
# --------------------------------------------------------------------------

@given(chains=st.integers(1, 5), layers=st.integers(1, 6),
       width=st.integers(1, 32), seed=st.integers(0, 1000))
@settings(max_examples=30, deadline=None)
def test_fleet_average_numpy_parity(chains, layers, width, seed):
    """The fleet mean is exactly numpy's element-wise mean per layer."""
    rng = np.random.default_rng(seed)
    snaps = [{j: rng.normal(size=width).astype(np.float32)
              for j in range(layers)} for _ in range(chains)]
    out = fleet_average(snaps)
    assert sorted(out) == list(range(layers))
    for j in range(layers):
        expect = np.mean(np.stack([s[j] for s in snaps]), axis=0)
        np.testing.assert_allclose(out[j], expect, rtol=1e-5, atol=1e-6)


def test_fleet_average_rejects_mismatched_layers():
    with pytest.raises(AssertionError):
        fleet_average([{0: np.zeros(3, np.float32)},
                       {1: np.zeros(3, np.float32)}])


def test_layer_aggregate_op_matches_tree_mean():
    """The packed-buffer mean (what live/fleet installs) equals the plain
    pytree mean (what the semantics oracle uses by default)."""
    chain, _ = WorkloadSpec(kind="mlp", seed=3, num_layers=4).build()
    rng = np.random.default_rng(0)
    versions = []
    for _ in range(3):
        versions.append([
            {k: np.asarray(v) + rng.normal(size=np.shape(v)).astype(
                np.float32) for k, v in p.items()} for p in chain.params])
    op = layer_aggregate_op(chain.flat_layout())
    for j in range(chain.num_layers):
        trees = [v[j] for v in versions]
        got, want = op(j, trees), tree_mean(trees)
        for k in want:
            np.testing.assert_allclose(np.asarray(got[k]),
                                       np.asarray(want[k]), rtol=1e-5)


# --------------------------------------------------------------------------
# barrier decision + per-chain partitions (pure functions)
# --------------------------------------------------------------------------

def test_aggregation_ready_decision():
    # all live chains arrived -> publish, nobody degraded
    assert aggregation_ready([0, 1], {0: 1, 1: 1}, 0.0, 60.0) \
        == (True, frozenset())
    # missing chain, deadline not reached -> wait
    assert aggregation_ready([0, 1], {0: 1}, 1.0, 60.0) \
        == (False, frozenset())
    # deadline passed with at least one arrival -> publish, degrade no-shows
    assert aggregation_ready([0, 1, 2], {0: 1}, 61.0, 60.0) \
        == (True, frozenset({1, 2}))
    # nobody arrived -> keep waiting even past the deadline
    assert aggregation_ready([0, 1], {}, 61.0, 60.0) == (False, frozenset())


def test_solve_fleet_partitions_independence():
    """Each chain's §III-D split matches solving that chain alone — no
    cross-chain coupling (the fleet only meets at the barrier)."""
    times = [1.0, 1.0, 2.0, 1.0, 3.0, 1.0]
    sizes = [10.0] * 6
    caps = [[1.0, 1.0], [1.0, 3.0, 2.0]]
    bws = [[100.0], [100.0, 50.0]]
    fleet = solve_fleet_partitions(times, sizes, caps, bws)
    assert len(fleet) == 2
    for res, c, b in zip(fleet, caps, bws):
        solo = solve_partition(times, sizes, c, b)
        assert res.points == solo.points
        assert res.bottleneck == solo.bottleneck
    # heterogeneous clusters genuinely get different splits here
    assert fleet[0].counts != fleet[1].counts


def test_workload_shard_disjoint_and_identical_model():
    spec = WorkloadSpec(kind="mlp", seed=7, num_data_batches=9)
    chain0, b0 = spec.shard(0, 2).build()
    chain1, b1 = spec.shard(1, 2).build()
    assert len(b0) + len(b1) == 9
    # identical init (shared seed) ...
    for p, q in zip(chain0.params, chain1.params):
        np.testing.assert_array_equal(np.asarray(p["w"]), np.asarray(q["w"]))
    # ... disjoint strided data
    full = spec.build()[1]
    for got, want in zip(b0, full[0::2]):
        np.testing.assert_array_equal(np.asarray(got["x"]),
                                      np.asarray(want["x"]))
    for got, want in zip(b1, full[1::2]):
        np.testing.assert_array_equal(np.asarray(got["x"]),
                                      np.asarray(want["x"]))
    with pytest.raises(ValueError):
        WorkloadSpec(kind="mlp", num_data_batches=2).shard(2, 3).build()


# --------------------------------------------------------------------------
# FleetAggregator unit behaviour (no runtime, hand-driven threads)
# --------------------------------------------------------------------------

def _snap(val, layers=2):
    return {j: np.full(4, val, np.float32) for j in range(layers)}


def test_aggregator_two_chain_round():
    import threading
    agg = FleetAggregator(2, barrier_timeout=30.0)
    out = {}

    def chain(cid, val):
        out[cid] = agg.aggregate(cid, 5, _snap(val))

    ts = [threading.Thread(target=chain, args=(c, v))
          for c, v in ((0, 1.0), (1, 3.0))]
    [t.start() for t in ts]
    [t.join(timeout=10) for t in ts]
    for cid in (0, 1):
        np.testing.assert_allclose(out[cid][0], np.full(4, 2.0))
    assert agg.rounds == [{"batch": 5, "contributors": [0, 1],
                           "degraded": []}]
    assert agg.latest_round()[0] == 5


def test_aggregator_degrade_then_solo_and_readmit():
    agg = FleetAggregator(2, barrier_timeout=30.0)
    agg.chain_dead(1)
    # solo round: caller IS the mean -> nothing to install (None), but the
    # round still publishes so a re-admitted chain can seed from it
    assert agg.aggregate(0, 4, _snap(2.0)) is None
    b, seed = agg.latest_round()
    assert b == 4 and np.allclose(seed[0], 2.0)
    assert agg.live_chains() == [0]
    agg.chain_alive(1)
    assert agg.live_chains() == [0, 1]
    agg.close()
    assert agg.aggregate(0, 8, _snap(1.0)) is None   # closed -> unblock


# --------------------------------------------------------------------------
# config / manifest / API redesign
# --------------------------------------------------------------------------

@given(chains=st.integers(1, 4), every=st.integers(1, 50),
       timeout=st.floats(1.0, 600.0), min_w=st.integers(1, 3),
       readmit=st.booleans(),
       devices=st.sampled_from([None, ((1.0, 2.0), (1.0, 1.0))]))
@settings(max_examples=40, deadline=None)
def test_fleet_config_round_trip(chains, every, timeout, min_w, readmit,
                                 devices):
    if devices is not None:
        chains = len(devices)
    cfg = FleetConfig(chains=chains, aggregate_every=every,
                      barrier_timeout=timeout, min_chain_workers=min_w,
                      readmit=readmit, chain_devices=devices)
    doc = json.loads(json.dumps(cfg.to_doc()))
    assert FleetConfig.from_doc(doc) == cfg


@given(chains=st.integers(1, 3), every=st.integers(1, 20),
       transport=st.sampled_from(["queue", "tcp"]),
       workers=st.integers(2, 5))
@settings(max_examples=25, deadline=None)
def test_runconfig_v2_manifest_round_trip_with_fleet(chains, every,
                                                     transport, workers):
    cfg = RunConfig(
        workload=WorkloadSpec(kind="mlp", seed=1),
        live=LiveConfig(num_workers=workers, num_batches=12),
        fleet=FleetConfig(chains=chains, aggregate_every=every),
        transport=transport)
    doc = json.loads(json.dumps(cfg.to_manifest()))
    assert doc["version"] == 2
    assert RunConfig.from_manifest(doc) == cfg


def test_legacy_v1_manifest_loads_as_single_chain():
    """Pre-fleet manifests (no ``fleet`` block, version 1) keep loading —
    and mean exactly what they used to: one chain."""
    cfg = RunConfig(workload=WorkloadSpec(kind="mlp"),
                    live=LiveConfig(num_workers=3, num_batches=8))
    doc = json.loads(json.dumps(cfg.to_manifest()))
    doc.pop("fleet")
    doc["version"] = 1
    back = RunConfig.from_manifest(doc)
    assert back.fleet == FleetConfig()
    assert back.fleet.chains == 1
    with pytest.raises(ValueError):
        RunConfig.from_manifest({**doc, "version": 99})


def test_kill_chain_never_reaches_the_manifest():
    cfg = FleetConfig(chains=2, kill_chain=(1, 9))
    assert "kill_chain" not in cfg.to_doc()
    assert FleetConfig.from_doc(cfg.to_doc()).kill_chain is None


def test_arg_map_matches_live_train_parser():
    """Every ``_ARG_MAP`` row is a real ``live_train`` flag and every
    config-bearing flag has a row — adding a flag is a one-line edit, and
    this invariant keeps the table from drifting."""
    from repro.launch.live_train import build_parser
    dests = {a.dest for a in build_parser()._actions}
    missing = sorted(set(_ARG_MAP) - dests)
    assert not missing, f"_ARG_MAP rows without a CLI flag: {missing}"


def test_status_nested_schema_before_start():
    run = Run(RunConfig(workload=WorkloadSpec(kind="mlp"),
                        live=LiveConfig(num_workers=3, num_batches=8),
                        fleet=FleetConfig(chains=2)))
    s = run.status()
    assert s["state"] == "created"
    assert s["fleet"]["chains"] == 2
    assert s["chains"] == {}             # nothing launched yet
    assert s["fleet"]["live"] == [] and s["fleet"]["rounds"] == 0


def test_fleet_rejects_resume_and_addr_of():
    cfg = RunConfig(workload=WorkloadSpec(kind="mlp"),
                    live=LiveConfig(num_workers=3, num_batches=8),
                    fleet=FleetConfig(chains=2))
    run = Run(cfg)
    run._resume_state = {"last_committed": 3}
    with pytest.raises(RuntimeError, match="resume"):
        run._run_impl()
    with pytest.raises(RuntimeError, match="single-chain"):
        Run(cfg, addr_of={1: ("127.0.0.1", 1)})._run_impl()


# --------------------------------------------------------------------------
# live fleets (threaded queue runtime; TCP parity is in the slow tier)
# --------------------------------------------------------------------------

def _live_cfg(batches=12, workers=3, **kw):
    return LiveConfig(num_workers=workers, num_batches=batches, lr=0.1,
                      protocol=ProtocolConfig(detect_timeout=0.75), **kw)


@pytest.mark.live
def test_queue_fleet_two_chains_aggregates():
    spec = WorkloadSpec(kind="mlp", seed=0, num_data_batches=8)
    fc = FleetCoordinator(spec, _live_cfg(batches=12),
                          FleetConfig(chains=2, aggregate_every=5),
                          transport="queue")
    res = fc.run()
    assert not res.chain_errors
    assert [r["batch"] for r in res.rounds] == [5, 10]
    assert all(r["contributors"] == [0, 1] for r in res.rounds)
    assert res.incarnations == {0: 1, 1: 1}
    assert np.isfinite(res.losses).all()
    assert res.final_flats and set(res.final_flats) == set(range(8))


@pytest.mark.live
def test_fleet_status_nested_schema_live():
    spec = WorkloadSpec(kind="mlp", seed=0, num_data_batches=8)
    run = start_run(RunConfig(
        workload=spec, live=_live_cfg(batches=10),
        fleet=FleetConfig(chains=2, aggregate_every=4)))
    res = run.wait()
    s = run.status()
    assert s["state"] == "finished"
    assert s["fleet"]["rounds"] == len(res.rounds) >= 1
    assert set(s["chains"]) <= {0, 1}
    for st_ in s["chains"].values():
        assert {"progress", "wire", "membership"} <= set(st_)
    assert max(c["progress"]["batches_done"]
               for c in s["chains"].values()) == 10


@pytest.mark.live
def test_chain_death_degrades_then_readmits():
    """Kill ALL of chain 1's workers mid-run: the fleet degrades to chain 0
    (solo rounds), then re-admits a second incarnation of chain 1 seeded
    from the next published round — which finishes cleanly."""
    spec = WorkloadSpec(kind="mlp", seed=0, num_data_batches=8)
    fc = FleetCoordinator(
        spec, _live_cfg(batches=18),
        FleetConfig(chains=2, aggregate_every=6, min_chain_workers=2,
                    kill_chain=(1, 8)),
        transport="queue")
    res = fc.run()
    assert not res.chain_errors, res.chain_errors
    assert res.incarnations[1] >= 2
    solo = [r for r in res.rounds if r["contributors"] == [0]]
    assert solo, res.rounds
    assert res.chains[1] is not None
    assert any("re-admitting chain 1" in e for _, e in res.events)


@pytest.mark.live
def test_chain_collapse_without_readmit_reports_error():
    """min_chain_workers floor: a chain that cannot hold the floor
    collapses as a unit, and with readmit=False the fleet reports it."""
    spec = WorkloadSpec(kind="mlp", seed=0, num_data_batches=8)
    fc = FleetCoordinator(
        spec, _live_cfg(batches=16),
        FleetConfig(chains=2, aggregate_every=6, min_chain_workers=2,
                    kill_chain=(1, 7), readmit=False),
        transport="queue")
    res = fc.run()
    assert 1 in res.chain_errors
    assert "min_workers" in res.chain_errors[1]
    assert res.chains[1] is None
    assert res.chains[0] is not None and not np.isnan(
        res.chains[0].losses).any()
    assert res.incarnations[1] == 1


@pytest.mark.live
@pytest.mark.slow
def test_queue_tcp_fleet_round_parity():
    """The barrier decision is the pure ``aggregation_ready`` — so the
    SAME fleet config produces the SAME rounds on both transports."""
    spec = WorkloadSpec(kind="mlp", seed=0, num_data_batches=8)

    def rounds(transport):
        fc = FleetCoordinator(
            spec, _live_cfg(batches=8, workers=2),
            FleetConfig(chains=2, aggregate_every=4), transport=transport)
        res = fc.run()
        assert not res.chain_errors, res.chain_errors
        return res.rounds

    assert rounds("queue") == rounds("tcp")
