"""Durable control plane: manifest round-trips, the crash-consistent
disk replica tier, cold resume with loss continuity, and the seq/ack
retransmit window on the data plane (docs/protocol.md §7–§8).
"""
import json
import os

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.checkpoint.manifest import RunManifest, atomic_write_json
from repro.checkpoint.replication_store import (DiskLayerTier,
                                                DurableLayerReplicaStore)
from repro.run import Run, RunConfig, start_run
from repro.runtime.live import LiveConfig
from repro.runtime.protocol import ProtocolConfig
from repro.runtime.transport import FaultSpec, Transport, TransportBase
from repro.runtime.workload import WorkloadSpec


# --------------------------------------------------------------------------
# RunConfig <-> manifest round-trip
# --------------------------------------------------------------------------

@given(kind=st.sampled_from(["mlp", "mobilenet"]),
       seed=st.integers(0, 10_000), layers=st.integers(2, 24),
       workers=st.integers(2, 6), batches=st.integers(1, 200),
       lr=st.floats(1e-4, 1.0), momentum=st.floats(0.0, 0.99),
       chain_every=st.integers(1, 50), global_every=st.integers(1, 100),
       tier=st.sampled_from(["off", "fp16", "int8"]),
       reliable=st.sampled_from([False, True]),
       transport=st.sampled_from(["queue", "tcp"]))
@settings(max_examples=40, deadline=None)
def test_runconfig_manifest_round_trip(kind, seed, layers, workers, batches,
                                       lr, momentum, chain_every,
                                       global_every, tier, reliable,
                                       transport):
    """to_manifest -> JSON -> from_manifest reproduces the config exactly
    (the contract that makes ``--resume`` ignore the command line)."""
    cfg = RunConfig(
        workload=WorkloadSpec(kind=kind, seed=seed, num_layers=layers),
        live=LiveConfig(
            num_workers=workers, num_batches=batches, lr=lr,
            momentum=momentum,
            protocol=ProtocolConfig(chain_every=chain_every,
                                    global_every=global_every),
            wire_compress=tier, reliable_data=reliable),
        transport=transport)
    doc = json.loads(json.dumps(cfg.to_manifest()))
    assert RunConfig.from_manifest(doc) == cfg


def test_manifest_save_load_atomic(tmp_path):
    d = str(tmp_path)
    assert RunManifest.try_load(d) is None
    m = RunManifest(config={"transport": "queue"},
                    state={"last_committed": 7, "worker_ids": [0, 1, 2]})
    m.save(d)
    back = RunManifest.load(d)
    assert back.last_committed == 7
    assert back.config == m.config and back.state == m.state
    # a later save atomically replaces (no partial reads possible: the
    # write goes to a tmp file first)
    RunManifest(config=m.config, state={"last_committed": 9}).save(d)
    assert RunManifest.load(d).last_committed == 9


def test_atomic_write_json_leaves_no_tmp(tmp_path):
    path = os.path.join(str(tmp_path), "x.json")
    atomic_write_json(path, {"a": 1})
    assert json.load(open(path)) == {"a": 1}
    assert [f for f in os.listdir(str(tmp_path))
            if f.endswith(".tmp")] == []


# --------------------------------------------------------------------------
# DiskLayerTier crash consistency
# --------------------------------------------------------------------------

class TestDiskLayerTier:
    def test_unsynced_put_is_invisible_after_crash(self, tmp_path):
        d = str(tmp_path)
        t = DiskLayerTier(d)
        t.put(0, 8, np.arange(4, dtype=np.float32))
        # no sync(): a SIGKILL here must leave NOTHING committed — the
        # .bin exists but the index never named it
        t2 = DiskLayerTier(d)
        assert t2.load() == {} and t2.batches() == {}

    def test_synced_put_survives_reopen(self, tmp_path):
        d = str(tmp_path)
        t = DiskLayerTier(d)
        for j in range(3):
            t.put(j, 16, np.full(4, j, np.float32))
        t.sync()
        got = DiskLayerTier(d).load()
        assert set(got) == {0, 1, 2}
        for j, (b, arr) in got.items():
            assert b == 16 and (arr == j).all()

    def test_orphans_are_garbage_collected(self, tmp_path):
        d = str(tmp_path)
        t = DiskLayerTier(d)
        t.put(0, 8, np.ones(4, np.float32))
        t.sync()
        # simulate a crash mid-put: stray tmp + unindexed bin
        open(os.path.join(d, "layer_00001.00000009.bin.tmp"), "wb").close()
        open(os.path.join(d, "layer_00001.00000009.bin"), "wb").close()
        t.put(0, 16, 2 * np.ones(4, np.float32))
        t.sync()
        names = set(os.listdir(d))
        assert "layer_00001.00000009.bin.tmp" not in names
        assert "layer_00001.00000009.bin" not in names
        b, arr = DiskLayerTier(d).load()[0]
        assert b == 16 and (arr == 2).all()

    def test_restamp_bumps_batch_without_rewrite(self, tmp_path):
        d = str(tmp_path)
        t = DiskLayerTier(d)
        t.put(0, 8, np.ones(4, np.float32))
        t.sync()
        before = os.path.getmtime(
            os.path.join(d, t._index[0]["file"]))
        t.restamp(0, 24)                     # delta-skip: same bytes
        t.sync()
        b, arr = DiskLayerTier(d).load()[0]
        assert b == 24 and (arr == 1).all()
        after = os.path.getmtime(os.path.join(d, t._index[0]["file"]))
        assert after == before               # the file was not rewritten

    def test_stale_put_ignored(self, tmp_path):
        t = DiskLayerTier(str(tmp_path))
        t.put(0, 16, np.ones(4, np.float32))
        t.put(0, 8, np.zeros(4, np.float32))   # older stamp: ignored
        t.sync()
        b, arr = DiskLayerTier(str(tmp_path)).load()[0]
        assert b == 16 and (arr == 1).all()


def test_durable_store_reports_disk_and_memory_separately(tmp_path):
    s = DurableLayerReplicaStore(str(tmp_path))
    s.put(0, 8, np.ones(8, np.float32), s.GLOBAL)
    s.put(0, 12, np.ones(8, np.float32), s.CHAIN)    # memory-only tier
    s.sync()
    rep = s.nbytes_report()
    assert rep["on_disk"] == 8 * 4                   # GLOBAL mirror only
    assert rep["per_tier"][s.GLOBAL] == 8 * 4
    assert rep["per_tier"][s.CHAIN] == 8 * 4
    # a reopened store replays the disk index into the GLOBAL tier
    s2 = DurableLayerReplicaStore(str(tmp_path))
    b, arr = s2.get(0, tier=s2.GLOBAL)
    assert b == 8 and (np.asarray(arr) == 1).all()


# --------------------------------------------------------------------------
# Cold resume with loss continuity (queue cluster)
# --------------------------------------------------------------------------

def _durable_config(run_dir, num_batches, lr=0.01):
    # modest lr: the seam batches right after a resume run on the
    # committed snapshot instead of the vertically-synced stale versions
    # an uninterrupted pipeline uses, and that gap scales with lr
    return RunConfig(
        workload=WorkloadSpec(kind="mlp", seed=0, num_layers=8),
        live=LiveConfig(
            num_workers=3, num_batches=num_batches, lr=lr,
            protocol=ProtocolConfig(chain_every=8, global_every=8,
                                    repartition_first_at=10_000,
                                    repartition_every=10_000,
                                    detect_timeout=0.5),
            run_dir=run_dir))


@pytest.mark.live
def test_queue_cold_resume_loss_continuity(tmp_path):
    """A durable run stopped after its first commits resumes from the
    manifest and tracks an uninterrupted reference run."""
    run_dir = str(tmp_path / "run")
    total = 24
    ref = start_run(_durable_config(None, total)).wait(timeout=120)

    # the "crashed" run: trains 16 batches, committing at global points
    start_run(_durable_config(run_dir, 16)).wait(timeout=120)
    m = RunManifest.load(run_dir)
    assert m.last_committed >= 0

    resumed = Run.resume(run_dir, num_batches=total)
    start = resumed.config.live.start_batch
    assert start == m.last_committed + 1
    res = resumed.start().wait(timeout=120)

    tail = [(b, l) for b, l in res.loss_log if b >= start]
    assert len(tail) == total - start
    div = max(abs(float(ref.losses[b]) - float(l)) for b, l in tail)
    assert div < 0.05, f"loss diverged across resume: {div}"


@pytest.mark.live
def test_resume_of_uncommitted_run_starts_fresh(tmp_path):
    """A manifest written before any global commit resumes from batch 0."""
    run_dir = str(tmp_path / "run")
    cfg = _durable_config(run_dir, 4)      # ends before the b=8 commit
    start_run(cfg).wait(timeout=120)
    resumed = Run.resume(run_dir, num_batches=6)
    assert resumed.config.live.start_batch == 0
    res = resumed.start().wait(timeout=120)
    assert not np.isnan(res.losses).any()


def test_run_status_and_stop(tmp_path):
    import time
    run = Run(_durable_config(str(tmp_path / "run"), 2000))
    assert run.status()["state"] == "created"
    run.start()
    deadline = time.monotonic() + 60
    while run.status()["chains"].get(0, {}).get(
            "progress", {}).get("batches_done", 0) < 2:   # it trains
        assert time.monotonic() < deadline
        time.sleep(0.01)
    run.stop()                              # wind down at a batch boundary
    res = run.wait(timeout=120)
    assert run.status()["state"] == "finished"
    assert 2 <= len(res.loss_log) < 2000


# --------------------------------------------------------------------------
# Reliable data plane: seq/ack retransmit window
# --------------------------------------------------------------------------

def _pump(t, node, want, deadline=20.0):
    import time
    got = []
    end = time.monotonic() + deadline
    while len(got) < want and time.monotonic() < end:
        m = t.recv(node, timeout=0.05)
        if m is not None:
            got.append(m)
    return got


def test_lossy_queue_delivers_exactly_once_in_order():
    """40% loss on acts AND acks: every frame still arrives exactly once,
    in order, via retransmission."""
    t = Transport(FaultSpec(drop=0.4, seed=7), reliable=True, rto=0.05)
    t.register(0)
    t.register(1)
    n = 30
    for i in range(n):
        t.send(0, 1, "act", {"i": i})
    msgs = _pump(t, 1, n)
    t.close()
    assert [m.payload["i"] for m in msgs] == list(range(n))
    assert all(m.kind == "act" for m in msgs)
    assert t.stats["retransmits"] > 0        # loss was actually exercised
    assert t.stats["rel_dups"] >= 0          # dropped acks cause dup copies


def test_unreliable_kinds_bypass_the_window():
    """Control traffic is NOT wrapped: the protocol's own timeouts own
    its loss story (and tests depend on plain-send semantics)."""
    t = Transport(reliable=True, rto=0.05)
    t.register(0)
    t.register(1)
    t.send(0, 1, "ctl", {"x": 1})
    m = t.recv(1, timeout=1.0)
    t.close()
    assert m.kind == "ctl" and m.payload == {"x": 1}
    assert t._rel_window == {}


def test_out_of_order_retransmit_released_in_order():
    """A frame that overtakes a lost predecessor is buffered until the
    retransmit fills the gap — receivers see an ordered stream."""
    t = Transport(reliable=True, rto=10.0)   # rto huge: we retransmit by hand
    t.register(0)
    t.register(1)
    w0 = t._rel_wrap(0, 1, "act", {"i": 0})
    w1 = t._rel_wrap(0, 1, "act", {"i": 1})
    # deliver out of order: seq 1 first (buffered), then seq 0 (releases both)
    assert t._rel_deliver(0, 1, "act", w1) == (True, [])
    fresh, released = t._rel_deliver(0, 1, "act", w0)
    t.close()
    assert fresh and [b["i"] for _, b in released] == [0, 1]


def test_reliable_reset_fences_a_new_era():
    """Frames from before a reset (stale era) are dropped, not buffered:
    a re-adopted pipeline's sequence space must not collide with the old
    incarnation's in-flight retransmits (docs/protocol.md §7)."""
    t = Transport(reliable=True, rto=10.0)
    t.register(0)
    t.register(1)
    stale = t._rel_wrap(0, 1, "act", {"i": 0})   # era 0, seq 0
    t.reliable_reset()                            # era 1, sequences restart
    fresh0 = t._rel_wrap(0, 1, "act", {"i": 100})  # era 1, seq 0
    assert t._rel_deliver(0, 1, "act", fresh0)[0] is True
    # the old incarnation's frame arrives late: same (src, dst, seq=0)
    assert t._rel_deliver(0, 1, "act", stale) == (False, [])
    assert t.stats["rel_stale"] == 1
    # an ack stamped with the old era must not retire a current-era frame
    seq0 = t._rel_wrap(0, 1, "act", {"i": 101})["_seq"]
    t._rel_deliver(1, 0, "ack", {"era": 0, "floor": seq0 + 1, "seqs": []})
    assert (0, 1, seq0) in t._rel_window
    t._rel_deliver(1, 0, "ack", {"era": 1, "floor": seq0 + 1, "seqs": []})
    assert (0, 1, seq0) not in t._rel_window
    t.close()


def test_factory_builds_both_transports():
    q = TransportBase.create("queue", reliable=True, rto=0.1)
    assert isinstance(q, Transport) and q._rel_on
    q.close()
    with pytest.raises(ValueError):
        TransportBase.create("tcp")              # needs addr_of + local
    with pytest.raises(ValueError):
        TransportBase.create("carrier-pigeon")


@pytest.mark.live
def test_lossy_socket_transport_delivers_exactly_once():
    """The same retransmit window over real TCP sockets."""
    from repro.runtime.net import SocketTransport, free_port

    addr_of = {0: ("127.0.0.1", free_port()), 1: ("127.0.0.1", free_port())}
    a = SocketTransport(addr_of, local=(0,), fault=FaultSpec(drop=0.3,
                                                             seed=3),
                        reliable=True, rto=0.05)
    b = SocketTransport(addr_of, local=(1,), reliable=True, rto=0.05)
    try:
        n = 20
        for i in range(n):
            a.send(0, 1, "act", {"i": i, "x": np.float32(i)})
        msgs = _pump(b, 1, n)
        assert [int(m.payload["i"]) for m in msgs] == list(range(n))
        assert a.stats["retransmits"] > 0
    finally:
        a.close()
        b.close()


@pytest.mark.live
def test_lossy_live_run_survives_on_retransmits():
    """A live queue cluster with 15% data-plane loss and reliable_data=True
    completes every batch WITHOUT transient-stall drains: the window turns
    a dropped act/grad into a ~rto resend."""
    protect = ("hb", "hello", "install", "abort", "segment", "seg_done",
               "commit", "loss", "replicate", "replicated", "chain_put",
               "global_put", "fetch_req", "fetch_res", "repart", "recover",
               "ready", "probe", "probe_ack", "stop")
    cfg = RunConfig(
        workload=WorkloadSpec(kind="mlp", seed=0, num_layers=8),
        live=LiveConfig(
            num_workers=3, num_batches=12, lr=0.1,
            protocol=ProtocolConfig(chain_every=8, global_every=16,
                                    repartition_first_at=10_000,
                                    repartition_every=10_000,
                                    detect_timeout=2.0),
            fault=FaultSpec(drop=0.15, seed=5, protect=protect),
            reliable_data=True))
    res = start_run(cfg).wait(timeout=180)
    assert not np.isnan(res.losses).any()
    assert not res.recoveries
    assert not [e for _, e in res.events if "transient stall" in e]
    assert res.transport_stats["retransmits"] > 0
