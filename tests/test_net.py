"""Multi-process TCP transport (runtime/net.py): frame-level unit tests on
real localhost sockets, the queue/TCP protocol-parity acceptance test, and
§III-F recovery from an actually SIGKILLed worker process.
"""
import time

import numpy as np
import pytest

from repro.runtime.devices import DeviceSpec, WorkloadProfile, \
    uniform_bandwidth
from repro.runtime.live import COORD, LiveConfig, run_live_training
from repro.runtime.net import (SocketTransport, cluster_addresses, free_port,
                               parse_peers, run_tcp_training)
from repro.runtime.protocol import ProtocolConfig
from repro.runtime.transport import FaultSpec
from repro.runtime.workload import WorkloadSpec

HOST = "127.0.0.1"


def _pair():
    """Two SocketTransports on localhost: 'coordinator side' hosting COORD
    and dev 0, and a single-node 'worker side' for dev 1."""
    addr_of = cluster_addresses(2, HOST)
    a = SocketTransport(addr_of, local=(COORD, 0))
    b = SocketTransport(addr_of, local=(1,))
    return a, b


class TestSocketTransport:
    def test_loopback_and_cross_process_round_trip(self):
        a, b = _pair()
        try:
            # loopback between the two node ids of one process still goes
            # through the codec: the receiver gets a fresh deserialized copy
            x = np.arange(64, dtype=np.float32)
            assert a.send(COORD, 0, "install", {"range": (0, 3),
                                                "layers": {0: x}})
            m = a.recv(0, timeout=1.0)
            assert m.kind == "install" and m.payload["range"] == (0, 3)
            assert m.payload["layers"][0] is not x
            np.testing.assert_array_equal(m.payload["layers"][0], x)
            # a real TCP hop, both directions
            assert a.send(0, 1, "act", (4, 2, x))
            m = b.recv(1, timeout=5.0)
            assert m.kind == "act" and m.payload[:2] == (4, 2)
            np.testing.assert_array_equal(m.payload[2], x)
            b.send(1, COORD, "hb", {"t": 0.5})
            m = a.recv(COORD, timeout=5.0)
            assert (m.kind, m.src, m.dst) == ("hb", 1, COORD)
        finally:
            a.close()
            b.close()

    def test_kill_fences_both_directions(self):
        a, b = _pair()
        try:
            a.kill(1)
            assert not a.send(0, 1, "act", (0, 0, None))
            assert a.stats["to_dead"] == 1
            b.send(1, COORD, "hb", {"t": 1.0})       # zombie traffic
            time.sleep(0.4)
            assert a.recv(COORD, timeout=0.2) is None
            a.revive(1)
            b.send(1, COORD, "hb", {"t": 2.0})
            assert a.recv(COORD, timeout=5.0).kind == "hb"
        finally:
            a.close()
            b.close()

    def test_reconnect_with_backoff_delivers_to_late_listener(self):
        """A frame enqueued BEFORE the peer listens is delivered once the
        peer comes up — the dialer retries with backoff instead of failing
        the send (this is what tolerates cluster bring-up races)."""
        ports = [free_port(HOST), free_port(HOST)]
        addr_of = {10: (HOST, ports[0]), 11: (HOST, ports[1])}
        s1 = SocketTransport(addr_of, local=(10,))
        s2 = None
        try:
            assert s1.send(10, 11, "hello", {"dev": 10})
            time.sleep(0.4)                      # several failed dials
            s2 = SocketTransport(addr_of, local=(11,))
            m = s2.recv(11, timeout=10.0)
            assert m is not None and m.kind == "hello"
        finally:
            s1.close()
            if s2 is not None:
                s2.close()

    def test_frames_to_dead_address_expire_not_block(self):
        """Sends to a never-up peer drop after the retry window without
        wedging the sender (the protocol's timeouts do failure detection,
        the transport must not)."""
        addr_of = {0: (HOST, free_port(HOST)), 1: (HOST, free_port(HOST))}
        s = SocketTransport(addr_of, local=(0,), retry_window=0.3)
        try:
            assert s.send(0, 1, "probe", {})
            deadline = time.monotonic() + 5.0
            while (s.stats["net_dropped"] == 0
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            assert s.stats["net_dropped"] == 1
        finally:
            s.close()

    def test_fault_drop_applies_on_send_path(self):
        addr_of = cluster_addresses(2, HOST)
        a = SocketTransport(addr_of, local=(COORD, 0),
                            fault=FaultSpec(drop=1.0, protect=("ctl",)))
        try:
            assert not a.send(COORD, 0, "data", {})
            assert a.send(COORD, 0, "ctl", {})
            assert a.recv(0, timeout=1.0).kind == "ctl"
        finally:
            a.close()

    def test_hello_crosses_kill_fence_with_payload_intact(self):
        """Elastic rejoin depends on the hello of a NEW incarnation being
        deliverable while the device is still fenced — admission is the
        coordinator's call (by the payload's inc), not the transport's."""
        a, b = _pair()
        try:
            a.kill(1)
            assert not a.send(0, 1, "probe", {})
            b.send(1, COORD, "hb", {"t": 1.0})       # zombie traffic: dropped
            b.send(1, COORD, "hello", {"dev": 1, "inc": 2,
                                       "host": "127.0.0.1", "port": 9})
            m = a.recv(COORD, timeout=5.0)
            assert m is not None and m.kind == "hello"
            assert m.payload["inc"] == 2
        finally:
            a.close()
            b.close()

    def test_add_route_reaches_late_joiner(self):
        """A node absent from the startup address map becomes reachable
        once add_route installs it (how a hot-joined device's hello
        teaches everyone the way)."""
        addr_of = cluster_addresses(2, HOST)
        a = SocketTransport(addr_of, local=(COORD, 0))
        late_port = free_port(HOST)
        c = SocketTransport({**addr_of, 5: (HOST, late_port)}, local=(5,))
        try:
            assert a.send(0, 5, "probe", {})         # no route: dropped
            time.sleep(0.2)
            assert c.recv(5, timeout=0.2) is None
            a.add_route(5, (HOST, late_port))
            assert a.send(0, 5, "admit", {"dev": 5, "inc": 1})
            m = c.recv(5, timeout=5.0)
            assert m is not None and m.kind == "admit"
        finally:
            a.close()
            c.close()

    def test_sender_reconnects_to_relaunched_listener(self):
        """Per-incarnation reconnect: after the peer process 'dies' (its
        listener closes with the socket half-open), a frame to the SAME
        address must reach a relaunched listener — the stale connection is
        detected before writing, not after a silent void-send."""
        port = free_port(HOST)
        addr_of = {0: (HOST, free_port(HOST)), 1: (HOST, port)}
        a = SocketTransport(addr_of, local=(0,))
        first = SocketTransport(addr_of, local=(1,))
        second = None
        try:
            assert a.send(0, 1, "act", (1, 0, np.zeros(4, np.float32)))
            assert first.recv(1, timeout=5.0) is not None
            first.close()                    # the old incarnation dies
            time.sleep(0.3)
            second = SocketTransport(addr_of, local=(1,))  # same port
            a.send(0, 1, "fetch_res", {"req_id": 1, "layers": {}})
            m = second.recv(1, timeout=10.0)
            assert m is not None and m.kind == "fetch_res"
        finally:
            a.close()
            first.close()
            if second is not None:
                second.close()

    def test_coalesced_frames_all_arrive_in_order(self):
        """Sender-side coalescing (many queued frames -> one sendall) must
        be invisible to receivers: every frame delivered, order kept."""
        a, b = _pair()
        try:
            n = 200
            for i in range(n):
                a.send(0, 1, "act", (7, i, None))
            got = [b.recv(1, timeout=5.0) for _ in range(n)]
            assert all(m is not None for m in got)
            assert [m.payload[1] for m in got] == list(range(n))
        finally:
            a.close()
            b.close()

    def test_parse_peers_expands_coord(self):
        got = parse_peers("coord=10.0.0.1:9000, 1=10.0.0.2:9001,"
                          "2=10.0.0.3:9002")
        assert got == {-1: ("10.0.0.1", 9000), 0: ("10.0.0.1", 9000),
                       1: ("10.0.0.2", 9001), 2: ("10.0.0.3", 9002)}
        with pytest.raises(ValueError):
            parse_peers("1=nohost")


# ===================== multi-process acceptance ==========================

def _fixed_profile(num_layers=8):
    """Synthetic per-layer profile: with capacity_source='spec' this makes
    every partition/recovery decision a pure function of the config, so
    queue and TCP runs must agree exactly."""
    return WorkloadProfile(fwd_times=np.full(num_layers, 1e-3),
                           bwd_times=np.full(num_layers, 2e-3),
                           out_bytes=np.full(num_layers, 1024.0),
                           weight_bytes=np.full(num_layers, 2048.0))


def _parity_cfg(**kw):
    d = dict(
        num_workers=3, num_batches=22,
        protocol=ProtocolConfig(chain_every=8, global_every=16,
                                repartition_first_at=5,
                                repartition_every=10_000,
                                detect_timeout=0.6),
        lr=0.1,
        device_specs=[DeviceSpec("central", 1.0), DeviceSpec("peer", 1.0),
                      DeviceSpec("slow", 4.0)],
        bandwidth=uniform_bandwidth(3, 1e9),
        profile=_fixed_profile(), capacity_source="spec")
    d.update(kw)
    return LiveConfig(**d)


@pytest.mark.live
@pytest.mark.slow
def test_tcp_matches_queue_losses_without_faults():
    """No faults, quiet cadences: the TCP cluster must reproduce the queue
    transport's per-batch losses — crossing a process boundary changes
    nothing about the math."""
    spec = WorkloadSpec(kind="mlp", seed=0, num_layers=8)
    cfg = LiveConfig(num_workers=3, num_batches=10,
                     protocol=ProtocolConfig(chain_every=10_000,
                                             global_every=10_000,
                                             repartition_first_at=10_000,
                                             repartition_every=10_000,
                                             detect_timeout=2.0),
                     lr=0.1)
    chain, batches = spec.build()
    ref = run_live_training(chain, batches, cfg)
    got = run_tcp_training(spec, cfg)
    assert got.worker_exitcodes == {1: 0, 2: 0}
    np.testing.assert_allclose(got.losses, ref.losses, rtol=1e-5, atol=1e-6)


@pytest.mark.live
@pytest.mark.slow
def test_tcp_sigkill_parity_with_queue_transport():
    """Acceptance: a coordinator + 2 worker PROCESSES survive a SIGKILLed
    worker, and every runtime/protocol.py decision — initial partition,
    §III-D re-partition, §III-F recovery partition and evicted device —
    is identical to the queue-transport run on the same seed/config."""
    spec = WorkloadSpec(kind="mlp", seed=0, num_layers=8)
    chain, batches = spec.build()
    queue_res = run_live_training(chain, batches,
                                  _parity_cfg(kill=(1, 9)))
    tcp_res = run_tcp_training(spec, _parity_cfg(kill=(1, 9)))

    # the worker really died by SIGKILL, its peer exited cleanly
    assert tcp_res.worker_exitcodes[1] == -9
    assert tcp_res.worker_exitcodes[2] == 0

    # both transports completed every batch and ran exactly one recovery
    for res in (queue_res, tcp_res):
        assert not np.isnan(res.losses).any()
        assert len(res.recoveries) == 1
        assert res.recoveries[0]["failed"] == [1]

    # protocol decisions are identical: same partition-points sequence,
    # same recovery partition (restart batch may differ by in-flight
    # commits — it is timing, not a protocol decision)
    q_pts = [tuple(int(p) for p in pts) for _, pts in queue_res.partitions]
    t_pts = [tuple(int(p) for p in pts) for _, pts in tcp_res.partitions]
    assert q_pts == t_pts
    assert tuple(int(p) for p in queue_res.recoveries[0]["partition"]) \
        == tuple(int(p) for p in tcp_res.recoveries[0]["partition"])

    # and both converge: same final loss (loose: post-recovery batches may
    # replay from a slightly different restart point)
    q_final = float(np.median(queue_res.losses[-4:]))
    t_final = float(np.median(tcp_res.losses[-4:]))
    untrained = float(np.median(queue_res.losses[:3]))
    assert q_final < 0.7 * untrained and t_final < 0.7 * untrained
    assert abs(q_final - t_final) < 0.35 * max(q_final, t_final) + 0.05


class TestPerKindStats:
    def test_socket_transport_kind_breakdown(self):
        """stats["kind_bytes"]/["kind_msgs"] attribute wire volume to
        act / grad / replica / control planes at the receiver."""
        a, b = _pair()
        try:
            x = np.arange(64, dtype=np.float32)
            a.send(0, 1, "act", (0, 0, x))
            a.send(0, 1, "grad", (0, 0, x))
            a.send(0, 1, "grad", (0, 1, x))
            a.send(0, 1, "chain_put", {"layers": {0: x}})
            a.send(0, 1, "hb", {"t": 0.1})
            for _ in range(5):
                assert b.recv(1, timeout=5.0) is not None
            km, kb = b.stats["kind_msgs"], b.stats["kind_bytes"]
            assert km == {"act": 1, "grad": 2, "replica": 1,
                          "replica_ov": 0, "control": 1}
            assert kb["grad"] > kb["act"] > 0
            assert kb["replica"] > 0 and kb["control"] > 0
            assert sum(kb.values()) == b.stats["bytes"]
            assert sum(km.values()) == b.stats["delivered"]
            # consistent with the coarser data/replica counters
            assert kb["act"] + kb["grad"] == b.stats["data_bytes"]
            assert kb["replica"] == b.stats["replica_bytes"]
        finally:
            a.close()
            b.close()

    def test_queue_transport_kind_breakdown_matches(self):
        from repro.runtime.transport import Transport, kind_class

        t = Transport(codec=True)
        t.register(0)
        t.register(1)
        x = np.arange(16, dtype=np.float32)
        for kind in ("act", "grad", "global_put", "install", "hb"):
            t.send(0, 1, kind, (0, 0, x))
            assert t.recv(1, timeout=1.0) is not None
        km = t.stats["kind_msgs"]
        assert km == {"act": 1, "grad": 1, "replica": 1,
                      "replica_ov": 0, "control": 2}
        assert sum(t.stats["kind_bytes"].values()) == t.stats["bytes"]
        # kind_class is the single source of the mapping
        assert kind_class("act") == "act" and kind_class("grad") == "grad"
        assert kind_class("chain_put") == kind_class("global_put") \
            == "replica"
        assert kind_class("ov_chain_put") == kind_class("ov_global_put") \
            == "replica_ov"
        for k in ("install", "fetch_res", "hello", "hb", "commit"):
            assert kind_class(k) == "control"

    @pytest.mark.live
    def test_run_status_surfaces_wire_breakdown(self):
        """Run.status() exposes the coordinator transport's per-plane
        counters (copies, not live references)."""
        from repro.run import RunConfig, start_run

        cfg = RunConfig(
            workload=WorkloadSpec(kind="mlp", seed=0, num_layers=6),
            live=LiveConfig(
                num_workers=2, num_batches=8,
                protocol=ProtocolConfig(chain_every=4, global_every=8,
                                        repartition_first_at=10_000,
                                        repartition_every=10_000,
                                        detect_timeout=2.0),
                lr=0.1, wire_codec=True),
            transport="queue")
        run = start_run(cfg)
        run.wait()
        status = run.status()
        wire = status["chains"][0]["wire"]
        assert wire["bytes"] > 0
        assert set(wire["kind_bytes"]) \
            == {"act", "grad", "replica", "replica_ov", "control"}
        assert wire["kind_bytes"]["act"] > 0
        assert wire["kind_msgs"]["control"] > 0
        # mutating the copy must not touch the transport's counters
        wire["kind_bytes"]["act"] = -1
        assert run.status()["chains"][0]["wire"]["kind_bytes"]["act"] > 0


def test_run_tcp_training_refuses_when_this_process_holds_a_chip(
        monkeypatch):
    """A chip serves one process: the localhost harness would have its
    spawned workers fight this process for it, so it refuses up front and
    points to the queue transport."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    spec = WorkloadSpec(kind="mlp", num_layers=4)
    with pytest.raises(RuntimeError, match="queue transport"):
        run_tcp_training(spec, LiveConfig(num_workers=2, num_batches=2))
