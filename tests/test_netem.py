"""WAN emulation layer (runtime/netem.py): shaper properties, the shared
delivery scheduler, and queue-vs-TCP parity under the same NetemSpec.

The shaper property tests drive ``LinkShaper.admit`` with an INJECTED
clock, so they are pure bookkeeping — no sleeping, no threads, no wall
time — and every bound they assert is exact, not statistical.
"""
import threading
import time

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.runtime.netem import LinkShaper, LinkSpec, NetemSpec
from repro.runtime.transport import FaultSpec, Transport

# one directed inter-node link, never colocated-exempt
SRC, DST = 0, 1


def shaper(link: LinkSpec, seed: int = 0) -> LinkShaper:
    return LinkShaper(NetemSpec(default=link, seed=seed, colocated=()))


link_specs = st.builds(
    LinkSpec,
    latency=st.floats(min_value=0.0, max_value=0.2),
    jitter=st.floats(min_value=0.0, max_value=0.02),
    rate=st.sampled_from([0.0, 1e5, 1e6, 1e7]),
    burst=st.sampled_from([1 << 10, 64 << 10]),
    loss=st.sampled_from([0.0, 0.1, 0.5]),
)


class TestShaperProperties:
    @settings(max_examples=50, deadline=None)
    @given(link=link_specs,
           sizes=st.lists(st.integers(min_value=1, max_value=1 << 20),
                          min_size=1, max_size=40),
           gaps=st.lists(st.floats(min_value=0.0, max_value=0.5),
                         min_size=40, max_size=40))
    def test_conservation_and_fifo(self, link, sizes, gaps):
        """Every message is exactly one of delivered/dropped, delays are
        never negative, and per-link arrivals are monotone (FIFO)."""
        sh = shaper(link)
        now, last_arrival, delivered, dropped = 100.0, -1.0, 0, 0
        for nbytes, gap in zip(sizes, gaps):
            now += gap
            verdict = sh.admit(SRC, DST, nbytes, now=now)
            if verdict is None:
                dropped += 1
                continue
            delivered += 1
            assert verdict >= 0.0
            arrival = now + verdict
            assert arrival >= last_arrival, "shaping must not reorder a link"
            last_arrival = arrival
        assert delivered + dropped == len(sizes)
        stats = sh.stats
        # a transparent link (no latency, jitter, rate, loss or partition)
        # takes the fast path: delivered at once, never counted as shaped
        assert stats["shaped"] == (0 if link.is_transparent() else delivered)
        assert stats["netem_dropped"] + stats["netem_blocked"] == dropped
        sh.close()

    @settings(max_examples=50, deadline=None)
    @given(rate=st.sampled_from([1e5, 1e6, 1e7]),
           burst=st.sampled_from([1 << 10, 16 << 10]),
           sizes=st.lists(st.integers(min_value=1, max_value=1 << 18),
                          min_size=2, max_size=40))
    def test_throughput_bounded_by_token_bucket(self, rate, burst, sizes):
        """A burst of back-to-back messages cannot beat the bucket: the
        last arrival is at least (total_bytes - burst) / rate after the
        first send, so measured throughput converges on ``rate``."""
        sh = shaper(LinkSpec(rate=rate, burst=burst))
        now = 50.0
        last = 0.0
        for nbytes in sizes:
            last = sh.admit(SRC, DST, nbytes, now=now)
        total = sum(sizes)
        assert last >= (total - burst) / rate - 1e-9
        # and no extra pessimism beyond one bucket of credit:
        assert last <= total / rate + 1e-9
        sh.close()

    @settings(max_examples=50, deadline=None)
    @given(latency=st.floats(min_value=0.001, max_value=0.2),
           jitter=st.floats(min_value=0.0, max_value=0.05),
           n=st.integers(min_value=1, max_value=30),
           gap=st.floats(min_value=0.2, max_value=1.0))
    def test_latency_within_jitter_bounds(self, latency, jitter, n, gap):
        """With no rate limit and sends spaced far apart (so the FIFO
        clamp never binds), every delay lands in [latency - jitter,
        latency + jitter]."""
        sh = shaper(LinkSpec(latency=latency, jitter=jitter))
        now = 10.0
        for _ in range(n):
            d = sh.admit(SRC, DST, 100, now=now)
            assert latency - jitter - 1e-9 <= d <= latency + jitter + 1e-9
            now += gap + 2 * (latency + jitter)
        sh.close()

    @settings(max_examples=30, deadline=None)
    @given(link=link_specs, seed=st.integers(min_value=0, max_value=999),
           sizes=st.lists(st.integers(min_value=1, max_value=1 << 16),
                          min_size=1, max_size=30))
    def test_seeded_determinism(self, link, seed, sizes):
        """Same spec + same per-link message sequence -> identical drop
        decisions and delays, on any transport, every run."""
        a, b = shaper(link, seed), shaper(link, seed)
        now = 7.0
        for nbytes in sizes:
            assert a.admit(SRC, DST, nbytes, now=now) == \
                b.admit(SRC, DST, nbytes, now=now)
            now += 0.01
        a.close(); b.close()

    def test_partition_window_blocks_everything(self):
        sh = LinkShaper(NetemSpec(
            default=LinkSpec(partitions=((1.0, 2.0),)), colocated=()))
        t0 = sh._t0
        assert sh.admit(SRC, DST, 10, now=t0 + 0.5) == 0.0
        assert sh.admit(SRC, DST, 10, now=t0 + 1.5) is None
        assert sh.stats["netem_blocked"] == 1
        assert sh.admit(SRC, DST, 10, now=t0 + 2.5) == 0.0
        sh.close()

    def test_colocated_and_overrides(self):
        """The link map resolves explicit override > colocated bus >
        default, per DIRECTED pair."""
        spec = NetemSpec(default=LinkSpec(latency=0.05),
                         links={(1, 2): LinkSpec(latency=0.5)},
                         colocated=((-1, 0),))
        assert spec.link(-1, 0).is_transparent()
        assert spec.link(0, -1).is_transparent()
        assert spec.link(1, 2).latency == 0.5
        assert spec.link(2, 1).latency == 0.05      # directed: no override
        assert spec.link(0, 1).latency == 0.05

    def test_doc_roundtrip(self):
        spec = NetemSpec(default=LinkSpec(latency=0.01, rate=1e6, loss=0.1),
                         links={(0, 1): LinkSpec(jitter=0.002,
                                                 partitions=((1.0, 2.0),))},
                         seed=42, colocated=((-1, 0), (1, 2)))
        again = NetemSpec.from_doc(spec.to_doc())
        assert again == spec
        import json
        json.dumps(spec.to_doc())                  # manifest/CLI-safe


class TestSchedulerAndTransport:
    def test_delay_uses_one_scheduler_thread_and_keeps_fifo(self):
        """Regression for the old one-Timer-per-message delay hack: 50
        delayed in-flight messages must cost at most ONE extra thread,
        and arrive in send order."""
        t = Transport.create("queue", netem=NetemSpec(
            default=LinkSpec(latency=0.02), colocated=()))
        t.register(0); t.register(1)
        before = threading.active_count()
        for i in range(50):
            assert t.send(0, 1, "probe", {"i": i})
        assert threading.active_count() - before <= 1
        got = [t.recv(1, timeout=2.0).payload["i"] for _ in range(50)]
        assert got == list(range(50))
        t.close()

    def test_faultspec_delay_is_degenerate_netem(self):
        """FaultSpec.delay still works, now routed through the shared
        scheduler instead of per-message threading.Timer."""
        t = Transport.create("queue", fault=FaultSpec(delay=0.03))
        t.register(0); t.register(1)
        t0 = time.monotonic()
        t.send(0, 1, "probe", {})
        msg = t.recv(1, timeout=2.0)
        assert msg is not None and time.monotonic() - t0 >= 0.025
        t.close()

    def test_netem_loss_drops_and_counts(self):
        t = Transport.create("queue", netem=NetemSpec(
            default=LinkSpec(loss=1.0), colocated=()))
        t.register(0); t.register(1)
        assert t.send(0, 1, "probe", {}) is False
        assert t.recv(1, timeout=0.1) is None
        assert t.stats["netem_dropped"] == 1
        t.close()

    def test_colocated_pair_unshaped_on_transport(self):
        """COORD<->0 share a process by default: their traffic must not
        pay WAN latency."""
        t = Transport.create("queue", netem=NetemSpec(
            default=LinkSpec(latency=0.25)))
        t.register(-1); t.register(0)
        t0 = time.monotonic()
        t.send(-1, 0, "probe", {})
        msg = t.recv(0, timeout=1.0)
        assert msg is not None and time.monotonic() - t0 < 0.2
        t.close()

    def test_close_stops_scheduler(self):
        t = Transport.create("queue", netem=NetemSpec(
            default=LinkSpec(latency=5.0), colocated=()))
        t.register(0); t.register(1)
        t.send(0, 1, "probe", {})
        t.close()
        assert t.netem.scheduler.closed
        # scheduled deliveries are shed; nothing should raise afterwards
        assert t.recv(1, timeout=0.05) is None


@pytest.mark.wan
@pytest.mark.live
def test_act_outrunning_segment_message_is_buffered_not_dropped():
    """Regression: links are delayed INDEPENDENTLY under netem, so a
    peer's first act for segment N can reach a worker before the
    coordinator's ``segment`` N message does. The worker must buffer it
    for the segment it is about to enter — dropping it as stale wedges
    the pipeline until segment_timeout on EVERY segment boundary.

    Deterministic reproducer: only the coordinator->worker-1 control link
    is slow (0.3s), while worker-0's data link is instant, so the act
    wins the race at every repartition boundary. On a regressed build
    each segment stalls, restarts at the same batch, and the no-progress
    guard raises within a few short timeouts."""
    import jax
    import numpy as np

    from repro.runtime.devices import DeviceSpec, WorkloadProfile
    from repro.runtime.live import LiveConfig, run_live_training
    from repro.runtime.protocol import ProtocolConfig
    from repro.runtime.workload import classification_batches, mlp_chain

    nl = 4
    profile = WorkloadProfile(fwd_times=np.full(nl, 1e-3),
                              bwd_times=np.full(nl, 2e-3),
                              out_bytes=np.full(nl, 512.0),
                              weight_bytes=np.full(nl, 1024.0))
    chain = mlp_chain(jax.random.PRNGKey(0), num_layers=nl)
    data = classification_batches("mlp", nl, batch=8, seed=0)
    cfg = LiveConfig(
        num_workers=2, num_batches=8,
        protocol=ProtocolConfig(chain_every=100, global_every=10_000,
                                repartition_first_at=2,
                                repartition_every=2),
        profile=profile, capacity_source="spec",
        device_specs=[DeviceSpec("a", 1.0), DeviceSpec("b", 1.0)],
        segment_timeout=3.0,
        netem=NetemSpec(default=LinkSpec(),
                        links={(-1, 1): LinkSpec(latency=0.3)},
                        colocated=()))
    res = run_live_training(chain, data, cfg)
    assert res.recoveries == []
    assert not np.isnan(res.losses).any()
    # a stalled segment waits out segment_timeout and restarts
    assert not [e for _, e in res.events if "stall" in e], res.events
    # 4 segment boundaries x 0.3s control-link delay, nothing else slow:
    # far below even ONE stall-restart cycle (segment_timeout=3.0). Timed
    # over the batches themselves: the stage compiles before the first
    # commit take what a loaded host gives them
    commits = res.commit_times
    wall = commits[max(commits)] - commits[min(commits)]
    assert wall < 3.0, f"pipeline stalled under asymmetric link delay: " \
                       f"{wall:.1f}s"


def _decision_trace(result):
    """The protocol decisions of a run, stripped of wall-clock noise:
    partition point sequences and recovery failure sets."""
    return ([tuple(int(p) for p in pts) for _, pts in result.partitions],
            [tuple(sorted(r["failed"])) for r in result.recoveries])


@pytest.mark.wan
@pytest.mark.live
def test_queue_vs_tcp_parity_same_netem_spec():
    """The SAME NetemSpec must produce the SAME protocol decision trace on
    the in-process queue transport and the real-socket TCP transport:
    partition cut sequences and failure sets match (a fixed profile +
    capacity_source="spec" pin the solver inputs, so decisions are a pure
    function of the config — the test_net.py parity recipe)."""
    import dataclasses

    import numpy as np

    from repro.run import Run, RunConfig
    from repro.runtime.devices import DeviceSpec
    from repro.runtime.devices import WorkloadProfile

    nl = 8
    profile = WorkloadProfile(fwd_times=np.full(nl, 1e-3),
                              bwd_times=np.full(nl, 2e-3),
                              out_bytes=np.full(nl, 1024.0),
                              weight_bytes=np.full(nl, 2048.0))
    spec = NetemSpec.wan(latency=0.003, jitter=0.001, rate=16e6, seed=5)
    traces = {}
    for transport in ("queue", "tcp"):
        cfg = RunConfig.from_args(type("NS", (), {})())
        live = dataclasses.replace(
            cfg.live, num_batches=12, num_workers=3, netem=spec,
            profile=profile, capacity_source="spec", kill=(1, 6),
            device_specs=[DeviceSpec("a", 1.0), DeviceSpec("b", 1.0),
                          DeviceSpec("c", 4.0)])
        cfg = dataclasses.replace(cfg, live=live, transport=transport)
        res = Run(cfg).start().wait(timeout=420)
        traces[transport] = _decision_trace(res)
    assert traces["queue"] == traces["tcp"], traces
