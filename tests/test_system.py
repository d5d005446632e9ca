"""End-to-end behaviour tests: the full framework stack actually trains, and
the full FTPipeHD protocol survives a mid-training failure."""
import jax
import jax.numpy as jnp
from repro.launch.mesh import axis_types_kwarg
import numpy as np
import pytest

from repro.configs import TrainConfig, get_config
from repro.data.synthetic import SyntheticLM, lm_batches
from repro.models import model as M
from repro.pipeline.pipeline_step import make_train_step
from repro.pipeline.sharding import param_shardings


@pytest.fixture(scope="module")
def mesh():
    if jax.device_count() < 8:
        pytest.skip("needs 8 host devices")
    return jax.make_mesh((2, 2, 2), ("data", "stage", "tensor"),
                         **axis_types_kwarg(3))


def _train(mesh, cfg, steps=40, lr=0.02, opt="adam"):
    tc = TrainConfig(learning_rate=lr, optimizer=opt, microbatches=2,
                     weight_decay=0.0)
    key = jax.random.PRNGKey(0)
    with jax.set_mesh(mesh):
        params = jax.jit(lambda k: M.init_params(k, cfg),
                         out_shardings=param_shardings(mesh, cfg))(key)
        step_fn, _ = make_train_step(mesh, cfg, tc)
        state = step_fn.init_state(params)
        jstep = jax.jit(step_fn)
        ds = SyntheticLM(vocab_size=cfg.vocab_size)
        losses = []
        for x, y in lm_batches(ds, 8, 32, steps):
            state, m = jstep(state, {"tokens": jnp.asarray(x),
                                     "labels": jnp.asarray(y)})
            losses.append(float(m["loss"]))
    return losses


@pytest.mark.slow
def test_pipelined_training_learns(mesh):
    cfg = get_config("qwen2-1.5b").reduced(pipeline_stages=2,
                                           tensor_parallel=2, num_layers=4,
                                           vocab_size=256)
    losses = _train(mesh, cfg, steps=40)
    assert all(np.isfinite(losses))
    assert np.mean(losses[-8:]) < np.mean(losses[:8]) - 0.1


@pytest.mark.slow
def test_training_with_stash_and_aggregation_learns(mesh):
    cfg = get_config("qwen2-1.5b").reduced(
        pipeline_stages=2, tensor_parallel=2, num_layers=4, vocab_size=256,
        stash_depth=2, aggregate_every=4)
    losses = _train(mesh, cfg, steps=40)
    assert all(np.isfinite(losses))
    assert np.mean(losses[-8:]) < np.mean(losses[:8]) - 0.05


def test_full_ftpipehd_protocol_with_failure():
    """Simulator end-to-end: profiling -> uniform init -> capacity estimation
    -> dynamic repartition -> replication -> kill worker -> detect ->
    redistribute -> resume. All 300 batches complete."""
    from repro.runtime.devices import (DeviceSpec, WorkloadProfile,
                                       uniform_bandwidth)
    from repro.runtime.simulator import PipelineSimulator, SimConfig
    devs = DeviceSpec.paper_trio()
    sim = PipelineSimulator(SimConfig(devs, WorkloadProfile.mobilenetv2(64),
                                      uniform_bandwidth(3),
                                      policy="ftpipehd", num_batches=300))
    r = sim.run(fail=(1, 205))
    assert np.all(np.isfinite(r.batch_done))
    assert len(r.partitions) >= 2                   # repartitioned at 10
    assert any("failure" in e for _, e in r.events)
    # post-recovery partition covers all layers with 2 workers
    pts = r.partitions[-1][1]
    assert len(pts) == 2 and pts[-1] == sim.cfg.profile.num_layers - 1


@pytest.mark.slow
def test_checkpoint_recovery_roundtrip(mesh, tmp_path):
    """Train, checkpoint, 'lose' state, restore, verify bit-equality."""
    from repro.checkpoint import CheckpointStore
    cfg = get_config("qwen2-1.5b").reduced(pipeline_stages=2,
                                           tensor_parallel=2, num_layers=4,
                                           vocab_size=256)
    tc = TrainConfig(learning_rate=0.02, optimizer="adam", microbatches=2)
    key = jax.random.PRNGKey(0)
    with jax.set_mesh(mesh):
        params = jax.jit(lambda k: M.init_params(k, cfg),
                         out_shardings=param_shardings(mesh, cfg))(key)
        step_fn, _ = make_train_step(mesh, cfg, tc)
        state = step_fn.init_state(params)
        jstep = jax.jit(step_fn)
        ds = SyntheticLM(vocab_size=cfg.vocab_size)
        batches = [(jnp.asarray(x), jnp.asarray(y))
                   for x, y in lm_batches(ds, 8, 32, 6)]
        for x, y in batches[:3]:
            state, _ = jstep(state, {"tokens": x, "labels": y})
        cs = CheckpointStore(str(tmp_path))
        cs.save(3, jax.device_get(state["params"]))
        restored, step = cs.restore_latest(
            jax.tree.map(np.zeros_like, jax.device_get(state["params"])))
        assert step == 3
        for a, b in zip(jax.tree.leaves(restored),
                        jax.tree.leaves(jax.device_get(state["params"]))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
