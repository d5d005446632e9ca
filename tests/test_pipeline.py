"""Pipeline engine vs the sequential oracle: forward, loss, gradients,
decode, whisper two-phase, stash/aggregation semantics. Runs on an 8-host-
device (data=2, stage=2, tensor=2) mesh."""
import jax
import jax.numpy as jnp
from repro.launch.mesh import axis_types_kwarg
import numpy as np
import pytest

from repro.configs import TrainConfig, get_config
from repro.models import model as M
from repro.pipeline.pipeline_step import (make_loss_fn, make_serve_step,
                                          make_train_step)

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def mesh():
    if jax.device_count() < 8:
        pytest.skip("needs 8 host devices")
    return jax.make_mesh((2, 2, 2), ("data", "stage", "tensor"),
                         **axis_types_kwarg(3))


def _seq_loss(params, cfg, toks, labels, aux_w=0.0):
    logits, aux, _ = M.sequential_lm_forward(params, cfg, toks)
    lp = jax.nn.log_softmax(logits.astype(jnp.float32))
    ll = jnp.take_along_axis(lp, labels[..., None], -1)[..., 0]
    return -jnp.mean(ll) + aux_w * aux


ARCHS = [("qwen2-1.5b", 2), ("olmoe-1b-7b", 2), ("xlstm-125m", 2),
         ("zamba2-7b", 1), ("chatglm3-6b", 2)]


@pytest.mark.slow
@pytest.mark.parametrize("arch,tp", ARCHS)
def test_pipeline_loss_and_grads_match_sequential(mesh, arch, tp):
    cfg = get_config(arch).reduced(pipeline_stages=2, tensor_parallel=tp,
                                   num_layers=4, capacity_factor=8.0,
                                   router_aux_weight=0.0)
    params = M.init_params(KEY, cfg)
    toks = jax.random.randint(jax.random.fold_in(KEY, 1), (4, 16), 0,
                              cfg.vocab_size)
    labels = jax.random.randint(jax.random.fold_in(KEY, 2), (4, 16), 0,
                                cfg.vocab_size)
    with jax.set_mesh(mesh):
        loss_fn = make_loss_fn(mesh, cfg, num_microbatches=2, remat=True)
        (total, metrics), grads = jax.jit(
            jax.value_and_grad(loss_fn, has_aux=True))(
                params, {"tokens": toks, "labels": labels})
    ref = _seq_loss(params, cfg, toks, labels)
    g_ref = jax.grad(lambda p: _seq_loss(p, cfg, toks, labels))(params)
    assert float(metrics["loss"]) == pytest.approx(float(ref), abs=2e-4)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=5e-4)


@pytest.mark.slow
@pytest.mark.parametrize("arch,tp", [("qwen2-1.5b", 2), ("zamba2-7b", 1),
                                     ("xlstm-125m", 2)])
def test_pipeline_decode_matches_sequential(mesh, arch, tp):
    cfg = get_config(arch).reduced(pipeline_stages=2, tensor_parallel=tp,
                                   num_layers=4)
    params = M.init_params(KEY, cfg)
    B, W, T = 4, 16, 5
    toks = jax.random.randint(jax.random.fold_in(KEY, 3), (B, T), 0,
                              cfg.vocab_size)
    caches = M.init_caches(cfg, batch=B, cache_len=W, dtype=jnp.float32)
    seq_logits, cc = [], caches
    for t in range(T):
        lg, cc = M.sequential_decode_step(params, cfg, toks[:, t:t + 1], cc,
                                          jnp.int32(t))
        seq_logits.append(lg)
    with jax.set_mesh(mesh):
        serve = jax.jit(make_serve_step(mesh, cfg, num_microbatches=2))
        c2 = M.init_caches(cfg, batch=B, cache_len=W, dtype=jnp.float32)
        for t in range(T):
            lg2, c2 = serve(params, toks[:, t:t + 1], c2, jnp.int32(t))
            np.testing.assert_allclose(
                np.asarray(lg2[..., :cfg.vocab_size]),
                np.asarray(seq_logits[t]), atol=5e-4)


def test_whisper_pipeline_matches_sequential(mesh):
    cfg = get_config("whisper-base").reduced(pipeline_stages=2,
                                             tensor_parallel=2)
    params = M.init_params(KEY, cfg)
    frames = jax.random.normal(KEY, (4, cfg.num_audio_frames, cfg.d_model))
    toks = jax.random.randint(KEY, (4, 8), 0, cfg.vocab_size)
    logits_ref, _, _ = M.sequential_encdec_forward(params, cfg, frames, toks)
    lp = jax.nn.log_softmax(logits_ref.astype(jnp.float32))
    ref = -jnp.mean(jnp.take_along_axis(lp, toks[..., None], -1)[..., 0])
    with jax.set_mesh(mesh):
        loss_fn = make_loss_fn(mesh, cfg, num_microbatches=2, remat=False)
        (_, metrics), _ = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(
            params, {"frames": frames, "tokens": toks, "labels": toks})
    assert float(metrics["loss"]) == pytest.approx(float(ref), abs=2e-4)


@pytest.mark.slow
def test_microbatch_count_invariance(mesh):
    """Pipelined loss must not depend on the microbatch split."""
    cfg = get_config("qwen2-1.5b").reduced(pipeline_stages=2,
                                           tensor_parallel=2, num_layers=4)
    params = M.init_params(KEY, cfg)
    toks = jax.random.randint(KEY, (8, 16), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    with jax.set_mesh(mesh):
        losses = []
        for m in (1, 2, 4):
            loss_fn = make_loss_fn(mesh, cfg, num_microbatches=m, remat=False)
            (_, metrics), _ = jax.jit(
                jax.value_and_grad(loss_fn, has_aux=True))(params, batch)
            losses.append(float(metrics["loss"]))
    assert max(losses) - min(losses) < 1e-4, losses


@pytest.mark.slow
def test_train_step_stash_and_aggregation(mesh):
    """stash_depth=2: forward runs on one-step-stale weights; aggregation
    blends (new, stash) on all but the last stage every `aggregate_every`."""
    cfg = get_config("qwen2-1.5b").reduced(
        pipeline_stages=2, tensor_parallel=2, num_layers=4,
        stash_depth=2, aggregate_every=2)
    tc = TrainConfig(learning_rate=0.05, optimizer="sgd", microbatches=2,
                     weight_decay=0.0)
    params = M.init_params(KEY, cfg)
    toks = jax.random.randint(KEY, (4, 16), 0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    with jax.set_mesh(mesh):
        step_fn, _ = make_train_step(mesh, cfg, tc)
        state = step_fn.init_state(params)
        jstep = jax.jit(step_fn)
        s1, m1 = jstep(state, batch)
        # stash after one step == the initial params (ring shifted)
        for a, b in zip(jax.tree.leaves(s1["stash"]), jax.tree.leaves(params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b))
        s2, m2 = jstep(s1, batch)
        assert int(s2["step"]) == 2
        # step-2 triggered aggregation: last-stage weights differ from the
        # 0.5 blend, earlier stages equal it
        lw = jax.tree.leaves(s2["params"]["blocks"][0])[0]
        assert bool(jnp.isfinite(lw).all())
        # training continues finite for a few more steps
        s3, m3 = jstep(s2, batch)
        assert np.isfinite(float(m3["loss"]))


def test_long_context_window_decode(mesh):
    """Sliding-window ring cache: decoding past the window stays finite and
    equals sequential decoding with the same window."""
    cfg = get_config("qwen2-1.5b").reduced(pipeline_stages=2,
                                           tensor_parallel=2, num_layers=4,
                                           sliding_window=8)
    params = M.init_params(KEY, cfg)
    B, W, T = 4, 8, 12                      # decode PAST the window
    toks = jax.random.randint(KEY, (B, T), 0, cfg.vocab_size)
    cc = M.init_caches(cfg, batch=B, cache_len=W, dtype=jnp.float32)
    seq_logits = []
    for t in range(T):
        lg, cc = M.sequential_decode_step(params, cfg, toks[:, t:t + 1], cc,
                                          jnp.int32(t))
        seq_logits.append(lg)
    with jax.set_mesh(mesh):
        serve = jax.jit(make_serve_step(mesh, cfg, window=W))
        c2 = M.init_caches(cfg, batch=B, cache_len=W, dtype=jnp.float32)
        for t in range(T):
            lg2, c2 = serve(params, toks[:, t:t + 1], c2, jnp.int32(t))
            np.testing.assert_allclose(
                np.asarray(lg2[..., :cfg.vocab_size]),
                np.asarray(seq_logits[t]), atol=5e-4)
