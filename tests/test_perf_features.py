"""Beyond-paper perf features: chunked-sequence prefill, flash-attention
routing, model-axis remapping (extra_data)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.mesh import axis_types_kwarg
from repro.models import model as M
from repro.pipeline.pipeline_step import make_prefill_step, make_train_step
from repro.configs.base import TrainConfig

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def mesh():
    if jax.device_count() < 8:
        pytest.skip("needs 8 host devices")
    return jax.make_mesh((2, 2, 2), ("data", "stage", "tensor"),
                         **axis_types_kwarg(3))


@pytest.fixture(scope="module")
def mesh_extra():
    if jax.device_count() < 8:
        pytest.skip("needs 8 host devices")
    return jax.make_mesh((2, 2, 2, 1), ("data", "extra", "stage", "tensor"),
                         **axis_types_kwarg(4))


@pytest.mark.parametrize("arch,tp,flash",
                         [("llama3-8b", 2, 0), ("llama3-8b", 2, 1),
                          ("zamba2-7b", 1, 0), ("olmoe-1b-7b", 2, 0),
                          ("qwen2-1.5b", 2, 1), ("xlstm-125m", 1, 0),
                          ("xlstm-125m", 2, 0)])
def test_chunked_prefill_matches_full_forward(mesh, arch, tp, flash):
    cfg = get_config(arch).reduced(pipeline_stages=2, tensor_parallel=tp,
                                   num_layers=4, capacity_factor=8.0,
                                   use_flash_attention=flash)
    params = M.init_params(KEY, cfg)
    B, S = 4, 64
    toks = jax.random.randint(jax.random.fold_in(KEY, 1), (B, S), 0,
                              cfg.vocab_size)
    full, _, _ = M.sequential_lm_forward(params, cfg, toks)
    with jax.set_mesh(mesh):
        caches = M.init_caches(cfg, batch=B, cache_len=S, dtype=jnp.float32)
        pf = jax.jit(make_prefill_step(mesh, cfg, seq_chunks=4))
        logits, new_caches = pf(params, {"tokens": toks}, caches)
    np.testing.assert_allclose(
        np.asarray(logits[:, 0, :cfg.vocab_size]),
        np.asarray(full[:, -1, :]), atol=5e-4)


def test_chunked_prefill_chunk_count_invariance(mesh):
    cfg = get_config("llama3-8b").reduced(pipeline_stages=2,
                                          tensor_parallel=2, num_layers=4)
    params = M.init_params(KEY, cfg)
    B, S = 4, 64
    toks = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    outs = []
    with jax.set_mesh(mesh):
        for chunks in (2, 4, 8):
            caches = M.init_caches(cfg, batch=B, cache_len=S,
                                   dtype=jnp.float32)
            pf = jax.jit(make_prefill_step(mesh, cfg, seq_chunks=chunks))
            logits, _ = pf(params, {"tokens": toks}, caches)
            outs.append(np.asarray(logits))
    for o in outs[1:]:
        np.testing.assert_allclose(outs[0], o, atol=5e-4)


def test_chunked_prefill_caches_usable_for_decode(mesh):
    """Production flow: chunked prefill fills caches, decode continues."""
    cfg = get_config("qwen2-1.5b").reduced(pipeline_stages=2,
                                           tensor_parallel=2, num_layers=4)
    params = M.init_params(KEY, cfg)
    B, S = 4, 32
    total = S + 4
    toks = jax.random.randint(KEY, (B, total), 0, cfg.vocab_size)
    # oracle: full forward over everything
    full, _, _ = M.sequential_lm_forward(params, cfg, toks)
    from repro.pipeline.pipeline_step import make_serve_step
    with jax.set_mesh(mesh):
        caches = M.init_caches(cfg, batch=B, cache_len=total,
                               dtype=jnp.float32)
        pf = jax.jit(make_prefill_step(mesh, cfg, seq_chunks=4))
        logits, caches = pf(params, {"tokens": toks[:, :S]}, caches)
        serve = jax.jit(make_serve_step(mesh, cfg))
        for t in range(S, total):
            logits, caches = serve(params, toks[:, t:t + 1], caches,
                                   jnp.int32(t))
            np.testing.assert_allclose(
                np.asarray(logits[:, 0, :cfg.vocab_size]),
                np.asarray(full[:, t, :]), atol=5e-4)


def test_flash_routing_matches_jnp_path():
    cfg = get_config("llama3-8b").reduced(num_layers=2)
    cfg_f = cfg.with_overrides(use_flash_attention=1)
    p = M.init_params(KEY, cfg)
    toks = jax.random.randint(KEY, (2, 48), 0, cfg.vocab_size)
    a, _, _ = M.sequential_lm_forward(p, cfg, toks)
    b, _, _ = M.sequential_lm_forward(p, cfg_f, toks)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_extra_data_axis_training(mesh_extra):
    """Model-axis remap: stage*tensor*extra tiles the model axis; training
    still matches the sequential oracle."""
    cfg = get_config("qwen2-1.5b").reduced(pipeline_stages=2,
                                           tensor_parallel=1, num_layers=4,
                                           extra_data=2)
    from repro.pipeline.pipeline_step import make_loss_fn
    params = M.init_params(KEY, cfg)
    toks = jax.random.randint(KEY, (8, 16), 0, cfg.vocab_size)
    labels = jax.random.randint(jax.random.fold_in(KEY, 1), (8, 16), 0,
                                cfg.vocab_size)
    with jax.set_mesh(mesh_extra):
        loss_fn = make_loss_fn(mesh_extra, cfg, num_microbatches=2,
                               remat=False)
        (total, metrics), grads = jax.jit(
            jax.value_and_grad(loss_fn, has_aux=True))(
                params, {"tokens": toks, "labels": labels})
    logits, _, _ = M.sequential_lm_forward(params, cfg, toks)
    lp = jax.nn.log_softmax(logits.astype(jnp.float32))
    ref = -jnp.mean(jnp.take_along_axis(lp, labels[..., None], -1)[..., 0])
    assert float(metrics["loss"]) == pytest.approx(float(ref), abs=2e-4)


def test_flash_kernel_q_offset_property():
    """Chunk-by-chunk flash == one-shot flash for arbitrary chunkings."""
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.flash_attention.kernel import flash_attention_kernel
    B, H, S, dh = 1, 2, 256, 64
    q = jax.random.normal(KEY, (B, H, S, dh))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, H, S, dh))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, H, S, dh))
    ref = flash_attention(q, k, v, True, 0, 128, 128, True)
    for L in (64, 128):
        outs = []
        for s0 in range(0, S, L):
            outs.append(flash_attention_kernel(
                q[:, :, s0:s0 + L], k, v, jnp.array([s0]), causal=True))
        np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, axis=2)),
                                   np.asarray(ref), atol=1e-5)


@pytest.mark.slow
def test_bf16_grads_training_still_learns(mesh):
    from repro.data.synthetic import SyntheticLM, lm_batches
    cfg = get_config("qwen2-1.5b").reduced(pipeline_stages=2,
                                           tensor_parallel=2, num_layers=4,
                                           vocab_size=256)
    tc = TrainConfig(learning_rate=0.02, optimizer="adam", microbatches=2,
                     weight_decay=0.0, bf16_grads=True)
    from repro.pipeline.sharding import param_shardings
    with jax.set_mesh(mesh):
        params = jax.jit(lambda k: M.init_params(k, cfg),
                         out_shardings=param_shardings(mesh, cfg))(KEY)
        step_fn, _ = make_train_step(mesh, cfg, tc)
        state = step_fn.init_state(params)
        jstep = jax.jit(step_fn)
        ds = SyntheticLM(vocab_size=cfg.vocab_size)
        losses = []
        for x, y in lm_batches(ds, 8, 32, 60):
            state, m = jstep(state, {"tokens": jnp.asarray(x),
                                     "labels": jnp.asarray(y)})
            losses.append(float(m["loss"]))
    assert all(np.isfinite(losses))
    assert np.mean(losses[-10:]) < np.mean(losses[:10])


class TestCostModelProperties:
    """Monotonicity / sanity properties of the analytic roofline model."""

    def _combo(self, **over):
        from repro.configs import get_config, get_shape
        from repro.launch.cost_model import Combo
        cfg = get_config("llama3-8b").with_overrides(**over) if over else \
            get_config("llama3-8b")
        return Combo(cfg, get_shape("prefill_32k"))

    def test_more_chunks_lower_compute(self):
        from repro.launch.cost_model import roofline
        bounds = []
        for c in (0, 8, 16, 32):
            r = roofline(self._combo(prefill_seq_chunks=c))
            bounds.append(r["terms"]["compute_s"])
        assert bounds[1] < bounds[0]
        assert bounds[2] < bounds[1] and bounds[3] < bounds[2]

    def test_flash_removes_score_traffic(self):
        from repro.launch.cost_model import hbm_bytes_per_device
        base = hbm_bytes_per_device(self._combo())
        flash = hbm_bytes_per_device(self._combo(use_flash_attention=1))
        assert base["scores"] > 0 and flash["scores"] == 0
        assert flash["total"] < base["total"]

    def test_decode_is_weights_bound(self):
        from repro.configs import get_config, get_shape
        from repro.launch.cost_model import Combo, hbm_bytes_per_device
        co = Combo(get_config("llama3-8b"), get_shape("decode_32k"))
        hb = hbm_bytes_per_device(co)
        assert hb["weights"] > hb["activations"]
