"""Continuous-batching serving engine: interleaved requests must produce
exactly the tokens a standalone generation produces."""
import jax
import jax.numpy as jnp
from repro.launch.mesh import axis_types_kwarg
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as M
from repro.serving import ServingEngine

KEY = jax.random.PRNGKey(0)


def _standalone_generate(cfg, params, prompt, n_new, cache_len=32):
    caches = M.init_caches(cfg, batch=1, cache_len=cache_len,
                           dtype=jnp.float32)
    toks = list(prompt)
    pos = 0
    out = []
    for t in toks[:-1]:
        _, caches = M.sequential_decode_step(
            params, cfg, jnp.asarray([[t]], jnp.int32), caches,
            jnp.int32(pos))
        pos += 1
    cur = toks[-1]
    for _ in range(n_new):
        lg, caches = M.sequential_decode_step(
            params, cfg, jnp.asarray([[cur]], jnp.int32), caches,
            jnp.int32(pos))
        pos += 1
        cur = int(jnp.argmax(lg[0, 0]))
        out.append(cur)
    return out


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen2-1.5b").reduced(num_layers=2, vocab_size=128)
    params = M.init_params(KEY, cfg)
    return cfg, params


def test_single_request_matches_standalone(setup):
    cfg, params = setup
    eng = ServingEngine(cfg, params, max_slots=2, cache_len=32)
    uid = eng.submit([5, 9, 2], max_new_tokens=6)
    out = eng.run_until_drained()
    ref = _standalone_generate(cfg, params, [5, 9, 2], 6)
    assert out[uid] == ref


def test_interleaved_requests_isolated(setup):
    """Requests of different lengths sharing the batch must not interfere."""
    cfg, params = setup
    prompts = [[5, 9, 2], [7], [11, 3], [1, 2, 3, 4]]
    refs = [_standalone_generate(cfg, params, p, 5) for p in prompts]
    eng = ServingEngine(cfg, params, max_slots=2, cache_len=32)  # 2 slots!
    uids = [eng.submit(p, max_new_tokens=5) for p in prompts]
    out = eng.run_until_drained()
    for uid, ref in zip(uids, refs):
        assert out[uid] == ref


def test_slot_reuse_resets_cache(setup):
    """A slot reused by a second request must not see the first's KV."""
    cfg, params = setup
    eng = ServingEngine(cfg, params, max_slots=1, cache_len=32)
    u1 = eng.submit([5, 9, 2], max_new_tokens=4)
    u2 = eng.submit([7, 7], max_new_tokens=4)
    out = eng.run_until_drained()
    assert out[u1] == _standalone_generate(cfg, params, [5, 9, 2], 4)
    assert out[u2] == _standalone_generate(cfg, params, [7, 7], 4)


def test_eos_stops_generation(setup):
    cfg, params = setup
    ref = _standalone_generate(cfg, params, [5, 9, 2], 8)
    eos = ref[2]
    eng = ServingEngine(cfg, params, max_slots=1, cache_len=32, eos_id=eos)
    uid = eng.submit([5, 9, 2], max_new_tokens=8)
    out = eng.run_until_drained()
    assert out[uid] == ref[:3]            # stops right at eos


def test_per_slot_positions_in_pipeline_decode():
    """The pipeline serve_step accepts a per-sequence position vector."""
    if jax.device_count() < 8:
        pytest.skip("needs 8 host devices")
    from repro.pipeline.pipeline_step import make_serve_step
    mesh = jax.make_mesh((2, 2, 2), ("data", "stage", "tensor"),
                         **axis_types_kwarg(3))
    cfg = get_config("qwen2-1.5b").reduced(pipeline_stages=2,
                                           tensor_parallel=2, num_layers=4)
    params = M.init_params(KEY, cfg)
    B, W = 4, 16
    toks = jax.random.randint(KEY, (B, 5), 0, cfg.vocab_size)
    # all slots at the same position vector == scalar-pos behaviour
    caches_a = M.init_caches(cfg, batch=B, cache_len=W, dtype=jnp.float32)
    caches_b = M.init_caches(cfg, batch=B, cache_len=W, dtype=jnp.float32)
    with jax.set_mesh(mesh):
        serve = jax.jit(make_serve_step(mesh, cfg, num_microbatches=2))
        for t in range(5):
            la, caches_a = serve(params, toks[:, t:t+1], caches_a,
                                 jnp.int32(t))
            lb, caches_b = serve(params, toks[:, t:t+1], caches_b,
                                 jnp.full((), t, jnp.int32))
            np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                       atol=1e-5)
