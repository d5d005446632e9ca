"""Pipelined batched serving: decode a batch of requests through the
stage-partitioned model with per-stage KV caches (the decode path every
decode_32k / long_500k dry-run shape lowers).

    PYTHONPATH=src python examples/serve_pipeline.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch.mesh import make_debug_mesh
from repro.models import model as model_lib
from repro.pipeline.pipeline_step import make_serve_step
from repro.pipeline.sharding import param_shardings


def main():
    # hybrid arch: exercises attention KV caches AND mamba SSM state
    cfg = get_config("zamba2-7b").reduced(pipeline_stages=2,
                                          tensor_parallel=1, num_layers=4)
    mesh = make_debug_mesh(data=2, stage=2, tensor=2)
    batch, steps, cache_len = 8, 24, 64

    with jax.set_mesh(mesh):
        params = jax.jit(lambda k: model_lib.init_params(k, cfg),
                         out_shardings=param_shardings(mesh, cfg))(
                             jax.random.PRNGKey(0))
        caches = model_lib.init_caches(cfg, batch=batch, cache_len=cache_len)
        serve = jax.jit(make_serve_step(mesh, cfg))

        tok = jnp.zeros((batch, 1), jnp.int32)
        streams = [[] for _ in range(batch)]
        t0 = time.time()
        for pos in range(steps):
            logits, caches = serve(params, tok, caches, jnp.int32(pos))
            tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
            for b, t in enumerate(jax.device_get(tok)[:, 0]):
                streams[b].append(int(t))
        dt = time.time() - t0
    print(f"decoded {steps} tokens x {batch} streams in {dt:.1f}s "
          f"({steps*batch/dt:.0f} tok/s, CPU illustrative)")
    for b in range(3):
        print(f"stream[{b}]: {streams[b]}")


if __name__ == "__main__":
    main()
