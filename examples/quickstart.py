"""Quickstart: train a tiny pipelined LM on synthetic data, on CPU.

Shows the whole public API surface in ~40 lines: config -> mesh -> sharded
init -> pipelined train_step (1F1B + weight stash + aggregation) -> loop.

    PYTHONPATH=src python examples/quickstart.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import TrainConfig, get_config
from repro.data.synthetic import SyntheticLM, lm_batches
from repro.launch.mesh import make_debug_mesh
from repro.models import model as model_lib
from repro.pipeline.pipeline_step import make_train_step
from repro.pipeline.sharding import param_shardings


def main():
    # a 4-layer qwen2-family model, 2 pipeline stages x 2-way tensor parallel
    cfg = get_config("qwen2-1.5b").reduced(
        pipeline_stages=2, tensor_parallel=2, num_layers=4, vocab_size=256,
        aggregate_every=4, stash_depth=2)      # the paper's features, on
    mesh = make_debug_mesh(data=2, stage=2, tensor=2)
    tc = TrainConfig(learning_rate=0.02, optimizer="adam", microbatches=2,
                     weight_decay=0.0)

    with jax.set_mesh(mesh):
        params = jax.jit(lambda k: model_lib.init_params(k, cfg),
                         out_shardings=param_shardings(mesh, cfg))(
                             jax.random.PRNGKey(0))
        train_step, _ = make_train_step(mesh, cfg, tc)
        state = train_step.init_state(params)
        jstep = jax.jit(train_step)

        ds = SyntheticLM(vocab_size=cfg.vocab_size)
        losses = []
        for i, (x, y) in enumerate(lm_batches(ds, batch=8, seq_len=32,
                                              num_batches=60)):
            state, metrics = jstep(state, {"tokens": jnp.asarray(x),
                                           "labels": jnp.asarray(y)})
            losses.append(float(metrics["loss"]))
            if i % 10 == 0:
                print(f"step {i:3d}  loss {losses[-1]:.4f}")
    print(f"\nloss: {np.mean(losses[:5]):.3f} -> {np.mean(losses[-5:]):.3f}")
    assert np.mean(losses[-5:]) < np.mean(losses[:5])
    print("quickstart OK")


if __name__ == "__main__":
    main()
