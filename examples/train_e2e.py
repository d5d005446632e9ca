"""End-to-end driver: train a ~100M-parameter qwen2-family model for a few
hundred steps through the full framework stack (pipelined 1F1B, tensor
parallel, weight stash + aggregation, checkpointing).

NOTE: ~100M params on CPU is slow (~minutes/step at the default shapes);
for CI-speed validation use --tiny (defaults shown train the real thing).

    PYTHONPATH=src python examples/train_e2e.py --steps 200
    PYTHONPATH=src python examples/train_e2e.py --tiny --steps 30
"""
import argparse
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import TrainConfig, get_config
from repro.data.synthetic import SyntheticLM, lm_batches
from repro.launch.mesh import make_debug_mesh
from repro.models import model as model_lib
from repro.models.modules import count_params
from repro.pipeline.pipeline_step import make_train_step
from repro.pipeline.sharding import param_shardings
from repro.checkpoint import CheckpointStore


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt", default="/tmp/repro_e2e_ckpt")
    args = ap.parse_args()

    base = get_config("qwen2-1.5b")
    if args.tiny:
        cfg = base.reduced(pipeline_stages=2, tensor_parallel=2,
                           num_layers=4, vocab_size=512)
        args.seq = min(args.seq, 64)
    else:
        # ~100M-param family member: 12L, d=512, ff=2048, 32k vocab
        cfg = base.with_overrides(
            num_layers=12, d_model=512, num_heads=8, num_kv_heads=2,
            head_dim=64, d_ff=2048, vocab_size=32_000,
            pipeline_stages=2, tensor_parallel=2, layers_per_stage=0,
            slot_layout=(), dtype="float32",
            aggregate_every=8, stash_depth=2)
    mesh = make_debug_mesh(data=2, stage=2, tensor=2)
    tc = TrainConfig(learning_rate=3e-4, optimizer="adam",
                     microbatches=2, weight_decay=0.0)

    with jax.set_mesh(mesh):
        params = jax.jit(lambda k: model_lib.init_params(k, cfg),
                         out_shardings=param_shardings(mesh, cfg))(
                             jax.random.PRNGKey(0))
        n = count_params(params)
        print(f"model: {cfg.name} variant, {n/1e6:.1f}M params, "
              f"{cfg.pipeline_stages} stages x {cfg.tensor_parallel} tp")
        train_step, _ = make_train_step(mesh, cfg, tc)
        state = train_step.init_state(params)
        jstep = jax.jit(train_step)
        ds = SyntheticLM(vocab_size=cfg.vocab_size, branching=16)
        ckpt = CheckpointStore(args.ckpt)
        losses = []
        for i, (x, y) in enumerate(lm_batches(ds, args.batch, args.seq,
                                              args.steps)):
            state, m = jstep(state, {"tokens": jnp.asarray(x),
                                     "labels": jnp.asarray(y)})
            losses.append(float(m["loss"]))
            if i % 10 == 0:
                print(f"step {i:4d} loss {losses[-1]:.4f}")
            if (i + 1) % 100 == 0:
                ckpt.save(i + 1, jax.device_get(state["params"]))
        print(f"\nloss {np.mean(losses[:5]):.4f} -> {np.mean(losses[-5:]):.4f}")
        print("checkpoints:", ckpt.steps())


if __name__ == "__main__":
    main()
