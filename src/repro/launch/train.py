"""End-to-end training driver.

Runs the full framework stack (config -> sharded init -> pipelined
train_step -> data pipeline -> checkpoint/replication) on whatever devices
exist. On CPU use --debug-mesh to emulate a (data, stage, tensor) mesh with
host devices; reduced configs (--reduced) train a real ~small model.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --reduced \
      --debug-mesh 2,2,2 --steps 50
  PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m --reduced \
      --steps 100 --aggregate-every 4
"""
import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--debug-mesh", default="2,2,2",
                    help="data,stage,tensor host-device mesh")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.02)
    ap.add_argument("--optimizer", default="adam")
    ap.add_argument("--aggregate-every", type=int, default=0)
    ap.add_argument("--stash-depth", type=int, default=2)
    ap.add_argument("--microbatches", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    args = ap.parse_args()

    dims = [int(x) for x in args.debug_mesh.split(",")]
    n_dev = dims[0] * dims[1] * dims[2]
    os.environ.setdefault(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={n_dev}")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import TrainConfig, get_config
    from repro.data.synthetic import SyntheticLM, lm_batches
    from repro.launch.mesh import make_debug_mesh
    from repro.models import model as model_lib
    from repro.pipeline.pipeline_step import make_train_step
    from repro.pipeline.sharding import param_shardings
    from repro.checkpoint import CheckpointStore

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(pipeline_stages=dims[1], tensor_parallel=dims[2],
                          dtype="float32")
    cfg = cfg.with_overrides(aggregate_every=args.aggregate_every,
                             stash_depth=args.stash_depth)
    mesh = make_debug_mesh(*dims)
    tc = TrainConfig(learning_rate=args.lr, optimizer=args.optimizer,
                     microbatches=args.microbatches, weight_decay=0.0)

    key = jax.random.PRNGKey(0)
    with jax.set_mesh(mesh):
        params = jax.jit(
            lambda k: model_lib.init_params(k, cfg),
            out_shardings=param_shardings(mesh, cfg))(key)
        train_step, _ = make_train_step(mesh, cfg, tc)
        train_step = jax.jit(train_step)
        state = train_step.init_state(params)

        ds = SyntheticLM(vocab_size=cfg.vocab_size)
        ckpt = CheckpointStore(args.ckpt_dir) if args.ckpt_dir else None
        losses = []
        for i, (x, y) in enumerate(lm_batches(ds, args.global_batch,
                                              args.seq_len, args.steps)):
            state, metrics = train_step(
                state, {"tokens": jnp.asarray(x), "labels": jnp.asarray(y)})
            losses.append(float(metrics["loss"]))
            if i % 10 == 0 or i == args.steps - 1:
                print(f"step {i:4d} loss {losses[-1]:.4f}")
            if ckpt and (i + 1) % 50 == 0:
                ckpt.save(i + 1, jax.device_get(state["params"]))
        first = float(np.mean(losses[:5]))
        last = float(np.mean(losses[-5:]))
        print(f"loss {first:.4f} -> {last:.4f} "
              f"({'improved' if last < first else 'NOT improved'})")
        return last < first


if __name__ == "__main__":
    main()
