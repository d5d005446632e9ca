"""Batched pipelined serving driver: decodes tokens through the stage-
partitioned model with per-stage KV/SSM caches.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b \
      --debug-mesh 2,2,2 --batch 8 --tokens 32
"""
import argparse
import os
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--debug-mesh", default="2,2,2")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    dims = [int(x) for x in args.debug_mesh.split(",")]
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count="
                          f"{dims[0]*dims[1]*dims[2]}")

    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.launch.mesh import make_debug_mesh
    from repro.models import model as model_lib
    from repro.pipeline.pipeline_step import make_serve_step
    from repro.pipeline.sharding import param_shardings

    cfg = get_config(args.arch).reduced(pipeline_stages=dims[1],
                                        tensor_parallel=dims[2])
    mesh = make_debug_mesh(*dims)
    key = jax.random.PRNGKey(0)
    with jax.set_mesh(mesh):
        params = jax.jit(lambda k: model_lib.init_params(k, cfg),
                         out_shardings=param_shardings(mesh, cfg))(key)
        layout = (cfg.decoder_slot_layout if cfg.family == "audio"
                  else cfg.slot_layout)
        caches = model_lib.init_caches(cfg, batch=args.batch,
                                       cache_len=args.cache_len,
                                       layout=layout)
        serve = jax.jit(make_serve_step(mesh, cfg))

        tok = jnp.zeros((args.batch, 1), jnp.int32)
        outs = []
        t0 = time.time()
        for pos in range(args.tokens):
            logits, caches = serve(params, tok, caches, jnp.int32(pos))
            if args.temperature > 0:
                key, k = jax.random.split(key)
                tok = jax.random.categorical(
                    k, logits[:, -1] / args.temperature)[:, None]
            else:
                tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
            tok = tok.astype(jnp.int32)
            outs.append(jax.device_get(tok)[:, 0])
        dt = time.time() - t0
        print(f"decoded {args.tokens} tokens x batch {args.batch} "
              f"in {dt:.2f}s ({args.tokens*args.batch/dt:.1f} tok/s on CPU "
              f"interpret — illustrative only)")
        print("sample stream[0]:", [int(o[0]) for o in outs])


if __name__ == "__main__":
    main()
