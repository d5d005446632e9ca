import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks the device count on first init.

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, SHAPES, TrainConfig, get_config, get_shape
from repro.launch import analysis
from repro.launch import cost_model
from repro.launch.mesh import make_production_mesh, make_train_mesh
from repro.launch import specs as specs_lib
from repro.pipeline.pipeline_step import (make_prefill_step, make_serve_step,
                                          make_train_step)

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def lower_combo(arch: str, shape_id: str, multi_pod: bool, overrides=None):
    """Lower + compile one (arch x shape x mesh) combo; returns the report."""
    cfg = get_config(arch)
    shape = get_shape(shape_id)
    cfg = specs_lib.shape_overrides(cfg, shape)
    if overrides:
        cfg = cfg.with_overrides(**overrides)
    mesh = make_train_mesh(cfg.pipeline_stages, cfg.tensor_parallel,
                           extra_data=cfg.extra_data, multi_pod=multi_pod)
    chips = mesh.devices.size
    t0 = time.time()

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            tc = TrainConfig(remat=True)
            step, _ = make_train_step(mesh, cfg, tc)
            state = specs_lib.state_sds(cfg, mesh, tc)
            batch = specs_lib.train_batch_sds(cfg, shape, mesh)
            lowered = jax.jit(step).lower(state, batch)
        elif shape.kind == "prefill":
            step = make_prefill_step(mesh, cfg,
                                     seq_chunks=cfg.prefill_seq_chunks)
            params = specs_lib.params_sds(cfg, mesh)
            batch = specs_lib.prefill_batch_sds(cfg, shape, mesh)
            if cfg.prefill_seq_chunks > 1:
                caches = specs_lib.prefill_caches_sds(cfg, shape, mesh)
                lowered = jax.jit(step).lower(params, batch, caches)
            else:
                lowered = jax.jit(step).lower(params, batch)
        else:  # decode
            dec = specs_lib.decode_inputs_sds(cfg, shape, mesh)
            step = make_serve_step(mesh, cfg, data_sharded=dec["data_sharded"])
            params = specs_lib.params_sds(cfg, mesh)
            if cfg.family == "audio":
                lowered = jax.jit(step).lower(params, dec["token"],
                                              dec["caches"], dec["pos"],
                                              dec["kv_source"])
            else:
                lowered = jax.jit(step).lower(params, dec["token"],
                                              dec["caches"], dec["pos"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll_hlo = analysis.collective_bytes(compiled.as_text())

    # roofline from the analytic per-device cost model (raw HLO counts each
    # while-loop body once — see cost_model.py docstring)
    combo = cost_model.Combo(cfg, shape, multi_pod=multi_pod)
    cm = cost_model.roofline(combo)
    mf = analysis.model_flops(cfg, shape)
    flops_dev = cm["flops"]["total"]

    report = {
        "arch": arch, "shape": shape_id,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "stage_x_tensor": [cfg.pipeline_stages, cfg.tensor_parallel],
        "microbatches": combo.M, "ticks": combo.ticks,
        "data_sharded": combo.data_sharded,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "hlo_flops_raw": float(cost.get("flops", 0.0)),
        "hlo_bytes_raw": float(cost.get("bytes accessed", 0.0)),
        "hlo_collectives_raw": coll_hlo,
        "bytes_per_device": {
            "arguments": mem.argument_size_in_bytes,
            "output": mem.output_size_in_bytes,
            "temp": mem.temp_size_in_bytes,
            "code": mem.generated_code_size_in_bytes,
        },
        "flops_per_device": cm["flops"],
        "collective_bytes_per_device": cm["collective_bytes"],
        "hbm_bytes_per_device": cm["hbm_bytes"],
        "roofline": cm["terms"],
        "dominant": cm["dominant"],
        "model_flops": mf,
        "useful_ratio": mf / (flops_dev * chips) if flops_dev else None,
    }
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=OUT_DIR)
    ap.add_argument("--set", default="",
                    help="config overrides for perf experiments, e.g. "
                         "pipeline_stages=4,tensor_parallel=1,extra_data=4")
    ap.add_argument("--tag", default="", help="suffix for the output json")
    args = ap.parse_args()

    overrides = {}
    for kv in filter(None, args.set.split(",")):
        k, v = kv.split("=")
        overrides[k] = int(v) if v.lstrip("-").isdigit() else float(v)

    os.makedirs(args.out, exist_ok=True)
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = []
    for arch in archs:
        for shape_id in shapes:
            for mp in meshes:
                tag = f"{arch}_{shape_id}_{'2x16x16' if mp else '16x16'}"
                if args.tag:
                    tag += f"_{args.tag}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    print(f"[skip] {tag} (cached)")
                    continue
                print(f"[lower+compile] {tag} ...", flush=True)
                try:
                    rep = lower_combo(arch, shape_id, mp, overrides)
                    with open(path, "w") as f:
                        json.dump(rep, f, indent=1)
                    r = rep["roofline"]
                    print(f"  OK compile={rep['compile_s']}s "
                          f"flops/dev={rep['flops_per_device']['total']:.3e} "
                          f"compute={r['compute_s']:.4f}s "
                          f"mem={r['memory_s']:.4f}s "
                          f"coll={r['collective_s']:.4f}s "
                          f"dom={rep['dominant']}", flush=True)
                except Exception as e:
                    failures.append((tag, repr(e)))
                    print(f"  FAIL {tag}: {e}")
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for t, e in failures:
            print(" ", t, e)
        raise SystemExit(1)
    print("\nall combos lowered + compiled OK")


if __name__ == "__main__":
    main()
