"""Bring-up check of the live FTPipeHD pipeline on a TPU.

Trains MobileNetV2 (the paper's workload: CIFAR variant, all 19 layers at
width 1.0) on 32x32x3 inputs at batch 128, with weights and batches made
from ``--seed``, through the normal entry point ``repro.run.Run`` on the
queue transport: a coordinator and one worker thread per stage, all in
this one process, which holds the chip. Every phase checks its own result:

  (a) 3 workers, no re-partition: the first batch's loss equals the
      sequential ``LayerChain.loss_fn`` within ``LOSS_RTOL``, every loss
      is finite and the median of the last 5 is below the first;
  (b) spec capacities 1,1,2 with one §III-D re-partition and worker 1
      killed mid-run: §III-F recovery and redistribution on the chip;
  (c) the fused int8 wire (``wire_compress="int8-fused"``);

plus the count of ``tpu_custom_call`` ops in one compiled stage step,
which must be positive (Pallas ran natively, not in the interpreter).

``--chips 4`` runs only the four-chip path: 4 workers, each on its own
chip, compared with the same run with every worker pinned to chip 0
(losses must agree within ``CHIPS_RTOL``), then the four-chip run again
with worker 1 killed mid-run, so §III-F redistributes between chips.

The last line of standard output is the JSON result. Without a TPU the
script exits non-zero and prints none: it never falls back to the CPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from unittest import mock

LOSS_RTOL = 2e-3      # pipeline vs sequential first-batch loss (TPU f32
#                       default matmul precision; the programs differ only
#                       in how XLA fuses the stage boundaries)
CHIPS_RTOL = 1e-5     # four chips vs one chip: identical stage programs
IMAGE_HW, BATCH = 32, 128
# Plain SGD. Stale-gradient 1F1B (the paper's async semantics) with
# batch-statistics BatchNorm diverges on 3 stages at lr 0.05, while the
# sequential model learns; at 0.02 the loss falls steadily (checked on the
# async-semantics oracle, runtime/semantics.py, at this size).
LR = 0.02


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _config(seed, workers, batches, *, capacities=None, repartition_at=None,
            kill=None, wire_compress="off"):
    from repro.run import RunConfig
    from repro.runtime.devices import DeviceSpec
    from repro.runtime.live import LiveConfig
    from repro.runtime.protocol import ProtocolConfig
    from repro.runtime.workload import WorkloadSpec

    caps = capacities or [1.0] * workers
    return RunConfig(
        workload=WorkloadSpec(kind="mobilenet", image_hw=IMAGE_HW,
                              batch_size=BATCH, seed=seed),
        live=LiveConfig(
            num_workers=workers, num_batches=batches, lr=LR,
            protocol=ProtocolConfig(
                chain_every=5, global_every=10,
                repartition_first_at=repartition_at or 10_000,
                repartition_every=10_000, detect_timeout=2.0),
            device_specs=[DeviceSpec(f"dev-{i}", c)
                          for i, c in enumerate(caps)],
            capacity_source="spec", kill=kill, wire_compress=wire_compress,
            segment_timeout=600.0))


def _train(name, cfg):
    """One run through the public facade; prints and returns its result."""
    from repro.run import Run

    t0 = time.perf_counter()
    res = Run(cfg).start().wait()
    wall = time.perf_counter() - t0
    commits = res.commit_times
    setup = commits[min(commits)]
    print(f"[{name}] set-up (to first commit, compiles included) "
          f"{setup:.2f} s, run {commits[max(commits)] - setup:.2f} s, "
          f"wall {wall:.2f} s")
    print(f"[{name}] losses {[round(float(x), 5) for x in res.losses]}")
    for b, pts in res.partitions:
        print(f"[{name}] partition from batch {b}: points {list(pts)}")
    for t, e in res.events:
        if e.startswith(("re-partition", "failure", "recovered")):
            print(f"[{name}] t={t:.2f}s {e}")
    print(f"[{name}] stage devices {res.stage_devices}")
    return res


def _check_losses(name, losses):
    import numpy as np
    check(bool(np.isfinite(losses).all()), f"{name}: non-finite losses")
    check(float(np.median(losses[-5:])) < float(losses[0]),
          f"{name}: loss did not decrease")


def _custom_calls(chain, a, e, x_shape, fused):
    """tpu_custom_call ops in the compiled step of stage slice [a, e]."""
    import jax
    import jax.numpy as jnp

    from repro.runtime.stage_executor import StageExecutor
    sl, buf = chain.flat_slice(a, e)
    ex = StageExecutor(chain, sl, last=False, lr=LR)
    x = jnp.zeros(x_shape, jnp.float32)
    ct = jnp.zeros(jax.eval_shape(ex.forward, buf, x).shape, jnp.float32)
    if fused:
        lowered = ex._step_q.lower(buf, buf, sl.zeros(), x, ct, None, None)
    else:
        lowered = ex._step.lower(buf, buf, sl.zeros(), x, ct, None)
    return lowered.compile().as_text().count("tpu_custom_call")


def one_chip(seed: int) -> None:
    import jax
    import numpy as np

    # (a) correctness against the sequential reference
    cfg = _config(seed, 3, 24)
    chain, batches = cfg.workload.build()
    ref = float(jax.jit(chain.loss_fn)(chain.params, batches[0]))
    res = _train("a", cfg)
    first = float(res.losses[0])
    print(f"[a] first-batch loss {first:.6f}, sequential reference "
          f"{ref:.6f}, |diff| {abs(first - ref):.3e} "
          f"(tolerance {LOSS_RTOL} x |ref|)")
    check(abs(first - ref) <= LOSS_RTOL * abs(ref),
          "a: first-batch loss differs from the sequential reference")
    _check_losses("a", res.losses)
    check(res.recoveries == [] and len(res.partitions) == 1,
          "a: unexpected re-partition or recovery")
    t0 = time.perf_counter()
    n_step = _custom_calls(chain, 7, 12, (BATCH, 16, 16, 32), fused=False)
    print(f"[a] stage [7, 12] step: {n_step} tpu_custom_call op(s), "
          f"compiled in {time.perf_counter() - t0:.2f} s")
    check(n_step > 0, "a: no Pallas kernel in the compiled stage step")

    # (b) one §III-D re-partition, then a kill and §III-F recovery
    res = _train("b", _config(seed, 3, 24, capacities=[1.0, 1.0, 2.0],
                              repartition_at=6, kill=(1, 14)))
    _check_losses("b", res.losses)
    check(any(0 < b < 14 for b, _ in res.partitions),
          "b: no capacity-driven re-partition before the kill")
    check(len(res.recoveries) == 1 and res.recoveries[0]["failed"] == [1],
          "b: no recovery from the kill of worker 1")
    check(len(res.final_partition) == 2, "b: recovered pipeline is not "
                                         "2 stages")
    print(f"[b] recovery {res.recoveries[0]}")

    # (c) the fused int8 wire
    res = _train("c", _config(seed, 3, 12, wire_compress="int8-fused"))
    check(bool(np.isfinite(res.losses).all()), "c: non-finite losses")
    kb = res.transport_stats["kind_bytes"]
    print(f"[c] wire bytes by kind {kb}")
    n_q = _custom_calls(chain, 7, 12, (BATCH, 16, 16, 32), fused=True)
    print(f"[c] stage [7, 12] fused-wire step: {n_q} tpu_custom_call op(s)")
    check(n_q > n_step, "c: the fused step holds no quantize kernel")


def four_chips(seed: int) -> None:
    import jax
    import numpy as np

    from repro.runtime import live

    devices = jax.devices()
    check(len(devices) == 4, f"--chips 4 needs 4 devices, JAX sees "
                             f"{len(devices)}")
    own = {d: [devices[d].id] for d in range(4)}
    spread = _train("4 chips", _config(seed, 4, 20))
    with mock.patch.object(live, "stage_device", lambda dev: devices[0]):
        pinned = _train("1 chip", _config(seed, 4, 20))
    for name, res in (("4 chips", spread), ("1 chip", pinned)):
        _check_losses(name, res.losses)
    check(spread.stage_devices == own,
          f"4 chips: stage buffers not one per chip: {spread.stage_devices}")
    check(all(ids == [devices[0].id]
              for ids in pinned.stage_devices.values()),
          f"1 chip: stage buffers off chip 0: {pinned.stage_devices}")
    diff = float(np.max(np.abs(spread.losses - pinned.losses)))
    scale = float(np.max(np.abs(pinned.losses)))
    print(f"[4 chips vs 1 chip] max |loss diff| {diff:.3e} "
          f"(tolerance {CHIPS_RTOL} x {scale:.4f})")
    check(diff <= CHIPS_RTOL * scale, "4 chips: losses differ from the "
                                      "one-chip run")

    # where a run resumes after a kill depends on what had committed when
    # the failure was detected: only the losses before the kill compare
    killed = _train("4 chips, kill", _config(seed, 4, 20, kill=(1, 12)))
    _check_losses("4 chips, kill", killed.losses)
    check(len(killed.recoveries) == 1
          and killed.recoveries[0]["failed"] == [1],
          "4 chips, kill: no recovery from the kill of worker 1")
    check(killed.stage_devices == own, f"4 chips, kill: a survivor left "
                                       f"its chip: {killed.stage_devices}")
    pre = float(np.max(np.abs(killed.losses[:12] - spread.losses[:12])))
    print(f"[4 chips, kill] recovery {killed.recoveries[0]}; max |loss "
          f"diff| before the kill {pre:.3e}")
    check(pre <= CHIPS_RTOL * scale, "4 chips, kill: losses before the kill "
                                     "differ from the run without it")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="1: phases (a)-(c) on one chip; 4: only the "
                         "four-chip placement path and its one-chip "
                         "comparison")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights and the synthetic batches")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              f"this check does not fall back to the CPU", file=sys.stderr)
        return 1
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    print(f"device: platform {dev.platform}, kind {dev.device_kind}, "
          f"count {len(devices)}")
    print(f"compile cache: {cache}")
    print(f"workload: MobileNetV2 (19 layers, width 1.0), {IMAGE_HW}x"
          f"{IMAGE_HW}x3 inputs, batch {BATCH}, queue transport, seed "
          f"{args.seed}")
    try:
        (four_chips if args.chips == 4 else one_chip)(args.seed)
    except SmokeFailure as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
