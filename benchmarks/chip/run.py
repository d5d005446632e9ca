"""Chip benchmark of the live FTPipeHD pipeline: one cell, one run.

    python3 -m benchmarks.chip.run --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Builds the cell's ``WorkloadSpec`` from ``--seed``, starts
``repro.run.Run`` on the queue transport in this one process (which holds
the chips), warms up through the cell's warm-up batches (at least two
global replication points), opens the window at the commit of the first
batch after them, measures for ``--seconds``, stops the run, checks the
run's first steps against the plain reference (and, in a cell with a
fault, the survivors' hand-off) and prints one JSON line last. With ``--trace 1`` the window is traced by the JAX profiler and the
line carries the cell's per-layer metrics instead of its end-to-end ones.

A cell with a ``kill`` first makes one rehearsal run of the same fault
and recovery on the same profile, so that the recovery's programs are in
the persistent compilation cache when the measured recovery loads them.

Without an accelerator, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()   # set-up is counted from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import inspect  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import numpy as np  # noqa: E402

from benchmarks.chip import check, registry, window  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
POLL_S = 0.005
WARMUP_DEADLINE_S = 1500.0
HANDOFF_WAIT_S = 60.0
SEED_MOD = 2 ** 31


class NoChip(RuntimeError):
    pass


@dataclasses.dataclass
class Context:
    """What a metric's reader may read."""
    cell: dict                     # with its model_kind and spec
    reference: object
    result: object                 # repro.runtime.live.LiveResult
    t_open: float                  # coordinator clock, seconds
    seconds: float
    batches: list                  # distinct batches committed in window
    traced_batches: list           # of those, committed in the traced span
    setup_s: float
    compile_setup: dict
    trace: object = None           # trace.Trace with --trace 1
    peaks: dict | None = None
    chips: int = 1


def log(text: str) -> None:
    print(text, flush=True)


# ------------------------------ the run ------------------------------

def run_config(cell: dict, seed: int, *, kill=None, profile=None,
               num_batches=None):
    from repro.run import RunConfig
    from repro.runtime.devices import DeviceSpec
    from repro.runtime.live import LiveConfig
    from repro.runtime.protocol import ProtocolConfig
    from repro.runtime.workload import WorkloadSpec

    never = 10 ** 9
    return RunConfig(
        workload=WorkloadSpec(kind=cell["model_kind"], seed=seed,
                              batch_size=cell["batch"],
                              num_data_batches=cell["data_batches"],
                              **cell["spec"]),
        live=LiveConfig(
            num_workers=cell["workers"],
            num_batches=num_batches or cell["horizon_batches"],
            lr=cell["lr"],
            protocol=ProtocolConfig(
                chain_every=cell["chain_every"],
                global_every=cell["global_every"],
                repartition_first_at=never, repartition_every=never,
                detect_timeout=cell["detect_timeout"]),
            device_specs=[DeviceSpec(f"dev-{i}", c)
                          for i, c in enumerate(cell["capacities"])],
            capacity_source="spec", kill=kill, profile=profile,
            segment_timeout=cell["segment_timeout"]),
        transport="queue")


def prepare(cell: dict):
    """The cell with its model's ``model_kind`` and ``spec`` (the
    ``WorkloadSpec`` fields of ``registry.spec_of``), and the model's
    reference."""
    config = registry.load_config(cell["config"])
    ref = registry.load_reference(config["reference"])
    return dict(cell, model_kind=config["model"]["kind"],
                spec=registry.spec_of(cell, config, ref)), ref


def analytic_profile(cell: dict):
    """The program's analytic profile the cell names, or None: given the
    batch and those of the cell's ``spec`` its signature takes."""
    if "profile" not in cell:
        return None
    from repro.runtime.devices import WorkloadProfile
    make = getattr(WorkloadProfile, cell["profile"])
    takes = inspect.signature(make).parameters
    args = dict(cell["spec"], batch=cell["batch"])
    return make(**{k: v for k, v in args.items() if k in takes})


def wait_committed(run, batch: int, deadline_s: float) -> float:
    """Poll until ``batch`` has committed; returns the monotonic time at
    which it was seen."""
    deadline = time.monotonic() + deadline_s
    while True:
        st = run.status()
        if st["state"] in ("failed", "finished"):
            run.wait()
            raise RuntimeError(f"run ended before batch {batch} committed")
        chain = st["chains"].get(0)
        if chain is not None and chain["progress"]["last_committed"] >= batch:
            return time.monotonic()
        if time.monotonic() > deadline:
            raise TimeoutError(f"batch {batch} did not commit in "
                               f"{deadline_s} s")
        time.sleep(POLL_S)


def await_resumed(run, span: int, deadline_s: float) -> None:
    """After the window: wait until the survivors have committed ``span``
    batches from their restart, for the check. Gives up at the deadline,
    leaving the check to fail."""
    deadline = time.monotonic() + deadline_s
    coord = run._coord
    while time.monotonic() < deadline and run.status()["state"] == "running":
        if coord.recoveries and (coord._committed >= coord.recoveries[0]
                                 ["restart"] + span - 1):
            return
        time.sleep(POLL_S)


def describe(tag: str, res) -> None:
    for b, pts in res.partitions:
        log(f"[{tag}] partition from batch {b}: last layers {list(pts)}")
    for t, e in res.events:
        if e.startswith(("KILL", "failure", "recovered")):
            log(f"[{tag}] t={t:.3f}s {e}")
    log(f"[{tag}] stage devices {res.stage_devices}")


def rehearse(cell: dict, seed: int, profile) -> None:
    """The cell's fault and recovery once, untimed, on the same profile."""
    from repro.run import Run
    reh = cell["rehearsal"]
    cfg = run_config(cell, seed, kill=(cell["kill"]["worker"],
                                       reh["kill_at"]),
                     profile=profile, num_batches=reh["stop_after"] + 50)
    run = Run(cfg).start()
    wait_committed(run, reh["stop_after"], WARMUP_DEADLINE_S)
    run.stop()
    res = run.wait(timeout=600)
    describe("rehearsal", res)
    if len(res.recoveries) != 1:
        raise RuntimeError(f"rehearsal made {len(res.recoveries)} "
                           f"recoveries, expected 1")


def peak_memory(devices) -> list[int]:
    """Peak bytes in use on each device, where the backend reports it."""
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices]


def jsonable(v):
    if isinstance(v, float) and not math.isfinite(v):
        return str(v)
    return v


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True,
             cache_dir: Path | None = CACHE_DIR) -> dict:
    """One run of one cell; returns the result line as a dict."""
    import jax

    from benchmarks.chip import compiles, peaks as peaks_mod, probe
    from benchmarks.chip import trace as trace_mod

    cell, ref = prepare(cell)
    wseed = seed % SEED_MOD

    devices = jax.devices()
    dev0 = devices[0]
    try:
        peaks = peaks_mod.peaks_for(dev0.platform, dev0.device_kind)
    except peaks_mod.UnknownDevice as exc:
        if require_chip:
            raise NoChip(str(exc)) from exc
        peaks = None
    if require_chip and len(devices) < cell["chips"]:
        raise NoChip(f"the cell asks for {cell['chips']} chips, JAX sees "
                     f"{len(devices)}")
    used = devices[:cell["chips"]]
    if cache_dir is not None:
        jax.config.update("jax_compilation_cache_dir", str(cache_dir))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        # no eviction: every program of the cell has to stay, and an
        # evicting cache refuses every write once one entry lacks its
        # access-time file
        jax.config.update("jax_compilation_cache_max_size", -1)
    log(f"device: platform {dev0.platform}, kind {dev0.device_kind}, "
        f"count {len(devices)}, cell uses {len(used)}")
    log(f"compile cache: {cache_dir}, min compile time 0 s")

    from repro.run import Run
    from repro.runtime.live import VerticalSyncStash, Worker

    with compiles.CompileLog() as clog:
        # an analytic profile leads to the same partitions in every run,
        # which a measured one does not
        profile = analytic_profile(cell)
        if "kill" in cell:
            rehearse(cell, wseed, profile)
            gc.collect()
        warm = cell["warmup_batches"]
        kill = None
        if "kill" in cell:
            kill = (cell["kill"]["worker"],
                    warm + cell["kill"]["batches_into_window"])
        steps = cell["check_steps"]
        run = Run(run_config(cell, wseed, kill=kill, profile=profile))
        handoff = probe.Redistribution(Worker) if kill else None
        with handoff or contextlib.nullcontext():
            with probe.Versions(VerticalSyncStash, (1, steps)) as cap:
                run.start()
                t_open_local = wait_committed(run, warm, WARMUP_DEADLINE_S)
            setup_s = t_open_local - T_PROCESS
            trace_dir, t_trace_ns = None, 0
            if trace:
                trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0    # host spans from JAX only
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                t_trace_ns = time.time_ns()
                t_trace = time.monotonic()
            time.sleep(max(0.0, t_open_local + seconds - time.monotonic()))
            t_close_local = time.monotonic()
            if trace:
                trace_window = time.monotonic() - t_trace
                jax.profiler.stop_trace()
            if kill:
                await_resumed(run, cell["data_batches"], HANDOFF_WAIT_S)
            run.stop()
            res = run.wait(timeout=600)
        t_zero = run._coord._t0            # the coordinator's clock zero
    compile_setup = clog.summary(T_PROCESS, t_open_local)
    compile_window = clog.summary(t_open_local, t_close_local)
    memory_peaks = peak_memory(used)

    t_open = res.commit_times[warm]
    batches = window.in_window(res.commit_times, t_open, seconds)
    traced = (window.in_window(res.commit_times, t_trace - t_zero,
                               trace_window) if trace else [])
    describe("measured", res)
    log(f"set-up: {setup_s:.3f} s; compile {compile_setup['compile_s']:.3f} s"
        f", {compile_setup['backend_compiles']} backend compiles, cache "
        f"hits {compile_setup['cache_hits']}, misses "
        f"{compile_setup['cache_misses']}")
    log(f"window: {len(batches)} batches committed in {seconds} s; "
        f"compiles in window {compile_window['backend_compiles']} "
        f"(cache hits {compile_window['cache_hits']}, misses "
        f"{compile_window['cache_misses']}, "
        f"{compile_window['compile_s']:.3f} s)")
    log(f"peak device memory per chip: {memory_peaks}")
    rec = window.recovery(res.events, res.commit_times)
    if rec is not None:
        before = window.rate_between(res.commit_times, t_open, rec["t_kill"],
                                     cell["batch"])
        after = window.rate_between(res.commit_times, rec["t_resume"],
                                    t_open + seconds, cell["batch"])
        seen: dict = {}
        for b, _ in res.loss_log:
            seen[b] = seen.get(b, 0) + 1
        rerun = sorted(b for b, n in seen.items() if n > 1)
        log(f"recovery: {rec}; kill {rec['t_kill'] - t_open:.3f} s and "
            f"first commit of the survivors {rec['t_resume'] - t_open:.3f} s "
            f"into the window; samples/s before the kill {before}, after "
            f"the survivors' first commit {after}; re-run batches {rerun}")

    # ---- the check: the run's first steps against the reference ----
    params0 = ref.init_params(wseed, **cell["spec"])
    sizes = ref.leaf_sizes(params0)
    layer_sizes = [sum(s) for s in sizes]
    workers0 = list(range(cell["workers"]))
    ranges, a = [], 0
    for p in res.partitions[0][1]:
        ranges.append((a, p))
        a = p + 1
    run_state = {"losses": [float(v) for v in res.losses[:steps]]}
    for v, key in ((0, "p0"), (1, "p1"), (steps, "pk")):
        got = cap.stage_slices(v, workers0)
        if got is None:
            raise RuntimeError(f"no stage pushed weight version {v}")
        run_state[key] = check.split_stages(
            ranges, {i: np.asarray(b) for i, b in got.items()}, layer_sizes)
    values = {}
    if kill:
        # exactly one recovery, of the killed worker, and its hand-off
        failed = [r["failed"] for r in res.recoveries]
        values["recovery_miss"] = float(failed != [[kill[0]]])
        values["redistribution_gap"] = float("inf")
        values["resume_loss_ratio"] = float("inf")
        if res.recoveries:
            first = res.recoveries[0]
            values["redistribution_gap"] = check.redistribution_gap(
                first, res.partitions[0][1], workers0, handoff.chain_rounds,
                handoff.installs, layer_sizes)
            values["resume_loss_ratio"] = check.resume_loss_ratio(
                res.losses, first["restart"], cell["data_batches"])
            rounds = {d: r[0] for d, r in handoff.chain_rounds.items()}
            refits = [(r["dev"], r["version"], r["range"])
                      for r in handoff.installs]
            log(f"hand-off: last chain replication per worker {rounds}; "
                f"refit installs (worker, version, range) {refits}")
    ctx = Context(cell=cell, reference=ref, result=res,
                  t_open=t_open, seconds=seconds, batches=batches,
                  traced_batches=traced, setup_s=setup_s,
                  compile_setup=compile_setup, peaks=peaks, chips=len(used))
    if trace:
        ctx.trace = trace_mod.load(trace_dir, trace_window, t_trace_ns)
        shutil.rmtree(trace_dir, ignore_errors=True)
    del run, cap, handoff
    gc.collect()

    data = ref.make_batches(wseed, steps, cell["batch"], **cell["spec"])
    ref_run = ref.first_steps(params0, data, lr=cell["lr"],
                              n_stages=cell["workers"], steps=steps)
    ref_state = {"losses": ref_run["losses"],
                 "grad0": ref.flat_layers(ref_run["grad0"]),
                 "p0": ref.flat_layers(ref_run["versions"][0]),
                 "pk": ref.flat_layers(ref_run["versions"][steps])}
    values.update(check.readings(run_state, ref_state, sizes, cell["lr"]))
    correct, shown = check.verdict(values, cell["limits"])
    log(f"check: {values}")

    # ---- metrics ----
    wanted = registry.metrics_of(cell["name"])
    group = wanted["per_layer"] if trace else wanted["end_to_end"]
    metrics = {}
    for m in group:
        value = registry.load_reader(m["name"]).read(ctx)
        if value is None:
            if not trace:
                raise RuntimeError(f"end-to-end metric {m['name']} has "
                                   f"nothing to read")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    losses = [float(res.losses[b]) for b in batches
              if b < len(res.losses)]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(used), "memory_peak_bytes": max(memory_peaks)}
    out = {"correct": bool(correct), "attempted": len(batches),
           "failed": sum(1 for v in losses if not math.isfinite(v)),
           "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = trace_mod.mean_busy_s(ctx.trace)
        device["window_s"] = ctx.trace.window_s
        out["breakdown"] = trace_mod.breakdown(ctx.trace)
    out["checks"] = {k: {"value": jsonable(v["value"]), "limit": v["limit"]}
                     for k, v in shown.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    cell = registry.load_cell(args.workload)
    try:
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as exc:
        print(f"chip benchmark: {exc}", file=sys.stderr)
        return 2
    for k, v in out["checks"].items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
