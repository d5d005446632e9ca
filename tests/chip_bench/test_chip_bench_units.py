"""The benchmark's yardstick without a chip: model FLOPs, the peaks table,
window arithmetic, the trace reduction and the comparison."""
import hashlib
import json
import math
import types

import numpy as np
import pytest

from benchmarks.chip import check, peaks, registry, window
from benchmarks.chip import trace as trace_mod

CELLS = ["mnv2.3stage.kill", "mnv2.4chip.steady"]


def reference():
    return registry.load_reference("mobilenetv2_cifar")


def test_forward_flops_per_sample_at_32x32():
    # 0.176 GFLOP: a t = 1 block has no expansion convolution
    assert reference().forward_flops_per_sample(image_hw=32) == 175_952_896


def test_parameter_count_matches_the_configuration():
    from benchmarks.chip import run
    for cell in CELLS:
        cell, ref = run.prepare(registry.load_cell(cell))
        cfg = registry.load_config(cell["config"])
        assert ref.param_count(**cell["spec"]) == cfg["model"]["parameters"]
        assert (ref.forward_flops_per_sample(**cell["spec"])
                == cfg["model"]["forward_flops_per_sample"])


# What each cell read before its model's sizes came through the
# reference's SPEC_KEYS: sha256 of the x and label bytes of its
# check_steps batches for seed 7.
BATCH_DIGESTS = {
    "mnv2.3stage.kill":
        "f2c60e66c28613ce9e3d794aa40a007efac8b0fa79c5019c3b1fb0e61b95d64a",
    "mnv2.4chip.steady":
        "d189ca74096699a4bb853917a66292fd1a366cc51732aca8bb0d98f77a6a1d84",
}


@pytest.mark.parametrize("name", CELLS)
def test_cell_builds_the_same_workload_batches_and_profile(name):
    from benchmarks.chip import run
    from repro.runtime.devices import WorkloadProfile
    from repro.runtime.workload import WorkloadSpec
    cell, ref = run.prepare(registry.load_cell(name))
    assert cell["spec"] == {"image_hw": 32, "noise": 0.3}
    assert run.run_config(cell, 7).workload == WorkloadSpec(
        kind="mobilenet", seed=7, image_hw=32, batch_size=128,
        num_data_batches=8, noise=0.3)
    digest = hashlib.sha256()
    for b in ref.make_batches(7, cell["check_steps"], cell["batch"],
                              **cell["spec"]):
        digest.update(b["x"].tobytes())
        digest.update(b["labels"].tobytes())
    assert digest.hexdigest() == BATCH_DIGESTS[name]
    assert ref.forward_flops_per_sample(**cell["spec"]) == 175_952_896
    got = run.analytic_profile(cell)
    if "profile" not in cell:
        assert got is None
        return
    want = WorkloadProfile.mobilenetv2(batch=128, image_hw=32)
    for field in ("fwd_times", "bwd_times", "out_bytes", "weight_bytes"):
        np.testing.assert_array_equal(getattr(got, field),
                                      getattr(want, field))


def test_mfu_counts_the_steady_cells_flops():
    from benchmarks.chip import run
    cell, ref = run.prepare(registry.load_cell("mnv2.4chip.steady"))
    commits = {b: 0.004 * b for b in range(1, 400)}
    ctx = types.SimpleNamespace(
        cell=cell, reference=ref, result=types.SimpleNamespace(
            commit_times=commits), t_open=0.2, seconds=1.0, chips=4,
        peaks=peaks.peaks_for("tpu", "TPU v5 lite"))
    rate = window.samples_per_s(commits, 0.2, 1.0, 128)
    assert math.isclose(registry.load_reader("mfu").read(ctx),
                        100.0 * 3 * 175_952_896 * rate / (4 * 197e12))
    ctx.peaks = None
    assert registry.load_reader("mfu").read(ctx) is None


def test_spec_comes_from_the_cell_else_the_configuration():
    ref = types.SimpleNamespace(SPEC_KEYS=("width", "noise"))
    config = {"name": "c", "model": {"kind": "mlp", "width": 64,
                                     "noise": 0.3}}
    assert registry.spec_of({"name": "w"}, config, ref) == {
        "width": 64, "noise": 0.3}
    assert registry.spec_of({"name": "w", "width": 8}, config, ref) == {
        "width": 8, "noise": 0.3}
    with pytest.raises(KeyError, match="depth"):
        registry.spec_of({"name": "w"}, config, types.SimpleNamespace(
            SPEC_KEYS=("width", "depth")))


@pytest.mark.parametrize("platform,kind", [("tpu", "TPU v9 imaginary"),
                                           ("cpu", "cpu"),
                                           ("gpu", "TPU v5 lite")])
def test_peaks_refuse_unknown_devices(platform, kind):
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for(platform, kind)


def test_peaks_of_v5e():
    p = peaks.peaks_for("tpu", "TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


def test_run_refuses_a_host_without_a_chip(capsys):
    from benchmarks.chip import run
    cell = registry.load_cell("mnv2.3stage.kill")
    with pytest.raises(run.NoChip):
        run.run_cell(cell, 1, 1.0, False)
    assert run.main(["--workload", "mnv2.3stage.kill", "--seed", "7",
                     "--seconds", "1"]) != 0
    assert "{" not in capsys.readouterr().out


def test_window_counts_a_rerun_batch_once():
    # batch 12 committed, lost to a kill, re-run: its last commit counts
    commits = {10: 1.0, 11: 1.1, 12: 2.9, 13: 3.0, 14: 3.1}
    assert window.in_window(commits, 0.5, 3.0) == [10, 11, 12, 13, 14]
    assert window.in_window(commits, 1.0, 2.0) == [11, 12, 13]
    assert window.samples_per_s(commits, 1.0, 2.0, 128) == 128 * 3 / 2.0


def test_recovery_split_adds_up():
    events = [(0.5, "chain replication @batch 5"),
              (1.25, "KILL worker dev1 @batch 30"),
              (3.5, "failure detected: devs [1]; probing done"),
              (3.625, "recovered: 2 workers, partition (7, 12), resume "
                      "@batch 31"),
              (4.0, "chain replication @batch 35")]
    commits = {29: 1.0, 30: 1.25, 31: 4.5, 32: 4.625}
    rec = window.recovery(events, commits)
    assert rec["detect_s"] == 2.25 and rec["resume_s"] == 0.875
    assert math.isclose(rec["detect_s"] + rec["protocol_s"]
                        + rec["resume_s"], rec["recover_s"])
    assert rec["recover_s"] == 3.25
    assert window.recovery(events[:3], commits) is None


def test_control_point_gaps():
    commits = {b: 0.01 * b for b in range(20)}
    commits.update({b: commits[b] + 0.05 for b in range(10, 20)})
    gaps = window.control_point_gaps(commits, list(range(3, 20)), [5, 10])
    assert len(gaps) == 3          # 5, 10, 15; batch 0 is outside
    assert math.isclose(gaps[1], 0.06) and math.isclose(gaps[0], 0.01)


# A small trace in the profiler's own format: two chips, each with its
# programs and operations, and one host thread.
TRACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 10 offset_ps: 0 duration_ps: 4000000000 }
    events { metadata_id: 11 offset_ps: 5000000000 duration_ps: 3000000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 20 offset_ps: 0 duration_ps: 3000000000 }
    events { metadata_id: 21 offset_ps: 3000000000 duration_ps: 1000000000 }
    events { metadata_id: 20 offset_ps: 5000000000
             duration_ps: 3000000000 } }
  event_metadata { key: 10 value { id: 10 name: "jit_step_fn(7)" } }
  event_metadata { key: 11 value { id: 11 name: "jit_fwd_out(3)" } }
  event_metadata { key: 20 value { id: 20 name: "fusion.1" } }
  event_metadata { key: 21 value { id: 21 name: "%sgd.2 = (f32[65536]{0}, f32[65536]{0}) custom-call(%p, %g, %m), custom_call_target=\\"tpu_custom_call\\"" } } }
planes { id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 20 offset_ps: 2000000000
             duration_ps: 6000000000 } }
  event_metadata { key: 20 value { id: 20 name: "fusion.1" } } }
planes { id: 4 name: "/device:CUSTOM:Megascale Trace" }
planes { id: 3 name: "/host:CPU"
  lines { id: 1 name: "worker-0" timestamp_ns: 0
    events { metadata_id: 40 offset_ps: 3500000000
             duration_ps: 2000000000 } }
  event_metadata { key: 40 value { id: 40 name: "ExecuteReplicated" } } }
"""


@pytest.fixture
def small_trace():
    from jax.profiler import ProfileData
    return trace_mod.from_profile(ProfileData.from_text_proto(TRACE),
                                  window_s=10e-3, start_epoch_ns=0)


def test_trace_busy_and_gaps(small_trace):
    tr = small_trace
    assert tr.device_names() == ["/device:TPU:0", "/device:TPU:1"]
    assert math.isclose(trace_mod.busy_s(tr, "/device:TPU:0"), 7e-3)
    assert math.isclose(trace_mod.mean_busy_s(tr), 6.5e-3)
    gaps = trace_mod.idle_gaps(tr, "/device:TPU:0")
    assert [(round(s, 6), round(e, 6)) for s, e in gaps] == [
        (0.004, 0.005), (0.008, 0.01)]
    b = trace_mod.breakdown(tr)
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(6e-3)]
    assert ["jit_step_fn(7)/fusion.1", pytest.approx(3e-3)] in b["device_ops"]
    assert ["jit_fwd_out(3)/fusion.1", pytest.approx(3e-3)] in b["device_ops"]
    assert ["jit_step_fn(7)/%sgd.2", pytest.approx(1e-3)] in b["device_ops"]
    assert b["idle_gaps"][0] == ["chip1 no host event", pytest.approx(2e-3)]
    assert ["chip0 ExecuteReplicated", pytest.approx(1e-3)] in b["idle_gaps"]
    json.dumps(b)


def test_trace_readers(small_trace):
    ctx = types.SimpleNamespace(
        trace=small_trace, batches=[1, 2, 3], traced_batches=[1, 2])
    idle = registry.load_reader("device.idle_share").read(ctx)
    assert math.isclose(idle, 35.0)
    step = registry.load_reader("stage_step.device_ms").read(ctx)
    assert math.isclose(step, 3.5)           # 7 ms of programs, 2 batches
    ctx.trace = None
    assert registry.load_reader("device.idle_share").read(ctx) is None


def _state(ref, params, p1, pk, losses):
    return {"losses": losses, "p0": ref.flat_layers(params),
            "p1": ref.flat_layers(p1), "pk": ref.flat_layers(pk)}


def test_check_catches_an_unchanged_state():
    import jax
    ref = reference()
    params = ref.init_params(3)
    g = jax.tree.map(lambda a: a * 0 + 1.0, params)
    p1 = jax.tree.map(lambda p, d: p - 0.1 * d, params, g)
    p3 = jax.tree.map(lambda p, d: p - 0.3 * d, params, g)
    sizes = ref.leaf_sizes(params)
    truth = {"losses": [2.3, 2.3, 2.3], "grad0": ref.flat_layers(g),
             "p0": ref.flat_layers(params), "pk": ref.flat_layers(p3)}
    same = check.readings(_state(ref, params, p1, p3, [2.3] * 3), truth,
                          sizes, 0.1)
    assert same["loss_gap"] == 0
    assert same["grad_gap"] < 1e-5 and same["change_gap"] < 1e-5
    stuck = check.readings(_state(ref, params, params, params, [2.3] * 3),
                           truth, sizes, 0.1)
    assert math.isclose(stuck["grad_gap"], 1.0)
    assert math.isclose(stuck["change_gap"], 1.0)
    ok, shown = check.verdict(stuck, {"grad_gap": 0.5})
    assert not ok and shown["grad_gap"]["limit"] == 0.5
    assert not check.verdict({"loss_gap": math.inf}, {"loss_gap": 1.0})[0]


def test_split_stages_refuses_a_wrong_slice():
    with pytest.raises(ValueError):
        check.split_stages([(0, 1)], {0: np.zeros(5)}, [2, 2])
    layers = check.split_stages([(0, 0), (1, 1)],
                                {0: np.arange(2), 1: np.arange(3)}, [2, 3])
    assert [list(x) for x in layers] == [[0, 1], [0, 1, 2]]


def test_every_metric_has_a_reader_and_every_cell_its_parts():
    bench = json.loads((registry.ROOT / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert hasattr(registry.load_reader(m["name"]), "read")
    for w in bench["workloads"]:
        cell = registry.load_cell(w["name"])
        assert cell["config"] == w["config"]
        assert cell["chips"] == w["chips"]
        mine = registry.metrics_of(w["name"], bench)
        names = {m["name"] for m in mine["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert mine["per_layer"]
    for c in bench["configs"]:
        cfg = registry.load_config(c["name"])
        assert cfg["reduced"] == c["reduced"]
        assert registry.load_reference(cfg["reference"])


def _handoff(sizes):
    """A 3-stage run over 6 layers of the given sizes, worker 1 dead: its
    last chain replication, and the survivors' installs as a recovery at
    batch 31 that splits the layers (0..3), (4..5)."""
    rng = np.random.default_rng(0)
    old_points, new_ranges = (1, 3, 5), {0: (0, 3), 2: (4, 5)}
    held = {d: rng.normal(size=sum(sizes[a:e + 1])).astype(np.float32)
            for d, (a, e) in {0: (0, 1), 1: (2, 3), 2: (4, 5)}.items()}
    olds = {0: (0, 1), 1: (2, 3), 2: (4, 5)}
    layers = {}
    for d, (a, e) in olds.items():
        layers.update({j: w for j, w in check.unpack(held[d], (a, e),
                                                     sizes).items()
                       if w is not None})
    installs = []
    for d, (a, e) in new_ranges.items():
        installs.append({"dev": d, "version": 31, "range": (a, e),
                         "installed": np.concatenate(
                             [layers[j] for j in range(a, e + 1)]),
                         "old_range": olds[d], "old_newest": held[d]})
    rounds = {1: (30, (2, 3), held[1])}
    return old_points, rounds, installs


def test_redistribution_gap_reads_a_copy_as_exact():
    sizes = [3, 2, 4, 1, 5, 2]
    rec = {"failed": [1], "restart": 31}
    points, rounds, installs = _handoff(sizes)
    assert check.redistribution_gap(rec, points, [0, 1, 2], rounds,
                                    installs, sizes) == 0.0
    zeroed = [dict(r) for r in installs]
    zeroed[0]["installed"] = zeroed[0]["installed"].copy()
    zeroed[0]["installed"][5:] = 0          # layers 2 and 3, from worker 1
    gap = check.redistribution_gap(rec, points, [0, 1, 2], rounds, zeroed,
                                   sizes)
    assert 0 < gap < math.inf
    stale = {1: (25, (2, 3), rounds[1][2] + 0.5)}
    assert math.isclose(check.redistribution_gap(
        rec, points, [0, 1, 2], stale, installs, sizes), 0.5, rel_tol=1e-5)
    assert check.redistribution_gap(rec, points, [0, 1, 2], rounds,
                                    installs[:1], sizes) == math.inf
    assert check.redistribution_gap(rec, points, [0, 1, 2], {}, installs,
                                    sizes) == math.inf


def test_resume_loss_ratio():
    losses = np.full(40, np.nan)
    losses[20:30] = 0.5
    losses[30:38] = 0.75
    assert check.resume_loss_ratio(losses, 30, 8) == 1.5
    assert check.resume_loss_ratio(losses, 33, 8) == math.inf
    assert check.resume_loss_ratio(losses, 22, 8) == math.inf
