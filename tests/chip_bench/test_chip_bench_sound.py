"""Sound runs of both cells at a CPU size: correct, with every end-to-end
metric of the cell, and the kill cell's recovery inside the window."""
from chipbench_faults import run_tiny


def test_steady_cell_runs_correct_across_devices(tiny_cell, compile_cache):
    out = run_tiny(tiny_cell("mnv2.4chip.steady"), cache_dir=compile_cache)
    assert out["correct"] is True, out["checks"]
    assert set(out["metrics"]) == {"samples_per_s", "setup_s"}
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"


def test_kill_cell_recovers_inside_the_window(tiny_cell, compile_cache):
    # the rehearsal leaves the survivors' programs in the cache, so the
    # measured recovery loads them instead of compiling in the window
    cell = tiny_cell("mnv2.3stage.kill")
    cell["kill"]["batches_into_window"] = 5
    out = run_tiny(cell, seconds=12.0, cache_dir=compile_cache)
    assert out["correct"] is True, out["checks"]
    assert set(out["metrics"]) == {"recover_s", "setup_s"}
    assert 2.0 < out["metrics"]["recover_s"]["value"] < 10.0
