"""A kill-cell run whose recovery installs zeroed layers on the survivors
comes out as not correct, by the hand-off check."""
import math

from chipbench_faults import FAULTS, run_tiny


def test_zeroed_handoff_is_caught(tiny_cell, monkeypatch, compile_cache):
    FAULTS["zeroed_handoff"](monkeypatch)
    cell = tiny_cell("mnv2.3stage.kill")
    cell["kill"]["batches_into_window"] = 5
    out = run_tiny(cell, seconds=8.0, cache_dir=compile_cache)
    assert out["correct"] is False, out["checks"]
    assert 0 < float(out["checks"]["redistribution_gap"]["value"]) < math.inf
