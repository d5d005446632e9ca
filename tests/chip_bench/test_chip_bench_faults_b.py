"""A run with a fault planted under the timed path comes out as not
correct (no_exchange, altered_loss)."""
import pytest

from chipbench_faults import FAULTS, run_tiny


@pytest.mark.parametrize("fault", ["no_exchange", "altered_loss"])
def test_fault_is_caught(fault, tiny_cell, monkeypatch, compile_cache):
    FAULTS[fault](monkeypatch)
    out = run_tiny(tiny_cell("mnv2.4chip.steady"), cache_dir=compile_cache)
    assert out["correct"] is False, out["checks"]
