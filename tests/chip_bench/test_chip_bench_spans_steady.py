"""A traced run of the steady cell at a CPU size: the control-point split
accounts for the control points, and each worker's counters for its
segments."""
import pytest

from chipbench_spans import Recorder, run_traced

SPLIT = ("control_point.drain_ms", "control_point.replicate_ms",
         "control_point.refill_ms")


@pytest.fixture
def recorder(monkeypatch):
    return Recorder(monkeypatch)


def test_steady_cell_splits_its_control_points(tiny_cell, compile_cache,
                                               recorder):
    out = run_traced(tiny_cell("mnv2.4chip.steady"), 3.0, compile_cache)
    assert out["correct"] is True, out["checks"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(SPLIT) | {"stage.wait_ms", "stage.host_ms"} <= set(m)
    assert sum(m[k] for k in SPLIT) == pytest.approx(
        m["control_point_ms"], rel=0.05)
    assert m["stage.wait_ms"] > 0 and m["stage.host_ms"] > 0
    recorder.check_counters()
    names = recorder.names()
    assert "ftp.coord.probe" not in names
    assert {f"ftp.w{d}.{k}" for d in range(4) for k in ("fwd", "step")} \
        <= set(names)
