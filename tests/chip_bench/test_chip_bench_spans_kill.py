"""A traced run of the kill cell at a CPU size: one probe decides the
recovery, the survivors' program loads lie inside its resume, and each
worker's counters account for its segments."""
import pytest

from chipbench_spans import Recorder, run_traced


@pytest.fixture
def recorder(monkeypatch):
    return Recorder(monkeypatch)


def test_kill_cell_times_its_probe_and_loads(tiny_cell, compile_cache,
                                             recorder):
    cell = tiny_cell("mnv2.3stage.kill")
    cell["kill"]["batches_into_window"] = 5
    out = run_traced(cell, 12.0, compile_cache)
    assert out["correct"] is True, out["checks"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    # the probe waits out its whole deadline for the dead worker
    assert 0.5 <= m["recover.probe_s"] < m["recover.detect_s"]
    assert 0 < m["recover.load_s"] <= m["recover.resume_s"]
    assert recorder.names().count("ftp.coord.probe") == 1
    assert recorder.names().count("ftp.coord.recover") == 1
    recorder.check_counters()
