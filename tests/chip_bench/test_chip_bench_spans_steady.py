"""A traced run of the steady cell at a CPU size: the workers' waits, host
time and replication rounds are read, and each worker's counters account
for its segments."""
import pytest

from chipbench_spans import Recorder, run_traced

STAGE = ("stage.wait_ms", "stage.host_ms", "stage.replicate_ms")


@pytest.fixture
def recorder(monkeypatch):
    return Recorder(monkeypatch)


def test_steady_cell_times_its_stages_and_rounds(tiny_cell, compile_cache,
                                                 recorder):
    out = run_traced(tiny_cell("mnv2.4chip.steady"), 3.0, compile_cache)
    assert out["correct"] is True, out["checks"]
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(STAGE) <= set(m)
    assert all(m[k] > 0 for k in STAGE), m
    recorder.check_counters()
    names = recorder.names()
    assert "ftp.coord.probe" not in names
    assert {f"ftp.w{d}.{k}" for d in range(4) for k in ("fwd", "step")} \
        <= set(names)
    # the rounds run inside the segment, in every worker's thread
    assert {f"ftp.w{d}.replicate" for d in range(4)} <= set(names)
