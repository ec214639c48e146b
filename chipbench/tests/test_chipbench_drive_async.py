"""The paper-net event-driven cell's driver loop at CPU size (W = 4,
cohorts of 2): the result line, the traced run, and the check seeing
each fault that the timed path can have."""
import pytest

from chipbench_tiny import drive
from test_chipbench_drive_sync import KEYS, _broken

CELL = "papernet-async-w1024"


def test_result_line(tiny_bench):
    out, logs = drive(tiny_bench, CELL)
    assert list(out) == KEYS
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"samples_per_s", "round_latency_p95_s",
                                   "setup_s"}
    assert any(line.endswith("compilations in the window: 0")
               for line in logs)


def test_traced_run(tiny_bench):
    out, _ = drive(tiny_bench, CELL, trace=True)
    assert list(out) == KEYS[:5] + ["breakdown", "checks"]
    assert out["correct"] is True
    assert set(out["metrics"]) == {"settle_ms.cnn"}


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_each_fault_fails_the_check(tiny_bench, monkeypatch, fault):
    from repro.core import fl_step
    monkeypatch.setattr(fl_step, "make_fl_round", _broken(fault))
    out, _ = drive(tiny_bench, CELL, seconds=0.3)
    assert out["correct"] is False
