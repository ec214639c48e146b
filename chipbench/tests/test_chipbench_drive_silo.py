"""The staged smollm-135m cross-silo cell's driver loop at CPU size (4
silos, smoke widths): the result line and the traced run. The cell is
not in BENCHMARK.json yet (its check does not separate the control;
PERF.md, Open questions), so the test adds it to its copy."""
import numpy as np
import pytest

from chipbench_tiny import drive, make_tiny, stage_silo
from test_chipbench_drive_sync import KEYS

SETTLEMENT = ("unsealed", "chain_invalid", "penalty_mismatch",
              "cid_mismatch")


@pytest.fixture(scope="module")
def silo(tmp_path_factory):
    bench = make_tiny(tmp_path_factory.mktemp("checkout"))
    return bench, stage_silo(bench)


def test_result_line(silo):
    out, logs = drive(*silo)
    assert list(out) == KEYS
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}
    for name in SETTLEMENT:
        assert out["checks"][name][0] == 0
    assert all(np.isfinite(v) for v, _ in out["checks"].values())
    assert set(out["checks"]) == set(SETTLEMENT)
    assert any(line.endswith("compilations in the window: 0")
               for line in logs)


def test_traced_run(silo):
    out, _ = drive(*silo, trace=True)
    assert list(out) == KEYS[:5] + ["breakdown", "checks"]
    assert set(out["metrics"]) == {"settle_ms.llm"}
