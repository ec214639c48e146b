"""``chip_smoke.py``'s phases at a tiny size on CPU: their control flow
and their checks, without a chip. Compiled for the CPU the round carries
no Pallas kernel, so every phase runs with ``expect_kernel=False``."""
import pathlib
import sys

import jax
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
import chip_smoke  # noqa: E402
from repro.configs.registry import get_smoke_config  # noqa: E402

TINY = dict(clusters=2, per_cluster=2, images=8)


def test_paper_net_sync_then_kernel_vs_reference():
    line, state = chip_smoke.paper_net_sync(rounds=2, expect_kernel=False,
                                            **TINY)
    assert line["phase"] == "a_paper_net_sync" and line["W"] == 4
    assert line["tpu_custom_call"] is False and line["compile_s"] > 0
    assert len(line["round_s"]) == 1          # the first round is excluded
    assert line["blocks"] >= 2
    b = chip_smoke.kernel_vs_reference(state, expect_kernel=False)
    assert b["W"] == 4 and b["tpu_custom_call"] == {"auto": False,
                                                    "off": False}
    assert b["max_abs_diff_scores"] <= chip_smoke.SCORE_ATOL \
        + chip_smoke.SCORE_RTOL


def test_paper_net_events():
    line = chip_smoke.paper_net_events(buffer_size=2, events=4,
                                       expect_kernel=False, **TINY)
    assert line["phase"] == "c_paper_net_events" and line["events"] >= 2
    assert line["max_staleness_on_chain"] > 0


def test_smollm_rounds():
    line = chip_smoke.smollm_rounds(cfg=get_smoke_config("smollm-135m"),
                                    seq=64, batch=2)
    assert line["phase"] == "d_smollm_135m" and line["W"] == 2
    assert line["tokens_per_worker"] == 128 and len(line["losses"]) == 2


def test_kernel_check_fires_off_chip():
    """A phase told to expect the kernel fails where the round has none."""
    with pytest.raises(AssertionError, match="trust kernel"):
        chip_smoke.paper_net_sync(rounds=1, expect_kernel=True, **TINY)


def test_main_refuses_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main() == 1
    assert capsys.readouterr().out == ""
