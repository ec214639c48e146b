"""The control: the reference computed in the precision below the one
the configuration states, put in the program's place, fails the cell's
check, at a size a test run holds.

paper-net states float32 at ``highest``; its control is three bfloat16
passes (``high``). XLA on a CPU computes every float32 matmul in full,
whatever precision is asked, so here the three passes are emulated on
the operands: each is rounded to a bfloat16 and a bfloat16 remainder,
which is what the passes multiply. The cohort is 64 workers of 32
images: at 4 workers of 8 the emulated passes stay under the limits."""
import pytest

from chipbench import checks, harness, traffic

TRAINING = ("loss_gap", "grad_gap", "delta_gap", "score_gap")


@pytest.mark.parametrize("name", ["papernet-sync-w1024",
                                  "papernet-async-w1024"])
def test_control_fails_the_check(tiny_bench, name):
    cell = harness.load_cell(name, tiny_bench)
    cell = cell._replace(traffic=dict(
        cell.traffic, workers_per_cluster=32, samples_per_worker=32,
        reference_workers_at_once=32, buffer_size=32))
    seed = 2**32 + 17
    rows = traffic.rounds(cell.config, cell.traffic, seed,
                          cell.traffic["warmup_rounds"])
    masks = checks.expected_masks(cell, seed, len(rows))
    init = checks.init_params(cell, seed)
    ref = checks.run_reference(cell, seed, rows, masks)
    same = checks.compare(ref, checks.run_reference(cell, seed, rows, masks),
                          init)
    assert all(v == 0 for v in same.values()), same
    kw = dict(checks.control(cell.config))
    if kw.get("precision") == "high":
        kw["quant"] = "bfloat16_3x"
    ctl = checks.run_reference(cell, seed, rows, masks, **kw)
    numbers = checks.compare(ref, ctl, init)
    limits = {k: v for k, v in cell.limits.items() if k in TRAINING}
    assert checks.judge(numbers, limits)["correct"] is False, numbers
