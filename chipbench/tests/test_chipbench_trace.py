"""Trace reduction and the per-layer readers on a synthetic trace whose
intervals are known."""
import pathlib

import pytest

from chipbench import harness, tracing
from chipbench.tracing import Event, Trace

HOME = pathlib.Path(harness.__file__).resolve().parent
E = Event

# window [0, 10] s; device ops cover [1, 3] ∪ [2, 4] ∪ [6, 7]: busy 4 s
OPS = [E("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 1, 3),
       E("%trust_agg.1 = f32[1,8]{1,0} custom-call(f32[8,8]{1,0} %u)", 2, 4),
       E("%fused_async_agg_kernel.3 = (f32[1,8]) custom-call()", 6, 7),
       E("%copy.2 = f32[8]{0} copy(f32[8]{0} %p)", 9.5, 11)]  # half outside
MODULES = [E("jit_fl_round(123)", 1, 4), E("jit_fl_round(123)", 6, 7),
           E("jit_broadcast_in_dim", 9.5, 10)]
HOST = [E(tracing.ROUND_SPAN, 0, 5), E("PjitFunction(fl_round)", 0, 0.5),
        E(tracing.ROUND_SPAN, 5, 10)]
TR = Trace({0: OPS}, {0: MODULES}, HOST, (0.0, 10.0))


def test_union_busy_idle():
    assert tracing.union(OPS, 0, 10) == pytest.approx(4.5)
    assert TR.busy_s() == pytest.approx(4.5)
    assert TR.window_s() == 10
    assert TR.idle_share() == pytest.approx(0.55)
    assert tracing.idle_gaps(OPS, 0, 10) == [(0, 1), (4, 6), (7, 9.5)]


def test_module_and_op_time():
    assert TR.module_s(tracing.ROUND_PROGRAM) == pytest.approx(4.0)
    assert TR.op_s(tracing.TRUST_KERNELS) == pytest.approx(3.0)


def test_devices_are_averaged():
    two = Trace({0: OPS, 1: [E("x", 0, 10)]}, {}, HOST, (0.0, 10.0))
    assert two.busy_s() == pytest.approx((4.5 + 10) / 2)


def test_breakdown():
    b = TR.breakdown()
    assert b["device_ops"][0] == ["%fusion.1 = f32[8] fusion(f32[8] %p)",
                                  2.0]
    assert len(b["device_ops"]) == 4
    assert b["idle_gaps"][0] == ["host: " + tracing.ROUND_SPAN, 2.5]
    assert all(len(v) <= 10 for v in b.values())


class _Rec:
    def __init__(self, settle=0.01, part=None):
        self.settle_time, self.settled, self.participation = settle, True, \
            part


def _run(name, trace=TR, recs=None, traffic=None):
    cell = harness.load_cell(name)
    if traffic:
        cell = cell._replace(traffic=dict(cell.traffic, **traffic))
    recs = recs or [_Rec(0.01), _Rec(0.03)]
    return cell, harness.Run(
        cell=cell, records=recs, trace=trace, traced_rounds=recs,
        peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
        flops_per_sample=1000, samples_per_worker=10,
        update_bytes=4_000_000, dim=1000)


def test_readers():
    cell, run = _run("papernet-sync-w1024")
    W = 1024
    got = {m["name"]: harness.read_metric(cell, m["name"], run)
           for m in cell.per_layer}
    assert got["device_idle.cnn"] == pytest.approx(55.0)
    # 2 rounds x W x 10 samples x 1000 FLOPs over 4 s at 1e12
    assert got["step_mfu.cnn"] == pytest.approx(
        100 * 2 * W * 10 * 1000 / (4.0 * 1e12))
    # 2 rounds x (4 MB + 4 kB) over 1 GB/s, over 3 s of kernels
    assert got["trust_roofline"] == pytest.approx(
        100 * 2 * 4_004_000 / 1e9 / 3.0)
    assert got["settle_ms.cnn"] == pytest.approx(20.0)


def test_async_mfu_counts_the_cohort_only():
    import numpy as np
    part = np.zeros(1024)
    part[:256] = 1
    cell, run = _run("papernet-async-w1024",
                     recs=[_Rec(part=part), _Rec(part=part)])
    v = harness.read_metric(cell, "step_mfu.cnn", run)
    assert v == pytest.approx(100 * 2 * 256 * 10 * 1000 / (4.0 * 1e12))


def test_readers_are_silent_without_their_events():
    empty = Trace({0: []}, {0: []}, HOST, (0.0, 10.0))
    cell, run = _run("papernet-sync-w1024", trace=empty)
    assert harness.read_metric(cell, "step_mfu.cnn", run) is None
    assert harness.read_metric(cell, "trust_roofline", run) is None
    cell, run = _run("papernet-sync-w1024", trace=None)
    for m in ("device_idle.cnn", "step_mfu.cnn", "trust_roofline"):
        assert harness.read_metric(cell, m, run) is None
