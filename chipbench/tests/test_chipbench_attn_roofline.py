"""attn_roofline.moonlight on a synthetic trace whose intervals are known:
the formula's value where the attention kernels ran, and silence where
they did not (the blocked scan, or no trace)."""
import pytest

from chipbench import harness, tracing
from chipbench.tracing import Event as E, Trace

CELL = "moonlight-silo-w4-s8k"
NAME = "attn_roofline.moonlight"
# window [0, 10] s; the round program runs [1, 9]; the attention kernels
# 0.4 + 0.3 + 0.5 = 1.2 s of it, the rest other operations
OPS = [E("%fusion.3 = bf16[8]{0} fusion()", 1, 2),
       E("%splash_mha_fwd_residuals.1 = (bf16[16,8192,128]) custom-call()",
         2, 2.4),
       E("%splash_mha_dq_no_residuals.1 = bf16[16,8192,192] custom-call()",
         3, 3.3),
       E("%splash_mha_dkv_no_residuals.1 = (bf16[16,8192,192]) "
         "custom-call()", 4, 4.5),
       E("%ragged-dot-none = bf16[96,64]{1,0} custom-call()", 5, 5.25),
       E("%while.1099 = (s32[], bf16[4,1,8192,16,192]) while()", 6, 8)]
MODULES = [E("jit_fl_round(9)", 1, 9)]
TR = Trace({0: OPS}, {0: MODULES}, [E(tracing.ROUND_SPAN, 0, 10)],
           (0.0, 10.0))


class _Rec:
    settled, participation = True, None


def _read(trace, rounds=2):
    cell = harness.load_cell(CELL)
    recs = [_Rec() for _ in range(rounds)]
    run = harness.Run(
        cell=cell, records=recs, trace=trace, traced_rounds=recs,
        peaks={"bf16_flops_per_s": 1e15, "hbm_bytes_per_s": 1e12},
        flops_per_sample=1000, samples_per_worker=8192,
        update_bytes=0, dim=0)
    return harness.read_metric(cell, NAME, run)


def test_attn_roofline_on_known_intervals():
    # 2 rounds x 4 silos x 8,192 tokens; a token needs, in each of 5
    # layers, 4 passes (forward, backward as two, the w_loss forward) of
    # 2 * 4,096 keys * 16 heads * (128 + 64 + 128) FLOPs; over 1.2 s at 1e15
    flops = 2 * 4 * 8192 * 4 * 5 * 2 * 4096 * 16 * (128 + 64 + 128)
    assert _read(TR) == pytest.approx(100 * flops / (1.2 * 1e15))


def test_attn_roofline_silent_without_the_kernels():
    assert _read(None) is None
    assert _read(Trace({0: []}, {0: []}, [], (0.0, 10.0))) is None
    blocked = [e for e in OPS if "splash" not in e.name]
    assert _read(Trace({0: blocked}, {0: MODULES}, TR.host, TR.window)) \
        is None
