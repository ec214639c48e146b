"""Roofline-accounting correctness: analytic param counts vs eval_shape,
the scan-undercount fact that motivates the analytic calculator, and the
HLO collective parser's trip-count attribution."""
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, ".")   # benchmarks package lives at repo root
from benchmarks import analytic
from repro.configs.registry import ARCH_IDS, INPUT_SHAPES, applicable, get_config
from repro.models import api


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_analytic_param_count_exact(arch):
    cfg = get_config(arch)
    sds = jax.eval_shape(lambda k: api.init(cfg, k, tp=16)[0],
                         jax.random.PRNGKey(0))
    actual = sum(x.size for x in jax.tree.leaves(sds))
    assert abs(actual - analytic.total_params(cfg)) / actual < 1e-4


def test_moe_active_params_less_than_total():
    for arch in ("qwen2-moe-a2.7b", "olmoe-1b-7b"):
        cfg = get_config(arch)
        assert analytic.total_params(cfg, active=True) < \
            analytic.total_params(cfg)


def test_cost_analysis_counts_scan_body_once():
    """The documented XLA behaviour that motivates analytic FLOPs."""
    def f_scan(ws, x):
        def body(x, w):
            return x @ w, None
        return jax.lax.scan(body, x, ws)[0]
    ws = jax.ShapeDtypeStruct((10, 128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    cost = jax.jit(f_scan).lower(ws, x).compile().cost_analysis()
    flops = cost["flops"]
    assert abs(flops - 2 * 128 ** 3) / (2 * 128 ** 3) < 0.01   # body, once


def test_roofline_terms_all_pairs_finite():
    for arch in ARCH_IDS:
        for shape in INPUT_SHAPES:
            if not applicable(arch, shape)[0]:
                continue
            t = analytic.roofline_terms(arch, shape)
            for k in ("compute_s", "memory_s", "collective_s"):
                assert np.isfinite(t[k]) and t[k] >= 0, (arch, shape, k)
            assert 0 < t["useful_ratio"] <= 1.5, (arch, shape)
            assert t["dominant"] in ("compute_s", "memory_s", "collective_s")


def test_decode_is_memory_bound_train_is_not():
    t_dec = analytic.roofline_terms("yi-6b", "decode_32k")
    t_train = analytic.roofline_terms("yi-6b", "train_4k")
    assert t_dec["dominant"] == "memory_s"
    assert t_train["dominant"] != "memory_s"


def test_collective_parser_trip_attribution():
    from repro.launch.dryrun import collective_bytes
    hlo = """
HloModule test

%cond.1 (p: (s32[], f32[8])) -> pred[] {
  %p = (s32[], f32[8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(30)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

%body.1 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  %x = f32[8] get-tuple-element(%p), index=1
  %ar = f32[1024] all-reduce(%big), to_apply=%add
  ROOT %t = (s32[], f32[8]) tuple(%i2, %x)
}

ENTRY %main.1 (a: f32[8]) -> f32[8] {
  %a = f32[8] parameter(0)
  %ag = f32[2048] all-gather(%a2), dimensions={0}
  %w = (s32[], f32[8]) while(%init), condition=%cond.1, body=%body.1
  ROOT %r = f32[8] get-tuple-element(%w), index=1
}
"""
    total, breakdown = collective_bytes(hlo)
    # all-gather once (2048*4B), all-reduce 30x (1024*4B*2 ring factor)
    assert breakdown["all-gather"]["count"] == 1
    assert breakdown["all-reduce"]["count"] == 30
    assert total == 2048 * 4 + 30 * 1024 * 4 * 2
