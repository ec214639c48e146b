"""Configurations: sizes, FLOP counts and the weights' layout against the
program's, at the published sizes (shapes only, nothing is allocated)."""
import pathlib

import jax
import pytest

from chipbench import harness

HOME = pathlib.Path(harness.__file__).resolve().parent


def _config(name):
    path = HOME / "configs" / f"{name}.json"
    return harness.load_json(path), harness.load_module(path.with_suffix(".py"))


def test_paper_net_forward_flops_by_hand():
    c, m = _config("paper-net")
    # conv1 24*24*10 outputs of 5*5*1 MACs, conv2 8*8*20 of 5*5*10,
    # fc1 320*50, fc2 50*10: 480,500 MACs, 961,000 FLOPs
    assert m.forward_flops(c) == 2 * (24 * 24 * 10 * 25 + 8 * 8 * 20 * 250
                                      + 320 * 50 + 50 * 10) == 961_000
    # backward: weight gradients of all four layers, input gradients of
    # all but conv1
    assert m.flops_per_sample(c, {}) == 3 * 961_000 - 2 * 24 * 24 * 10 * 25


@pytest.mark.parametrize("name", ["paper-net", "smollm-135m"])
def test_weights_match_program_params(name):
    from repro.configs.base import ModelConfig
    from repro.models import api
    c, m = _config(name)
    key = jax.random.PRNGKey(0)
    mine = jax.eval_shape(lambda k: m.init(c, k), key)
    cfg = ModelConfig(**m.program(c)["model"])
    theirs = jax.eval_shape(lambda k: api.init(cfg, k, tp=1)[0], key)
    assert jax.tree.map(lambda x: (x.shape, x.dtype), mine) == \
        jax.tree.map(lambda x: (x.shape, x.dtype), theirs)


def test_paper_net_dimension():
    c, m = _config("paper-net")
    shapes = jax.eval_shape(lambda k: m.init(c, k), jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 21_840


def test_smollm_param_count_against_analytic():
    from benchmarks.analytic import total_params
    from repro.configs.base import ModelConfig
    c, m = _config("smollm-135m")
    cfg = ModelConfig(**m.program(c)["model"])
    shapes = jax.eval_shape(lambda k: m.init(c, k), jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert n == m.param_count(c)
    # the analytic count leaves out the final norm's d weights
    assert n == total_params(cfg) + c["hidden_size"] == 134_515_008


def test_smollm_flops_per_token():
    c, m = _config("smollm-135m")
    matmul = 30 * (576 * 576 * 2 + 576 * 192 * 2 + 3 * 576 * 1536) \
        + 49152 * 576
    assert m.flops_per_sample(c, {"seq_len": 1024}) == \
        6 * matmul + 12 * 30 * 9 * 64 * 1024
