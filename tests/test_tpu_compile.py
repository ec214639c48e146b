"""Compile the trust kernels, and the paper-net round that picks them, for a
described TPU v5e at the paper CNN's packed width — what interpret mode
cannot show: VMEM overflows, unaligned tiles, kernels the TPU compiler
refuses. Nothing runs; only the TPU compiler is needed, no chip.

The topology is described inside a fixture, never at import, so every
pytest worker collects the same tests and only the worker that runs this
file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import FederationConfig, TrainConfig
from repro.configs.registry import get_config
from repro.core import fl_step
from repro.kernels import fused_round, pack
from repro.kernels.trust_agg import trust_agg
from repro.kernels.trust_score import trust_score_stats
from repro.models import api

D_PAPER = 21_840      # packed width of the paper CNN (configs/paper_net.py)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_paper_net_pack_width():
    params = jax.eval_shape(lambda k: api.init(get_config("paper-net"), k,
                                               tp=1)[0],
                            jax.random.PRNGKey(0))
    assert pack.pack_spec(params).total == D_PAPER


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("W", [3, 256, 1024, 10240, 20480])
def test_trust_score_stats_compiles(one_chip, W, dtype):
    text = _compiled_text(lambda u: trust_score_stats(u),
                          _sds((W, D_PAPER), dtype, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("W", [3, 256, 1024, 10240, 20480])
def test_trust_agg_compiles(one_chip, W, dtype):
    text = _compiled_text(lambda u, w: trust_agg(u, w),
                          _sds((W, D_PAPER), dtype, one_chip),
                          _sds((W,), jnp.float32, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("W", [3, 256, 1024, 10240])
def test_fused_async_agg_kernel_compiles(one_chip, W):
    text = _compiled_text(
        lambda u, p, w, k: fused_round.fused_async_agg_kernel(u, p, w, k),
        _sds((W, D_PAPER), jnp.float32, one_chip),
        _sds(fused_round.pending_shape(W, D_PAPER), jnp.float32, one_chip),
        _sds((W,), jnp.float32, one_chip),
        _sds((W,), jnp.float32, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("async_mode", [False, True],
                         ids=["sync", "async"])
def test_paper_net_round_takes_kernels(one_chip, async_mode):
    """The default (``fused_trust_path="auto"``) paper-net round, compiled
    for the chip, carries the trust kernels; compiled for the CPU it
    carries none (the flat-jnp reference takes their place)."""
    W = 8
    cfg = get_config("paper-net")
    fed = FederationConfig(num_clusters=2, workers_per_cluster=4,
                           async_mode=async_mode)
    tc = TrainConfig()
    gp = jax.eval_shape(lambda k: api.init(cfg, k, tp=1)[0],
                        jax.random.PRNGKey(0))
    opt = jax.eval_shape(lambda p: fl_step.init_worker_opt(p, fed, tc), gp)
    batch = {"images": jax.ShapeDtypeStruct((W, 1, 4, 28, 28, 1),
                                            jnp.float32),
             "labels": jax.ShapeDtypeStruct((W, 1, 4), jnp.int32)}
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    args = [gp, opt, batch, rng]
    if async_mode:
        args += [jax.ShapeDtypeStruct((W,), jnp.float32),
                 jax.eval_shape(lambda p: fl_step.init_async_state_for(
                     cfg, fed, p, W), gp)]
    fn = fl_step.make_fl_round(cfg, fed, tc)
    on_chip = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), args)
    assert "tpu_custom_call" in _compiled_text(fn, *on_chip)
    assert "tpu_custom_call" not in _compiled_text(fn, *args)


def test_held_expert_layer_compiles(one_chip):
    """moonlight-16b-a3b's held expert share (8 of 64 experts, top-6, four
    silos of 8,192 tokens), forward and input gradient as the round takes
    them: the grouped products compile to the TPU's ragged-dot kernels,
    whose time ``moe_roofline.moonlight`` reads by that name."""
    import dataclasses
    from repro.configs.registry import get_config
    from repro.models import moe as MOE
    cfg = get_config("moonlight-16b-a3b")
    moe_cfg = dataclasses.replace(cfg.moe, experts_held=(0, 8))
    p = jax.eval_shape(lambda k: MOE.init_moe(k, cfg.d_model, moe_cfg, 1,
                                              jnp.bfloat16)[0],
                       jax.random.PRNGKey(0))

    def step(params, x):
        def loss(x):
            out, c = MOE.apply_moe_held(params, x, moe_cfg)
            return jnp.sum(out.astype(jnp.float32)), c
        return jax.vmap(jax.grad(loss, has_aux=True))(x)
    text = _compiled_text(
        step, jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), p),
        _sds((4, 1, 8192, cfg.d_model), jnp.bfloat16, one_chip))
    assert "ragged-dot" in text and "tpu_custom_call" in text
