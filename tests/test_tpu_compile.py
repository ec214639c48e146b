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


@pytest.mark.parametrize("arch,silos,S", [
    ("moonlight-16b-a3b", 4, 8192), ("minicpm3-4b", 1, 4096)])
def test_mla_training_attention_compiles(one_chip, arch, silos, S):
    """MLA's training attention, forward and gradient through
    ``apply_mla``, at the moonlight cell's shapes (four silos, 8,192
    tokens, 16 heads, q and k heads of 192, v of 128) and at minicpm3-4b's
    heads (40 heads, q and k 96, v 64, 4,096 tokens): the causal splash
    kernels, forward, dq and dk/dv, compile for the chip (VMEM and
    tiling), and ``attn_roofline.moonlight`` reads their time by these
    names."""
    from repro.models import layers as L
    cfg = get_config(arch)
    p = jax.eval_shape(lambda k: L.init_mla(k, cfg.d_model, cfg.num_heads,
                                            cfg.mla, 1, jnp.bfloat16)[0],
                       jax.random.PRNGKey(0))

    def step(params, x):
        def loss(x):
            o = L.apply_mla(params, x, num_heads=cfg.num_heads, mla=cfg.mla,
                            positions=jnp.arange(S),
                            rope_theta=cfg.rope_theta, kv_chunk=512)
            return jnp.sum(o.astype(jnp.float32))
        return jax.vmap(jax.grad(loss))(x)
    text = _compiled_text(
        step, jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), p),
        _sds((silos, 1, S, cfg.d_model), jnp.bfloat16, one_chip))
    for kernel in ("splash_mha_fwd", "splash_mha_dq", "splash_mha_dkv"):
        assert f"%{kernel}" in text


@pytest.mark.parametrize("act", [None, "seq"], ids=["heads", "heads-seq"])
def test_mla_training_attention_compiles_partitioned(topo, act):
    """The same attention jitted over a described four-chip mesh, heads
    sharded over ``model`` as ``launch.specs`` shards them (and, in the
    second case, the residual stream sharded over the sequence as the MLA
    prefill policy does), under the launch layer's
    ``activation_sharding``: it compiles, on the blocked scan, since the
    partitioner cannot split the splash kernel."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.models import layers as L
    from repro.models.sharding import activation_sharding
    cfg = get_config("moonlight-16b-a3b")
    S, tp = 2048, 4
    mesh = Mesh(np.array(topo.devices).reshape(1, tp), ("data", "model"))
    specs = {}

    def init(k):
        params, spec = L.init_mla(k, cfg.d_model, cfg.num_heads, cfg.mla, tp,
                                  jnp.bfloat16)
        specs.update(spec)
        return params
    p = jax.eval_shape(init, jax.random.PRNGKey(0))
    sh = lambda spec: NamedSharding(mesh, spec)
    residual = sh(P(None, "model", None)) if act else None

    def step(params, x):
        def loss(x):
            o = L.apply_mla(params, x, num_heads=cfg.num_heads, mla=cfg.mla,
                            positions=jnp.arange(S),
                            rope_theta=cfg.rope_theta, kv_chunk=512)
            return jnp.sum(o.astype(jnp.float32))
        with activation_sharding(residual, mesh):
            return jax.grad(loss)(x)
    params = jax.tree.map(lambda s, spec: _sds(s.shape, s.dtype, sh(spec)),
                          p, specs, is_leaf=lambda x: isinstance(x, P))
    x = _sds((1, S, cfg.d_model), jnp.bfloat16, sh(P()))
    text = jax.jit(step).lower(params, x).compile().as_text()
    assert "splash_mha" not in text
