"""Adapter-only federation (``ModelConfig.lora``): the trained leaves are
rank-r LoRA adapters on the attention projections named in
``lora.targets``; the rest of the model is a frozen base that every worker
shares.

Adapters mirror the decoder's layer stacks, one {"a": (L, in, r),
"b": (L, r, out)} pair per target, in f32: A is PEFT's kaiming-uniform
(bound 1/sqrt(in)), B is zero, so the adapted model starts as the base.

The frozen base stands in for a checkpoint that every silo loads: it is
drawn from ``lora.base_seed`` alone, leaf by leaf, so anyone can rebuild it
bit for bit. The leaf at path ``p`` (its dict keys joined by "/", e.g.
``layers/attn/wq``) is drawn from
``fold_in(PRNGKey(base_seed), crc32(p) & 0x7FFFFFFF)``: norms are ones, ``embed`` is
normal * 0.02, ``router_bias`` normal * 0.1 (f32), and every other weight
normal * fan_in ** -0.5 (fan_in its next-to-last dim), in f32 and then
cast to the leaf's dtype. The normal draw is fenced off from its scaling
(``optimization_barrier``), so that no compiler folds the two into one
multiply and every program that draws the base gets the same bits.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import transformer as TF
from repro.models.layers import MLA_TARGETS

STACKS = ("dense_layers", "layers")


def _path(path) -> str:
    return "/".join(str(k.key) for k in path)


def draw(path: str, shape, dtype, base_seed: int):
    """The frozen base's leaf at ``path`` (the module docstring's scheme)."""
    key = jax.random.fold_in(jax.random.PRNGKey(base_seed),
                             zlib.crc32(path.encode()) & 0x7FFFFFFF)
    name = path.rsplit("/", 1)[-1]
    if "norm" in name:
        return jnp.ones(shape, dtype)
    if name in ("embed", "router_bias"):
        std = 0.02 if name == "embed" else 0.1
    else:
        std = shape[-2] ** -0.5
    z = jax.lax.optimization_barrier(jax.random.normal(key, shape,
                                                       jnp.float32))
    return (z * std).astype(dtype)


def init_frozen(cfg: ModelConfig):
    """The frozen base of a LoRA configuration, from ``lora.base_seed``."""
    shapes = jax.eval_shape(lambda k: TF.init_decoder(k, cfg, 1)[0],
                            jax.random.PRNGKey(0))
    return jax.tree_util.tree_map_with_path(
        lambda p, s: draw(_path(p), s.shape, s.dtype, cfg.lora.base_seed),
        shapes)


def init_adapters(key, cfg: ModelConfig):
    """(adapters, specs) for every target projection of every layer."""
    base = jax.eval_shape(lambda k: TF.init_decoder(k, cfg, 1)[0],
                          jax.random.PRNGKey(0))
    by_name = {v: k for k, v in MLA_TARGETS.items()}
    r = cfg.lora.rank
    params, specs = {}, {}
    for stack in STACKS:
        if stack not in base:
            continue
        params[stack], specs[stack] = {}, {}
        for t in cfg.lora.targets:
            L, fan_in, fan_out = base[stack]["attn"][by_name[t]].shape
            key, sub = jax.random.split(key)
            bound = fan_in ** -0.5
            params[stack][t] = {
                "a": jax.random.uniform(sub, (L, fan_in, r), jnp.float32,
                                        -bound, bound),
                "b": jnp.zeros((L, r, fan_out), jnp.float32)}
            specs[stack][t] = {"a": P(None, None, None),
                               "b": P(None, None, None)}
    return params, specs
