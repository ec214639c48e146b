"""Decoder-only transformer stack (dense / moe / vlm families).

Layout: params are nested dicts; per-layer params are *stacked* on a leading
layer dim and the stack is applied with ``lax.scan`` (keeps HLO size and
compile time flat in depth); ``jax.checkpoint`` on the scanned body gives the
activation-remat policy for training shapes. A MoE model's leading dense
layers (``first_dense_layers``) are a stack of their own, ``dense_layers``,
scanned before ``layers``.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models.layers import maybe, shard_dim
from repro.models.sharding import shard_residual


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_decoder_layer(key, cfg: ModelConfig, tp: int, *, dense: bool = False):
    """One layer; ``dense`` gives a MoE model's leading dense-MLP layer."""
    dt = _dtype(cfg)
    k_attn, k_mlp = jax.random.split(key)
    if cfg.attn_type == "mla":
        attn, attn_s = L.init_mla(k_attn, cfg.d_model, cfg.num_heads, cfg.mla, tp, dt)
    else:
        attn, attn_s = L.init_gqa(k_attn, cfg.d_model, cfg.num_heads,
                                  cfg.num_kv_heads, cfg.resolved_head_dim, tp, dt)
    params = {"attn": attn,
              "norm1": jnp.ones((cfg.d_model,), dt),
              "norm2": jnp.ones((cfg.d_model,), dt)}
    specs = {"attn": attn_s, "norm1": P(None), "norm2": P(None)}
    if cfg.moe.enabled and not dense:
        params["moe"], specs["moe"] = MOE.init_moe(k_mlp, cfg.d_model, cfg.moe, tp, dt)
    else:
        params["mlp"], specs["mlp"] = L.init_swiglu(k_mlp, cfg.d_model, cfg.d_ff, tp, dt)
    return params, specs


def _init_stack(key, cfg: ModelConfig, tp: int, n: int, dense: bool):
    keys = jax.random.split(key, n)
    stacked = jax.vmap(
        lambda k: init_decoder_layer(k, cfg, tp, dense=dense)[0])(keys)
    _, specs = init_decoder_layer(keys[0], cfg, tp, dense=dense)
    return stacked, jax.tree.map(lambda s: P(None, *s), specs,
                                 is_leaf=lambda x: isinstance(x, P))


def init_decoder(key, cfg: ModelConfig, tp: int):
    dt = _dtype(cfg)
    k_emb, k_layers, k_head = jax.random.split(key, 3)
    v = maybe(shard_dim(cfg.vocab_size, tp))
    nd = cfg.first_dense_layers
    stacked, layer_specs = _init_stack(k_layers, cfg, tp,
                                       cfg.num_layers - nd, False)
    params = {"embed": L.embed_init(k_emb, (cfg.vocab_size, cfg.d_model), dt),
              "layers": stacked,
              "final_norm": jnp.ones((cfg.d_model,), dt)}
    specs = {"embed": P(v, None), "layers": layer_specs, "final_norm": P(None)}
    if nd:
        params["dense_layers"], specs["dense_layers"] = _init_stack(
            jax.random.fold_in(k_layers, 1), cfg, tp, nd, True)
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(k_head, (cfg.d_model, cfg.vocab_size),
                                         cfg.d_model, dt)
        specs["lm_head"] = P(None, v)
    return params, specs


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def embed_tokens(params, cfg: ModelConfig, tokens, patch_embeds=None):
    """tokens: (B, S_text) int32. VLM: ``patch_embeds`` (B, P, d) prepended
    (early fusion — the stub VQ frontend's output)."""
    x = jnp.take(params["embed"], tokens, axis=0)
    if patch_embeds is not None:
        x = jnp.concatenate([patch_embeds.astype(x.dtype), x], axis=1)
    return x


def _decoder_stack(params, cfg: ModelConfig, tokens, *, patch_embeds=None,
                   remat: bool = False, kv_chunk: int = 1024,
                   prefill_cache_len: int = 0, lora=None):
    """The layers over the embedded tokens: (final hidden (B, S, d), aux
    loss, counters, per-layer K/V of ``layers`` in prefill mode).
    ``lora``: adapters mirroring ``params``' stacks, with ``params`` as the
    frozen base. Counters are the held expert layers' (``moe.held``)."""
    x = embed_tokens(params, cfg, tokens, patch_embeds)
    S = x.shape[1]
    positions = jnp.arange(S)
    prefill = prefill_cache_len > 0
    if prefill and "dense_layers" in params:
        raise NotImplementedError("prefill of leading dense layers")
    lora_scale = cfg.lora.scale if lora is not None else 1.0

    def make_body(moe: bool):
        def body(carry, inp):
            x, aux, cnt = carry
            lp, ad = inp
            # barrier: stops XLA hoisting convert(whole checkpoint stack) out
            # of the backward loop (an f32 copy of all saved residuals)
            x = jax.lax.optimization_barrier(x)
            h = L.rms_norm(x, lp["norm1"], cfg.norm_eps)
            kv = None
            if cfg.attn_type == "mla":
                with jax.named_scope("mla"):
                    a = L.apply_mla(lp["attn"], h, num_heads=cfg.num_heads,
                                    mla=cfg.mla, positions=positions,
                                    rope_theta=cfg.rope_theta,
                                    kv_chunk=kv_chunk, return_kv=prefill,
                                    lora=ad, lora_scale=lora_scale)
            else:
                a = L.apply_gqa(lp["attn"], h, num_heads=cfg.num_heads,
                                num_kv_heads=cfg.num_kv_heads,
                                head_dim=cfg.resolved_head_dim,
                                positions=positions, rope_theta=cfg.rope_theta,
                                window=cfg.window if cfg.attn_type == "swa" else 0,
                                kv_chunk=kv_chunk, return_kv=prefill)
            if prefill:
                a, kv = a
                pad = prefill_cache_len - S
                kv = jax.tree.map(
                    lambda t: jnp.pad(t.astype(jnp.dtype(cfg.dtype)),
                                      ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)),
                    kv)
            x = x + a
            h = L.rms_norm(x, lp["norm2"], cfg.norm_eps)
            if moe and cfg.moe.held:
                m, c = MOE.apply_moe_held(lp["moe"], h, cfg.moe)
                cnt, aux_l = MOE.merge_counters(cnt, c), 0.0
            elif moe:
                m, aux_l = MOE.apply_moe(lp["moe"], h, cfg.moe)
            else:
                m, aux_l = L.apply_swiglu(lp["mlp"], h), 0.0
            return (shard_residual(x + m), aux + aux_l, cnt), kv

        if remat:
            return jax.checkpoint(body, prevent_cse=False)
        return body

    def adapters(name):
        return None if lora is None else lora[name]

    carry = (x, 0.0, MOE.zero_counters() if cfg.moe.held else {})
    if "dense_layers" in params:
        carry, _ = jax.lax.scan(make_body(False), carry,
                                (params["dense_layers"],
                                 adapters("dense_layers")))
    (x, aux, cnt), cache = jax.lax.scan(
        make_body(cfg.moe.enabled), carry,
        (params["layers"], adapters("layers")))
    return L.rms_norm(x, params["final_norm"], cfg.norm_eps), aux, cnt, cache


def decoder_hidden(params, cfg: ModelConfig, tokens, *, patch_embeds=None,
                   remat: bool = False, kv_chunk: int = 1024, lora=None):
    """Training forward: (final hidden (B, S, d), aux loss, counters)."""
    x, aux, cnt, _ = _decoder_stack(params, cfg, tokens,
                                    patch_embeds=patch_embeds, remat=remat,
                                    kv_chunk=kv_chunk, lora=lora)
    return x, aux, cnt


def decoder_forward(params, cfg: ModelConfig, tokens, *, patch_embeds=None,
                    remat: bool = False, kv_chunk: int = 1024,
                    prefill_cache_len: int = 0):
    """Returns (logits (B, S, V), aux_loss); in prefill mode
    (``prefill_cache_len > 0``) returns (last_logits (B, 1, V), cache) — the
    per-layer K/V emitted from the scan, zero-padded to the cache length."""
    x, aux, _, cache = _decoder_stack(
        params, cfg, tokens, patch_embeds=patch_embeds, remat=remat,
        kv_chunk=kv_chunk, prefill_cache_len=prefill_cache_len)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    if prefill_cache_len > 0:
        return x[:, -1:, :] @ head, cache
    return x @ head, aux


# ---------------------------------------------------------------------------
# decode (single-token serve step with stacked per-layer KV cache)
# ---------------------------------------------------------------------------

def decoder_cache_shape(cfg: ModelConfig, batch: int, seq: int):
    if cfg.attn_type == "mla":
        per = L.mla_cache_shape(batch, seq, cfg.mla)
    else:
        per = L.gqa_cache_shape(batch, seq, cfg.num_kv_heads, cfg.resolved_head_dim)
    return {k: (cfg.num_layers,) + v for k, v in per.items()}


def decoder_cache_spec(cfg: ModelConfig, tp: int, data_axes):
    if cfg.attn_type == "mla":
        per = L.mla_cache_spec(data_axes, tp)
    else:
        per = L.gqa_cache_spec(cfg.num_kv_heads, tp, data_axes)
    return {k: P(None, *v) for k, v in per.items()}


def decoder_decode_step(params, cfg: ModelConfig, cache, tokens, cur_index):
    """tokens: (B, 1) — one new token per sequence. Returns (logits, cache)."""
    x = jnp.take(params["embed"], tokens, axis=0)           # (B,1,d)
    positions = jnp.full((1,), cur_index)

    def body(x, inp):
        lp, layer_cache = inp
        # barrier: keep per-layer cache converts inside the loop (XLA would
        # otherwise hoist an f32 copy of the whole stacked cache out)
        layer_cache = jax.lax.optimization_barrier(layer_cache)
        h = L.rms_norm(x, lp["norm1"], cfg.norm_eps)
        if cfg.attn_type == "mla":
            a, new_cache = L.apply_mla(
                lp["attn"], h, num_heads=cfg.num_heads, mla=cfg.mla,
                positions=positions, rope_theta=cfg.rope_theta,
                cache=layer_cache, cur_index=cur_index)
        else:
            a, new_cache = L.apply_gqa(
                lp["attn"], h, num_heads=cfg.num_heads,
                num_kv_heads=cfg.num_kv_heads, head_dim=cfg.resolved_head_dim,
                positions=positions, rope_theta=cfg.rope_theta,
                window=cfg.window if cfg.attn_type == "swa" else 0,
                cache=layer_cache, cur_index=cur_index)
        x = x + a
        h = L.rms_norm(x, lp["norm2"], cfg.norm_eps)
        if cfg.moe.enabled:
            m, _ = MOE.apply_moe(lp["moe"], h, cfg.moe,
                                 capacity_factor=2 * cfg.moe.capacity_factor)
        else:
            m = L.apply_swiglu(lp["mlp"], h)
        return x + m, new_cache

    x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return x @ head, new_cache
