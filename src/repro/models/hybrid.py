"""zamba2-style hybrid: Mamba2 backbone + a single *shared* attention block.

The shared attention+MLP block (one parameter copy) is applied after every
``shared_attn_every``-th Mamba2 layer. Layers are grouped into scanned
"super-layers" of ``shared_attn_every`` Mamba2 layers + one shared-block
application; a remainder tail is applied unscanned.

Deviation from the released Zamba2 (noted in DESIGN.md): the shared block
consumes the hidden stream directly rather than concat(hidden, embedding),
and per-invocation LoRA deltas are omitted — compute/communication character
is preserved; parameter sharing (the paper point of the architecture) is
exact.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import ssm as S
from repro.models.sharding import shard_residual


def _split_layers(cfg: ModelConfig):
    k = cfg.shared_attn_every
    n_super = cfg.num_layers // k
    n_tail = cfg.num_layers - n_super * k
    return k, n_super, n_tail


def init_hybrid(key, cfg: ModelConfig, tp: int):
    dt = jnp.dtype(cfg.dtype)
    k, n_super, n_tail = _split_layers(cfg)
    k_emb, k_m, k_t, k_sh, k_head = jax.random.split(key, 5)

    def init_m(kk):
        p, _ = S.init_mamba2(kk, cfg.d_model, cfg.ssm, tp, dt)
        return {"mamba": p, "norm": jnp.ones((cfg.d_model,), dt)}

    _, m_specs = S.init_mamba2(k_m, cfg.d_model, cfg.ssm, tp, dt)
    m_specs = {"mamba": m_specs, "norm": P(None)}

    super_keys = jax.random.split(k_m, n_super * k)
    super_keys = super_keys.reshape(n_super, k, *super_keys.shape[1:])
    super_params = jax.vmap(jax.vmap(init_m))(super_keys)
    super_specs = jax.tree.map(lambda s: P(None, None, *s), m_specs,
                               is_leaf=lambda x: isinstance(x, P))
    tail_params = [init_m(kk) for kk in jax.random.split(k_t, n_tail)] if n_tail else []

    # shared attention + MLP block (single copy)
    ka, km = jax.random.split(k_sh)
    attn, attn_s = L.init_gqa(ka, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                              cfg.resolved_head_dim, tp, dt)
    mlp, mlp_s = L.init_swiglu(km, cfg.d_model, cfg.d_ff, tp, dt)
    shared = {"attn": attn, "mlp": mlp,
              "norm1": jnp.ones((cfg.d_model,), dt),
              "norm2": jnp.ones((cfg.d_model,), dt)}
    shared_s = {"attn": attn_s, "mlp": mlp_s, "norm1": P(None), "norm2": P(None)}

    v = L.maybe(L.shard_dim(cfg.vocab_size, tp))
    params = {"embed": L.embed_init(k_emb, (cfg.vocab_size, cfg.d_model), dt),
              "super": super_params, "tail": tail_params, "shared": shared,
              "final_norm": jnp.ones((cfg.d_model,), dt),
              "lm_head": L.dense_init(k_head, (cfg.d_model, cfg.vocab_size), cfg.d_model, dt)}
    specs = {"embed": P(v, None), "super": super_specs,
             "tail": [m_specs for _ in range(n_tail)], "shared": shared_s,
             "final_norm": P(None), "lm_head": P(None, v)}
    return params, specs


def _shared_fwd(cfg, sp, x, positions, kv_chunk, cache=None, cur_index=None,
                return_kv=False):
    h = L.rms_norm(x, sp["norm1"], cfg.norm_eps)
    if cache is not None:
        a, new_cache = L.apply_gqa(sp["attn"], h, num_heads=cfg.num_heads,
                                   num_kv_heads=cfg.num_kv_heads,
                                   head_dim=cfg.resolved_head_dim,
                                   positions=positions, rope_theta=cfg.rope_theta,
                                   cache=cache, cur_index=cur_index)
    else:
        a = L.apply_gqa(sp["attn"], h, num_heads=cfg.num_heads,
                        num_kv_heads=cfg.num_kv_heads,
                        head_dim=cfg.resolved_head_dim, positions=positions,
                        rope_theta=cfg.rope_theta, kv_chunk=kv_chunk,
                        return_kv=return_kv)
        new_cache = None
        if return_kv:
            a, new_cache = a
    x = x + a
    h = L.rms_norm(x, sp["norm2"], cfg.norm_eps)
    x = x + L.apply_swiglu(sp["mlp"], h)
    return (x, new_cache) if (cache is not None or return_kv) else x


def hybrid_forward(params, cfg: ModelConfig, tokens, *, remat: bool = False,
                   kv_chunk: int = 1024, prefill_cache_len: int = 0,
                   return_hidden: bool = False):
    k, n_super, n_tail = _split_layers(cfg)
    x = jnp.take(params["embed"], tokens, axis=0)
    Sq = x.shape[1]
    positions = jnp.arange(Sq)
    prefill = prefill_cache_len > 0
    dt = jnp.dtype(cfg.dtype)

    def mamba_step(x, lp):
        h = L.rms_norm(x, lp["norm"], cfg.norm_eps)
        if prefill:
            out, (ssm_new, (cx, cbc)) = S.apply_mamba2(lp["mamba"], h, cfg.ssm,
                                                       return_state=True)
            return x + out, {"ssm": ssm_new, "conv_x": cx, "conv_bc": cbc}
        return x + S.apply_mamba2(lp["mamba"], h, cfg.ssm), None

    def super_body(x, sl):
        x = jax.lax.optimization_barrier(x)
        states = []
        for j in range(k):
            lp = jax.tree.map(lambda a: a[j], sl)
            x, st = mamba_step(x, lp)
            states.append(st)
        x = shard_residual(x)
        if prefill:
            x, kv = _shared_fwd(cfg, params["shared"], x, positions, kv_chunk,
                                return_kv=True)
            pad = prefill_cache_len - Sq
            kv = jax.tree.map(lambda t: jnp.pad(
                t.astype(dt), ((0, 0), (0, pad), (0, 0), (0, 0))), kv)
            states = jax.tree.map(lambda *xs: jnp.stack(xs), *states)
            return x, (states, kv)
        x = _shared_fwd(cfg, params["shared"], x, positions, kv_chunk)
        return x, None

    if remat and not prefill:
        super_body = jax.checkpoint(super_body, prevent_cse=False)
    x, ys = jax.lax.scan(super_body, x, params["super"])
    tail_states = []
    for lp in params["tail"]:
        x, st = mamba_step(x, lp)
        tail_states.append(st)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if prefill:
        super_ssm, shared_kv = ys
        tail = (jax.tree.map(lambda *xs: jnp.stack(xs), *tail_states)
                if tail_states else
                jax.tree.map(lambda t: jnp.zeros((1,) + t.shape[1:], t.dtype),
                             jax.tree.map(lambda a: a[:, 0], super_ssm)))
        cache = {"super_ssm": super_ssm, "tail_ssm": tail,
                 "shared_attn": shared_kv}
        return x[:, -1:, :] @ params["lm_head"], cache
    if return_hidden:
        return x, 0.0
    return x @ params["lm_head"], 0.0


def hybrid_cache_shape(cfg: ModelConfig, batch: int, seq: int):
    k, n_super, n_tail = _split_layers(cfg)
    m = S.mamba2_state_shape(batch, cfg.d_model, cfg.ssm)
    attn = L.gqa_cache_shape(batch, seq, cfg.num_kv_heads, cfg.resolved_head_dim)
    return {
        "super_ssm": {kk: (n_super, k) + v for kk, v in m.items()},
        "tail_ssm": {kk: (max(n_tail, 1),) + v for kk, v in m.items()},
        "shared_attn": {kk: (n_super,) + v for kk, v in attn.items()},
    }


def hybrid_cache_spec(cfg: ModelConfig, tp: int, data_axes):
    m = S.mamba2_state_spec(cfg.d_model, cfg.ssm, tp, data_axes)
    a = L.gqa_cache_spec(cfg.num_kv_heads, tp, data_axes)
    return {
        "super_ssm": {kk: P(None, None, *v) for kk, v in m.items()},
        "tail_ssm": {kk: P(None, *v) for kk, v in m.items()},
        "shared_attn": {kk: P(None, *v) for kk, v in a.items()},
    }


def hybrid_decode_step(params, cfg: ModelConfig, cache, tokens, cur_index):
    k, n_super, n_tail = _split_layers(cfg)
    x = jnp.take(params["embed"], tokens, axis=0)
    positions = jnp.full((1,), cur_index)

    def super_body(x, inp):
        sl, ssm_states, attn_cache = inp
        ssm_states, attn_cache = jax.lax.optimization_barrier(
            (ssm_states, attn_cache))
        new_states = []
        for j in range(k):
            lp = jax.tree.map(lambda a: a[j], sl)
            st = jax.tree.map(lambda a: a[j], ssm_states)
            h = L.rms_norm(x, lp["norm"], cfg.norm_eps)
            out, (ssm_new, (cx, cbc)) = S.apply_mamba2(
                lp["mamba"], h, cfg.ssm,
                state=st["ssm"], conv_state=(st["conv_x"], st["conv_bc"]))
            x = x + out
            new_states.append({"ssm": ssm_new, "conv_x": cx, "conv_bc": cbc})
        new_states = jax.tree.map(lambda *xs: jnp.stack(xs), *new_states)
        x, new_attn = _shared_fwd(cfg, params["shared"], x, positions, 1024,
                                  cache=attn_cache, cur_index=cur_index)
        return x, (new_states, new_attn)

    x, (new_super_ssm, new_shared) = jax.lax.scan(
        super_body, x, (params["super"], cache["super_ssm"], cache["shared_attn"]))

    new_tail = cache["tail_ssm"]
    if n_tail:
        tails = []
        for i, lp in enumerate(params["tail"]):
            st = jax.tree.map(lambda a: a[i], cache["tail_ssm"])
            h = L.rms_norm(x, lp["norm"], cfg.norm_eps)
            out, (ssm_new, (cx, cbc)) = S.apply_mamba2(
                lp["mamba"], h, cfg.ssm,
                state=st["ssm"], conv_state=(st["conv_x"], st["conv_bc"]))
            x = x + out
            tails.append({"ssm": ssm_new, "conv_x": cx, "conv_bc": cbc})
        new_tail = jax.tree.map(lambda *xs: jnp.stack(xs), *tails)

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = x @ params["lm_head"]
    return logits, {"super_ssm": new_super_ssm, "tail_ssm": new_tail,
                    "shared_attn": new_shared}
