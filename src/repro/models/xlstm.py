"""xLSTM stack (mLSTM + sLSTM mix) — the ``ssm`` family.

Layers are grouped into scanned super-layers of ``slstm_every - 1`` mLSTM
blocks followed by one sLSTM block (the ≈7:1 mix of xLSTM-1.3b when
``slstm_every == 8``).
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
    k = cfg.slstm_every
    assert cfg.num_layers % k == 0, "xlstm stack expects num_layers % slstm_every == 0"
    return k - 1, cfg.num_layers // k      # (mlstm per super-layer, n_super)


def init_xlstm(key, cfg: ModelConfig, tp: int):
    dt = jnp.dtype(cfg.dtype)
    n_m, n_super = _split_layers(cfg)
    k_emb, k_m, k_s, k_head = jax.random.split(key, 4)

    def init_mblock(kk):
        p, _ = S.init_mlstm(kk, cfg.d_model, cfg.ssm, tp, dt)
        return {"mlstm": p, "norm": jnp.ones((cfg.d_model,), dt)}

    def init_sblock(kk):
        p, _ = S.init_slstm(kk, cfg.d_model, cfg.num_heads, tp, dt)
        return {"slstm": p, "norm": jnp.ones((cfg.d_model,), dt)}

    _, m_specs = S.init_mlstm(k_m, cfg.d_model, cfg.ssm, tp, dt)
    _, s_specs = S.init_slstm(k_s, cfg.d_model, cfg.num_heads, tp, dt)
    m_specs = {"mlstm": m_specs, "norm": P(None)}
    s_specs = {"slstm": s_specs, "norm": P(None)}

    mkeys = jax.random.split(k_m, n_super * n_m)
    mkeys = mkeys.reshape(n_super, n_m, *mkeys.shape[1:])
    skeys = jax.random.split(k_s, n_super)
    super_params = {
        "m": jax.vmap(jax.vmap(init_mblock))(mkeys),
        "s": jax.vmap(init_sblock)(skeys),
    }
    super_specs = {
        "m": jax.tree.map(lambda s: P(None, None, *s), m_specs,
                          is_leaf=lambda x: isinstance(x, P)),
        "s": jax.tree.map(lambda s: P(None, *s), s_specs,
                          is_leaf=lambda x: isinstance(x, P)),
    }
    v = L.maybe(L.shard_dim(cfg.vocab_size, tp))
    params = {"embed": L.embed_init(k_emb, (cfg.vocab_size, cfg.d_model), dt),
              "super": super_params,
              "final_norm": jnp.ones((cfg.d_model,), dt),
              "lm_head": L.dense_init(k_head, (cfg.d_model, cfg.vocab_size),
                                      cfg.d_model, dt)}
    specs = {"embed": P(v, None), "super": super_specs, "final_norm": P(None),
             "lm_head": P(None, v)}
    return params, specs


def xlstm_forward(params, cfg: ModelConfig, tokens, *, remat: bool = False,
                  prefill_cache_len: int = 0, return_hidden: bool = False,
                  **_):
    n_m, n_super = _split_layers(cfg)
    x = jnp.take(params["embed"], tokens, axis=0)
    prefill = prefill_cache_len > 0

    def super_body(x, sl):
        x = jax.lax.optimization_barrier(x)
        mstates = []
        for j in range(n_m):
            lp = jax.tree.map(lambda a: a[j], sl["m"])
            h = L.rms_norm(x, lp["norm"], cfg.norm_eps)
            if prefill:
                out, (ssm_new, conv_new) = S.apply_mlstm(
                    lp["mlstm"], h, cfg.ssm, chunk=cfg.ssm.chunk_size,
                    return_state=True)
                mstates.append({"ssm": ssm_new, "conv": conv_new})
            else:
                out = S.apply_mlstm(lp["mlstm"], h, cfg.ssm,
                                    chunk=cfg.ssm.chunk_size)
            x = x + out
        x = shard_residual(x)
        h = L.rms_norm(x, sl["s"]["norm"], cfg.norm_eps)
        if prefill:
            out, (c, n, hh, m) = S.apply_slstm(sl["s"]["slstm"], h,
                                               cfg.num_heads, return_state=True)
            x = x + out
            mstates = jax.tree.map(lambda *xs: jnp.stack(xs), *mstates)
            return x, (mstates, {"c": c, "n": n, "h": hh, "m": m})
        x = x + S.apply_slstm(sl["s"]["slstm"], h, cfg.num_heads)
        return x, None

    if remat and not prefill:
        super_body = jax.checkpoint(super_body, prevent_cse=False)
    x, ys = jax.lax.scan(super_body, x, params["super"])
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if prefill:
        return x[:, -1:, :] @ params["lm_head"], {"m": ys[0], "s": ys[1]}
    if return_hidden:
        return x, 0.0
    return x @ params["lm_head"], 0.0


def xlstm_cache_shape(cfg: ModelConfig, batch: int, seq: int):
    n_m, n_super = _split_layers(cfg)
    m = S.mlstm_state_shape(batch, cfg.d_model, cfg.ssm)
    s = S.slstm_state_shape(batch, cfg.d_model, cfg.num_heads)
    return {"m": {k: (n_super, n_m) + v for k, v in m.items()},
            "s": {k: (n_super,) + v for k, v in s.items()}}


def xlstm_cache_spec(cfg: ModelConfig, tp: int, data_axes):
    m = S.mlstm_state_spec(cfg.d_model, cfg.ssm, tp, data_axes)
    s = S.slstm_state_spec(data_axes)
    return {"m": {k: P(None, None, *v) for k, v in m.items()},
            "s": {k: P(None, *v) for k, v in s.items()}}


def xlstm_decode_step(params, cfg: ModelConfig, cache, tokens, cur_index):
    n_m, n_super = _split_layers(cfg)
    x = jnp.take(params["embed"], tokens, axis=0)          # (B,1,d)

    def super_body(x, inp):
        sl, mstate, sstate = inp
        mstate, sstate = jax.lax.optimization_barrier((mstate, sstate))
        new_m = []
        for j in range(n_m):
            lp = jax.tree.map(lambda a: a[j], sl["m"])
            st = jax.tree.map(lambda a: a[j], mstate)
            h = L.rms_norm(x, lp["norm"], cfg.norm_eps)
            out, (ssm_new, conv_new) = S.apply_mlstm(
                lp["mlstm"], h, cfg.ssm, state=st["ssm"], conv_state=st["conv"])
            x = x + out
            new_m.append({"ssm": ssm_new, "conv": conv_new})
        new_m = jax.tree.map(lambda *xs: jnp.stack(xs), *new_m)
        h = L.rms_norm(x, sl["s"]["norm"], cfg.norm_eps)
        carry = (sstate["c"], sstate["n"], sstate["h"], sstate["m"])
        out, (c, n, hh, m) = S.apply_slstm(sl["s"]["slstm"], h, cfg.num_heads,
                                           carry=carry)
        x = x + out
        return x, (new_m, {"c": c, "n": n, "h": hh, "m": m})

    x, (new_m, new_s) = jax.lax.scan(super_body, x,
                                     (params["super"], cache["m"], cache["s"]))
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x @ params["lm_head"], {"m": new_m, "s": new_s}
