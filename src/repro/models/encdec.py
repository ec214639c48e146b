"""whisper-base encoder-decoder (audio family).

The mel-spectrogram + conv feature extractor is the spec'd stub: the model
consumes precomputed frame embeddings ``frames: (B, encoder_seq, d_model)``
(what the conv frontend would emit). Encoder and decoder transformers are
real (pre-LN, GELU MLPs, learned-sinusoidal positions approximated with
RoPE=0 + learned pos embeddings, per whisper's layout).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.sharding import shard_residual


def _init_block(key, cfg: ModelConfig, tp, dt, cross: bool):
    ks = jax.random.split(key, 3)
    attn, attn_s = L.init_gqa(ks[0], cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
                              cfg.resolved_head_dim, tp, dt)
    mlp, mlp_s = L.init_gelu_mlp(ks[1], cfg.d_model, cfg.d_ff, tp, dt)
    p = {"attn": attn, "mlp": mlp,
         "ln1": {"w": jnp.ones((cfg.d_model,), dt), "b": jnp.zeros((cfg.d_model,), dt)},
         "ln2": {"w": jnp.ones((cfg.d_model,), dt), "b": jnp.zeros((cfg.d_model,), dt)}}
    s = {"attn": attn_s, "mlp": mlp_s,
         "ln1": {"w": P(None), "b": P(None)}, "ln2": {"w": P(None), "b": P(None)}}
    if cross:
        xattn, xattn_s = L.init_gqa(ks[2], cfg.d_model, cfg.num_heads,
                                    cfg.num_kv_heads, cfg.resolved_head_dim, tp, dt)
        p["xattn"] = xattn
        p["ln_x"] = {"w": jnp.ones((cfg.d_model,), dt), "b": jnp.zeros((cfg.d_model,), dt)}
        s["xattn"] = xattn_s
        s["ln_x"] = {"w": P(None), "b": P(None)}
    return p, s


def init_encdec(key, cfg: ModelConfig, tp: int):
    dt = jnp.dtype(cfg.dtype)
    k_emb, k_pos, k_enc, k_dec, k_head = jax.random.split(key, 5)
    v = L.maybe(L.shard_dim(cfg.vocab_size, tp))

    enc_keys = jax.random.split(k_enc, cfg.encoder_layers)
    dec_keys = jax.random.split(k_dec, cfg.num_layers)
    enc = jax.vmap(lambda k: _init_block(k, cfg, tp, dt, cross=False)[0])(enc_keys)
    dec = jax.vmap(lambda k: _init_block(k, cfg, tp, dt, cross=True)[0])(dec_keys)
    _, enc_s = _init_block(enc_keys[0], cfg, tp, dt, cross=False)
    _, dec_s = _init_block(dec_keys[0], cfg, tp, dt, cross=True)
    lift = lambda t: jax.tree.map(lambda s: P(None, *s), t,
                                  is_leaf=lambda x: isinstance(x, P))
    params = {
        "embed": L.embed_init(k_emb, (cfg.vocab_size, cfg.d_model), dt),
        "enc_pos": L.embed_init(k_pos, (cfg.encoder_seq, cfg.d_model), dt),
        "enc": enc, "dec": dec,
        "enc_norm": {"w": jnp.ones((cfg.d_model,), dt), "b": jnp.zeros((cfg.d_model,), dt)},
        "dec_norm": {"w": jnp.ones((cfg.d_model,), dt), "b": jnp.zeros((cfg.d_model,), dt)},
    }
    specs = {
        "embed": P(v, None), "enc_pos": P(None, None),
        "enc": lift(enc_s), "dec": lift(dec_s),
        "enc_norm": {"w": P(None), "b": P(None)},
        "dec_norm": {"w": P(None), "b": P(None)},
    }
    return params, specs


def _ln(x, p, eps):
    return L.layer_norm(x, p["w"], p["b"], eps)


def encode(params, cfg: ModelConfig, frames, *, remat: bool = False):
    """frames: (B, encoder_seq, d) stub-frontend embeddings -> encoder states."""
    x = frames.astype(jnp.dtype(cfg.dtype)) + params["enc_pos"][None]
    positions = jnp.arange(x.shape[1])

    def body(x, lp):
        x = jax.lax.optimization_barrier(x)
        h = _ln(x, lp["ln1"], cfg.norm_eps)
        x = x + L.apply_gqa(lp["attn"], h, num_heads=cfg.num_heads,
                            num_kv_heads=cfg.num_kv_heads,
                            head_dim=cfg.resolved_head_dim, positions=positions,
                            rope_theta=cfg.rope_theta, causal=False)
        h = _ln(x, lp["ln2"], cfg.norm_eps)
        return shard_residual(x + L.apply_gelu_mlp(lp["mlp"], h)), None

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, _ = jax.lax.scan(body, x, params["enc"])
    return _ln(x, params["enc_norm"], cfg.norm_eps)


def decode_train(params, cfg: ModelConfig, tokens, enc_states, *,
                 remat: bool = False, kv_chunk: int = 1024,
                 prefill_cache_len: int = 0, return_hidden: bool = False):
    """Teacher-forced decoder over full target sequence; in prefill mode
    also emits per-layer self-attn K/V (padded) and cross-attn K/V."""
    x = jnp.take(params["embed"], tokens, axis=0)
    Sq = x.shape[1]
    positions = jnp.arange(Sq)
    prefill = prefill_cache_len > 0
    dt = jnp.dtype(cfg.dtype)

    def body(x, lp):
        x = jax.lax.optimization_barrier(x)
        h = _ln(x, lp["ln1"], cfg.norm_eps)
        a = L.apply_gqa(lp["attn"], h, num_heads=cfg.num_heads,
                        num_kv_heads=cfg.num_kv_heads,
                        head_dim=cfg.resolved_head_dim, positions=positions,
                        rope_theta=cfg.rope_theta, kv_chunk=kv_chunk,
                        return_kv=prefill)
        self_kv = None
        if prefill:
            a, self_kv = a
            pad = prefill_cache_len - Sq
            self_kv = jax.tree.map(lambda t: jnp.pad(
                t.astype(dt), ((0, 0), (0, pad), (0, 0), (0, 0))), self_kv)
        x = x + a
        h = _ln(x, lp["ln_x"], cfg.norm_eps)
        a = L.apply_gqa(lp["xattn"], h, num_heads=cfg.num_heads,
                        num_kv_heads=cfg.num_kv_heads,
                        head_dim=cfg.resolved_head_dim, positions=positions,
                        rope_theta=cfg.rope_theta, cross_kv=enc_states,
                        return_kv=prefill)
        cross_kv = None
        if prefill:
            a, cross_kv = a
            cross_kv = jax.tree.map(lambda t: t.astype(dt), cross_kv)
        x = x + a
        h = _ln(x, lp["ln2"], cfg.norm_eps)
        x = shard_residual(x + L.apply_gelu_mlp(lp["mlp"], h))
        return x, ((self_kv, cross_kv) if prefill else None)

    if remat and not prefill:
        body = jax.checkpoint(body, prevent_cse=False)
    x, ys = jax.lax.scan(body, x, params["dec"])
    x = _ln(x, params["dec_norm"], cfg.norm_eps)
    if prefill:
        return x[:, -1:, :] @ params["embed"].T, {"self": ys[0],
                                                  "cross_kv": ys[1]}
    if return_hidden:
        return x, 0.0
    return x @ params["embed"].T, 0.0     # whisper ties output head


def encdec_forward(params, cfg: ModelConfig, tokens, *, frames,
                   remat: bool = False, kv_chunk: int = 1024,
                   prefill_cache_len: int = 0, return_hidden: bool = False):
    enc_states = encode(params, cfg, frames, remat=remat)
    return decode_train(params, cfg, tokens, enc_states, remat=remat,
                        kv_chunk=kv_chunk, prefill_cache_len=prefill_cache_len,
                        return_hidden=return_hidden)


def encdec_cache_shape(cfg: ModelConfig, batch: int, seq: int):
    per = L.gqa_cache_shape(batch, seq, cfg.num_kv_heads, cfg.resolved_head_dim)
    cross = L.gqa_cache_shape(batch, cfg.encoder_seq, cfg.num_kv_heads,
                              cfg.resolved_head_dim)
    return {"self": {k: (cfg.num_layers,) + v for k, v in per.items()},
            "cross_kv": {k: (cfg.num_layers,) + v for k, v in cross.items()}}


def encdec_cache_spec(cfg: ModelConfig, tp: int, data_axes):
    per = L.gqa_cache_spec(cfg.num_kv_heads, tp, data_axes)
    # cross K/V spans encoder_seq (1500) — not TP-divisible: batch-shard only
    h = L.maybe(L.shard_dim(cfg.num_kv_heads, tp))
    cross = {k: P(data_axes, None, h, None) for k in ("k", "v")}
    return {"self": {k: P(None, *v) for k, v in per.items()},
            "cross_kv": {k: P(None, *v) for k, v in cross.items()}}


def encdec_decode_step(params, cfg: ModelConfig, cache, tokens, cur_index):
    """Single-token decode: self-attn against cache + cross-attn against the
    prefill-computed per-layer cross K/V."""
    x = jnp.take(params["embed"], tokens, axis=0)
    positions = jnp.full((1,), cur_index)

    def body(x, inp):
        lp, self_c, cross_c = inp
        self_c, cross_c = jax.lax.optimization_barrier((self_c, cross_c))
        h = _ln(x, lp["ln1"], cfg.norm_eps)
        a, new_self = L.apply_gqa(lp["attn"], h, num_heads=cfg.num_heads,
                                  num_kv_heads=cfg.num_kv_heads,
                                  head_dim=cfg.resolved_head_dim,
                                  positions=positions, rope_theta=cfg.rope_theta,
                                  cache=self_c, cur_index=cur_index)
        x = x + a
        h = _ln(x, lp["ln_x"], cfg.norm_eps)
        # cross-attn reads the (static) cached encoder K/V directly
        q = (h @ lp["xattn"]["wq"]).reshape(
            x.shape[0], 1, cfg.num_heads, cfg.resolved_head_dim)
        o = L.decode_attention(q, cross_c["k"], cross_c["v"],
                               cur_index=cross_c["k"].shape[1] - 1)
        x = x + o.reshape(x.shape[0], 1, -1) @ lp["xattn"]["wo"]
        h = _ln(x, lp["ln2"], cfg.norm_eps)
        x = x + L.apply_gelu_mlp(lp["mlp"], h)
        return x, new_self

    x, new_self = jax.lax.scan(body, x, (params["dec"], cache["self"],
                                         cache["cross_kv"]))
    x = _ln(x, params["dec_norm"], cfg.norm_eps)
    return x @ params["embed"].T, {"self": new_self, "cross_kv": cache["cross_kv"]}
