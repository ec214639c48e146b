"""Shared model-zoo building blocks.

Pure-functional: params are nested dicts of jnp arrays; every ``init_*``
returns ``(params, specs)`` where ``specs`` mirrors ``params`` with
``jax.sharding.PartitionSpec`` leaves (TP over the ``model`` mesh axis,
replicated where a dim doesn't divide the axis size).

Attention never materializes the (Sq, Skv) score matrix for long sequences:
``blocked_attention`` runs an online-softmax scan over KV chunks
(flash-attention structure, pure JAX — the Pallas ``swa_decode`` kernel in
``repro.kernels`` is the TPU-tiled decode variant).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

# The TP mesh axis name used by every spec in the zoo.
TP_AXIS = "model"


def shard_dim(size: int, tp: int) -> bool:
    """Whether a dim of ``size`` can be TP-sharded over ``tp`` devices."""
    return tp > 1 and size % tp == 0


def maybe(axis_ok: bool):
    return TP_AXIS if axis_ok else None


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def dense_init(key, shape, in_dim: int, dtype=jnp.float32):
    scale = 1.0 / math.sqrt(in_dim)
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return (jax.random.normal(key, shape) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm(x, weight, eps: float = 1e-5):
    """RMSNorm with a hand-written VJP whose cotangents live in the PRIMAL
    dtype. With the autodiff VJP, XLA fuses the f32 upcast of dx into the
    producing TP matmul and then all-reduces the residual cotangent in f32 —
    2x the collective bytes of the bf16 boundary (measured: the dominant
    collective of the 34B train step)."""
    return _rms_fwd(x, weight, eps)[0]


def _rms_fwd(x, weight, eps):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    y = (xf * r * weight).astype(dt)
    return y, (x, weight, r)


def _rms_bwd(eps, res, g):
    x, weight, r = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    gw = gf * weight.astype(jnp.float32)
    xhat = xf * r
    dx = r * (gw - xhat * jnp.mean(gw * xhat, axis=-1, keepdims=True))
    dw = jnp.sum(gf * xhat, axis=tuple(range(x.ndim - 1)))
    return dx.astype(x.dtype), dw.astype(weight.dtype)


rms_norm.defvjp(_rms_fwd, _rms_bwd)


def layer_norm(x, weight, bias, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * weight + bias).astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                           # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]                     # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention cores
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _gqa_scores(q, k):
    """q: (B, Sq, KV, G, hd), k: (B, Skv, KV, hd) -> (B, KV, G, Sq, Skv) f32.
    Inputs stay in their storage dtype (bf16) with f32 MXU accumulation —
    casting the operands would let XLA hoist a full-precision copy of the
    whole KV cache out of the layer scan."""
    return jnp.einsum("bqkgh,bskh->bkgqs", q, k,
                      preferred_element_type=jnp.float32)


def blocked_attention(q, k, v, *, q_positions, kv_positions, causal: bool = True,
                      window: int = 0, kv_chunk: int = 1024):
    """Online-softmax attention over KV chunks — O(Sq·chunk) live memory.

    q: (B, Sq, H, hd); k, v: (B, Skv, KV, hd) with H % KV == 0.
    window > 0 => sliding-window mask (q_pos - kv_pos < window).
    Returns (B, Sq, H, hd).
    """
    B, Sq, H, hd = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    qs = q.reshape(B, Sq, KV, G, hd) * scale

    if Skv <= kv_chunk or Skv % kv_chunk != 0:
        # direct path (small or non-chunk-aligned KV, e.g. whisper's 1500
        # encoder frames)
        s = _gqa_scores(qs, k)                              # (B,KV,G,Sq,Skv)
        mask = _attn_mask(q_positions, kv_positions, causal, window)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqs,bskh->bqkgh", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        return o.reshape(B, Sq, H, hd).astype(q.dtype)
    if Sq > kv_chunk and Sq % kv_chunk == 0:
        # queries a block at a time (each recomputed in the backward pass):
        # the KV scan's saved carries then span one block of queries, not
        # all of them (16 chunks x 8k queries x 16 heads of f32 would be
        # 6 GB a worker at MLA's head size)
        nb = Sq // kv_chunk

        @jax.checkpoint
        def one_block(args):
            q_i, pos_i = args
            return blocked_attention(q_i, k, v, q_positions=pos_i,
                                     kv_positions=kv_positions, causal=causal,
                                     window=window, kv_chunk=kv_chunk)
        o = jax.lax.map(one_block, (
            jnp.moveaxis(q.reshape(B, nb, kv_chunk, H, hd), 1, 0),
            q_positions.reshape(nb, kv_chunk)))
        return jnp.moveaxis(o, 0, 1).reshape(B, Sq, H, hd)
    n_chunks = Skv // kv_chunk
    kc = k.reshape(B, n_chunks, kv_chunk, KV, hd)
    vc = v.reshape(B, n_chunks, kv_chunk, KV, hd)
    kvpos = kv_positions.reshape(n_chunks, kv_chunk)

    # remat the chunk body: backward recomputes per-chunk scores instead of
    # stacking (n_chunks, B, KV, G, Sq, chunk) f32 residuals (flash-style)
    @jax.checkpoint
    def body(carry, inp):
        m, l, acc = carry
        k_i, v_i, pos_i = inp
        s = _gqa_scores(qs, k_i)                            # (B,KV,G,Sq,chunk)
        mask = _attn_mask(q_positions, pos_i, causal, window)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgqs,bskh->bkgqh", p.astype(v_i.dtype), v_i,
                        preferred_element_type=jnp.float32)
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KV, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KV, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, KV, G, Sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0), kvpos))
    o = acc / jnp.maximum(l, 1e-30)[..., None]              # (B,KV,G,Sq,hd)
    return jnp.moveaxis(o, 3, 1).reshape(B, Sq, H, hd).astype(q.dtype)


def _attn_mask(q_pos, kv_pos, causal: bool, window: int):
    """(Sq, Skv) boolean mask: True = attend."""
    dq = q_pos[:, None]
    dk = kv_pos[None, :]
    mask = jnp.ones((q_pos.shape[0], kv_pos.shape[0]), bool)
    if causal:
        mask &= dk <= dq
    if window > 0:
        mask &= (dq - dk) < window
    return mask


def decode_attention(q, k_cache, v_cache, *, cur_index, window: int = 0):
    """Single-token decode: q (B, 1, H, hd) vs cache (B, S, KV, hd).

    ``cur_index``: scalar position of the new token; cache slots >= cur_index
    are masked (and slots outside the sliding window when ``window > 0``).
    Linear in cache length — the sub-quadratic decode path.
    """
    B, _, H, hd = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    qs = (q.reshape(B, KV, G, hd) * scale).astype(k_cache.dtype)
    s = jnp.einsum("bkgh,bskh->bkgs", qs, k_cache,
                   preferred_element_type=jnp.float32)      # (B,KV,G,S)
    pos = jnp.arange(S)
    valid = pos <= cur_index
    if window > 0:
        valid &= (cur_index - pos) < window
    s = jnp.where(valid[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskh->bkgh", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, 1, H, v_cache.shape[-1]).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention layer (llama/yi/smollm/danube/whisper-self/zamba-shared)
# ---------------------------------------------------------------------------

def init_gqa(key, d_model: int, num_heads: int, num_kv_heads: int,
             head_dim: int, tp: int, dtype):
    ks = jax.random.split(key, 4)
    hq, hkv = num_heads * head_dim, num_kv_heads * head_dim
    params = {
        "wq": dense_init(ks[0], (d_model, hq), d_model, dtype),
        "wk": dense_init(ks[1], (d_model, hkv), d_model, dtype),
        "wv": dense_init(ks[2], (d_model, hkv), d_model, dtype),
        "wo": dense_init(ks[3], (hq, d_model), hq, dtype),
    }
    specs = {
        "wq": P(None, maybe(shard_dim(num_heads, tp))),
        "wk": P(None, maybe(shard_dim(num_kv_heads, tp))),
        "wv": P(None, maybe(shard_dim(num_kv_heads, tp))),
        "wo": P(maybe(shard_dim(num_heads, tp)), None),
    }
    return params, specs


def apply_gqa(params, x, *, num_heads: int, num_kv_heads: int, head_dim: int,
              positions, rope_theta: float, causal: bool = True,
              window: int = 0, kv_chunk: int = 1024,
              cache=None, cur_index=None, cross_kv=None,
              return_kv: bool = False):
    """x: (B, S, d). If ``cache`` is given (decode): S == 1, returns
    (out, new_cache). ``cross_kv=(k, v)`` bypasses self-attn KV projections'
    inputs (whisper cross-attention: kv from encoder states).
    ``return_kv``: prefill mode — also return the projected (k, v) so the
    caller can populate a decode cache."""
    from repro.models.sharding import gather_weight as gw
    B, S, _ = x.shape
    q = (x @ gw(params["wq"])).reshape(B, S, num_heads, head_dim)
    if cross_kv is None:
        k = (x @ gw(params["wk"])).reshape(B, S, num_kv_heads, head_dim)
        v = (x @ gw(params["wv"])).reshape(B, S, num_kv_heads, head_dim)
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    else:
        enc = cross_kv
        Se = enc.shape[1]
        k = (enc @ params["wk"]).reshape(B, Se, num_kv_heads, head_dim)
        v = (enc @ params["wv"]).reshape(B, Se, num_kv_heads, head_dim)

    if cache is not None and cross_kv is None:
        # decode: write this token's k/v at cur_index, attend over cache
        k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), cur_index, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), cur_index, axis=1)
        o = decode_attention(q, k_cache, v_cache, cur_index=cur_index, window=window)
        new_cache = {"k": k_cache, "v": v_cache}
        return (o.reshape(B, S, -1) @ params["wo"]), new_cache

    if cross_kv is not None:
        kv_pos = jnp.arange(k.shape[1])
        o = blocked_attention(q, k, v, q_positions=positions, kv_positions=kv_pos,
                              causal=False, window=0, kv_chunk=kv_chunk)
    else:
        from repro.models.sharding import replicate_kv
        k2, v2 = replicate_kv(k, v)
        o = blocked_attention(q, k2, v2, q_positions=positions,
                              kv_positions=positions, causal=causal,
                              window=window, kv_chunk=kv_chunk)
    out = o.reshape(B, S, -1) @ gw(params["wo"])
    if return_kv:
        return out, {"k": k, "v": v}
    return out


def gqa_cache_shape(batch: int, seq: int, num_kv_heads: int, head_dim: int):
    return {"k": (batch, seq, num_kv_heads, head_dim),
            "v": (batch, seq, num_kv_heads, head_dim)}


def gqa_cache_spec(num_kv_heads: int, tp: int, data_axes):
    h = maybe(shard_dim(num_kv_heads, tp))
    if h is None and tp > 1:
        # few KV heads (GQA): shard the cache SEQUENCE over the TP axis
        # instead — decode attention becomes a partial softmax + tiny psum
        # (flash-decode) rather than a replicated-cache reshuffle.
        return {"k": P(data_axes, TP_AXIS, None, None),
                "v": P(data_axes, TP_AXIS, None, None)}
    return {"k": P(data_axes, None, h, None), "v": P(data_axes, None, h, None)}


# ---------------------------------------------------------------------------
# MLA attention (minicpm3 / deepseek-style latent attention)
# ---------------------------------------------------------------------------

def init_mla(key, d_model: int, num_heads: int, mla, tp: int, dtype):
    ks = jax.random.split(key, 8)
    qk_hd = mla.qk_nope_head_dim + mla.qk_rope_head_dim
    h = maybe(shard_dim(num_heads, tp))
    if mla.q_lora_rank:
        params = {
            "wq_a": dense_init(ks[0], (d_model, mla.q_lora_rank), d_model, dtype),
            "q_a_norm": jnp.ones((mla.q_lora_rank,), dtype),
            "wq_b": dense_init(ks[1], (mla.q_lora_rank, num_heads * qk_hd), mla.q_lora_rank, dtype),
        }
        r = maybe(shard_dim(mla.q_lora_rank, tp))
        specs = {"wq_a": P(None, r), "q_a_norm": P(r), "wq_b": P(r, h)}
    else:
        params = {"wq": dense_init(ks[0], (d_model, num_heads * qk_hd), d_model, dtype)}
        specs = {"wq": P(None, h)}
    params.update({
        "wkv_a": dense_init(ks[2], (d_model, mla.kv_lora_rank + mla.qk_rope_head_dim), d_model, dtype),
        "kv_a_norm": jnp.ones((mla.kv_lora_rank,), dtype),
        "wkv_b": dense_init(ks[3], (mla.kv_lora_rank, num_heads * (mla.qk_nope_head_dim + mla.v_head_dim)), mla.kv_lora_rank, dtype),
        "wo": dense_init(ks[4], (num_heads * mla.v_head_dim, d_model), num_heads * mla.v_head_dim, dtype),
    })
    specs.update({"wkv_a": P(None, None), "kv_a_norm": P(None),
                  "wkv_b": P(None, h), "wo": P(h, None)})
    return params, specs


# the MLA projections by their Hugging Face (DeepSeek) names, which name a
# LoRA adapter's target
MLA_TARGETS = {"wq": "q_proj", "wkv_a": "kv_a_proj_with_mqa",
               "wkv_b": "kv_b_proj", "wo": "o_proj"}


def lora_dense(x, w, adapter=None, scale: float = 1.0):
    """x @ w, plus the LoRA term (x @ a) @ b * scale where an ``adapter``
    {"a": (in, r), "b": (r, out)} is given. Every product takes operands in
    x's dtype and accumulates in f32; the f32 adapters are cast to it."""
    if adapter is None:
        return x @ w
    dt = x.dtype
    y = jnp.dot(x, w, preferred_element_type=jnp.float32)
    with jax.named_scope("lora"):
        xa = jnp.dot(x, adapter["a"].astype(dt),
                     preferred_element_type=jnp.float32).astype(dt)
        y = y + scale * jnp.dot(xa, adapter["b"].astype(dt),
                                preferred_element_type=jnp.float32)
    return y.astype(dt)


def apply_mla(params, x, *, num_heads: int, mla, positions, rope_theta: float,
              kv_chunk: int = 1024, cache=None, cur_index=None,
              return_kv: bool = False, lora=None, lora_scale: float = 1.0):
    """MLA: queries through a low-rank bottleneck (or one ``wq`` where
    ``q_lora_rank`` is 0); K/V through a compressed latent (kv_lora_rank)
    + a decoupled RoPE key shared across heads. The decode cache stores
    the *latent* (B, S, kv_lora_rank + rope_dim) — the MLA memory win.
    ``lora``: adapters by target name (``MLA_TARGETS``), training path
    only. RoPE rotates the two halves of the rope dims; DeepSeek's
    checkpoints interleave pairs, which is the same up to a permutation
    of the rope columns of ``wq`` and ``wkv_a``."""
    B, S, _ = x.shape
    nope, rd, vd = mla.qk_nope_head_dim, mla.qk_rope_head_dim, mla.v_head_dim
    qk_hd = nope + rd
    lora = lora or {}

    def proj(inp, name):
        return lora_dense(inp, params[name], lora.get(MLA_TARGETS[name]),
                          lora_scale)

    if mla.q_lora_rank:
        q = rms_norm(x @ params["wq_a"], params["q_a_norm"])
        q = q @ params["wq_b"]
    else:
        q = proj(x, "wq")
    q = q.reshape(B, S, num_heads, qk_hd)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, rope_theta)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)

    kv_a = proj(x, "wkv_a")                                 # (B,S,rank+rd)
    latent, k_rope = kv_a[..., :mla.kv_lora_rank], kv_a[..., mla.kv_lora_rank:]
    latent = rms_norm(latent, params["kv_a_norm"])
    k_rope = apply_rope(k_rope[..., None, :], positions, rope_theta)  # (B,S,1,rd)

    if cache is not None:
        # ABSORBED decode (DeepSeek-V2-style serving form): attention runs in
        # the compressed latent space — the cache is never expanded to
        # per-head K/V. q̃_h = W_kvb_k(h)ᵀ q_nope_h ∈ R^rank;
        # score_i = q̃·latent_i + q_rope·k_rope_i; out_h = W_kvb_v(h) (p·latent).
        lat_entry = jnp.concatenate([latent, k_rope[..., 0, :]], axis=-1)
        lat_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["latent"], lat_entry.astype(cache["latent"].dtype), cur_index, axis=1)
        rank = mla.kv_lora_rank
        lat_dt = lat_cache.dtype
        latent_all = lat_cache[..., :rank]                       # (B,Sc,r)
        k_rope_all = lat_cache[..., rank:]                       # (B,Sc,rd)
        wkv = params["wkv_b"].reshape(rank, num_heads, nope + vd)
        w_k, w_v = wkv[..., :nope], wkv[..., nope:]
        scale = 1.0 / math.sqrt(qk_hd)
        qh = (q[:, 0] * scale).astype(lat_dt)                    # (B,H,qk_hd)
        q_til = jnp.einsum("bhn,rhn->bhr", qh[..., :nope],
                           w_k.astype(lat_dt),
                           preferred_element_type=jnp.float32).astype(lat_dt)
        s = (jnp.einsum("bhr,bsr->bhs", q_til, latent_all,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bhd,bsd->bhs", qh[..., nope:], k_rope_all,
                          preferred_element_type=jnp.float32))
        Sc = lat_cache.shape[1]
        pos = jnp.arange(Sc)
        s = jnp.where((pos <= cur_index)[None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        ctx = jnp.einsum("bhs,bsr->bhr", p.astype(lat_dt), latent_all,
                         preferred_element_type=jnp.float32)     # (B,H,r)
        o = jnp.einsum("bhr,rhv->bhv", ctx.astype(lat_dt),
                       w_v.astype(lat_dt),
                       preferred_element_type=jnp.float32).astype(x.dtype)
        return (o.reshape(B, S, -1) @ params["wo"]), {"latent": lat_cache}

    kv = proj(latent, "wkv_b").reshape(B, S, num_heads, nope + vd)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (B, S, num_heads, rd))], axis=-1)
    v = kv[..., nope:]
    # pad v to qk head dim for the shared blocked core, then slice back
    o = blocked_attention(q, k, jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, qk_hd - vd))),
                          q_positions=positions, kv_positions=positions,
                          causal=True, kv_chunk=kv_chunk)[..., :vd]
    out = proj(o.reshape(B, S, -1), "wo")
    if return_kv:
        # MLA prefill cache: the compressed latent + decoupled rope key
        return out, {"latent": jnp.concatenate([latent, k_rope[..., 0, :]], axis=-1)}
    return out


def mla_cache_shape(batch: int, seq: int, mla):
    return {"latent": (batch, seq, mla.kv_lora_rank + mla.qk_rope_head_dim)}


def mla_cache_spec(data_axes, tp: int = 1):
    # the compressed latent has no head dim: shard its sequence over TP
    return {"latent": P(data_axes, TP_AXIS if tp > 1 else None, None)}


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_swiglu(key, d_model: int, d_ff: int, tp: int, dtype):
    ks = jax.random.split(key, 3)
    f = maybe(shard_dim(d_ff, tp))
    params = {
        "w_gate": dense_init(ks[0], (d_model, d_ff), d_model, dtype),
        "w_up": dense_init(ks[1], (d_model, d_ff), d_model, dtype),
        "w_down": dense_init(ks[2], (d_ff, d_model), d_ff, dtype),
    }
    specs = {"w_gate": P(None, f), "w_up": P(None, f), "w_down": P(f, None)}
    return params, specs


def apply_swiglu(params, x):
    from repro.models.sharding import gather_weight as gw
    return (jax.nn.silu(x @ gw(params["w_gate"]))
            * (x @ gw(params["w_up"]))) @ gw(params["w_down"])


def init_gelu_mlp(key, d_model: int, d_ff: int, tp: int, dtype):
    ks = jax.random.split(key, 2)
    f = maybe(shard_dim(d_ff, tp))
    params = {
        "w_in": dense_init(ks[0], (d_model, d_ff), d_model, dtype),
        "b_in": jnp.zeros((d_ff,), dtype),
        "w_out": dense_init(ks[1], (d_ff, d_model), d_ff, dtype),
        "b_out": jnp.zeros((d_model,), dtype),
    }
    specs = {"w_in": P(None, f), "b_in": P(f), "w_out": P(f, None), "b_out": P(None)}
    return params, specs


def apply_gelu_mlp(params, x):
    return jax.nn.gelu(x @ params["w_in"] + params["b_in"]) @ params["w_out"] + params["b_out"]
