"""Mixture-of-Experts layer (qwen2-moe, olmoe).

Two mathematically-identical implementations:

* ``apply_moe(..., impl="gather")`` — production path. Per-expert top-C
  token selection (capacity-based, GShard-style dropping) + batched gather,
  expert matmuls batched over the expert dim (sharded over the ``model``
  mesh axis => expert parallelism), scatter-add combine. All ops are plain
  jnp => vmap-safe (needed by the FL worker dim) and GSPMD-shardable.
* ``apply_moe(..., impl="dense")`` — oracle: every token through every
  expert, mask-weighted. Used in tests to validate the gather path
  (identical outputs when capacity is not exceeded).

Experts are padded to a multiple of the TP axis so the expert dim shards
evenly (padded experts get -inf router logits => never selected).

``apply_moe_held`` is one chip's share of an expert-parallel layer
(``MoEConfig.experts_held``, DeepSeek-V3 routing): the router scores every
expert, the chip computes the experts it holds for every token-expert pair
routed to them, with no capacity and no drop, as one grouped product over
the pairs sorted by expert (``grouped_matmul``); the pairs of absent
experts are left out. The shared experts run on every token.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap
from jax.sharding import PartitionSpec as P

from repro.models.layers import dense_init, maybe, shard_dim


def padded_experts(num_experts: int, tp: int) -> int:
    return int(math.ceil(num_experts / max(tp, 1)) * max(tp, 1))


def init_moe(key, d_model: int, moe_cfg, tp: int, dtype):
    """Router + routed experts (+ optional always-on shared experts). A
    held share (``experts_held``) keeps the router's full width and the
    weights of its own experts only; DeepSeek-V3 routing adds the f32
    selection bias and keeps the router in the model dtype, as its
    checkpoints do."""
    if moe_cfg.held:
        E_router, E = moe_cfg.num_experts, moe_cfg.experts_held[1]
    else:
        E_router = E = padded_experts(moe_cfg.num_experts, tp)
    f = moe_cfg.d_ff_expert
    ks = jax.random.split(key, 6)
    sig = moe_cfg.scoring == "sigmoid"
    params = {
        "router": dense_init(ks[0], (d_model, E_router), d_model,
                             dtype if sig else jnp.float32),
        "w_gate": dense_init(ks[1], (E, d_model, f), d_model, dtype),
        "w_up": dense_init(ks[2], (E, d_model, f), d_model, dtype),
        "w_down": dense_init(ks[3], (E, f, d_model), f, dtype),
    }
    if sig:
        params["router_bias"] = jnp.zeros((E_router,), jnp.float32)
    e = maybe(shard_dim(E, tp))
    fs = maybe(shard_dim(f, tp)) if e is None else None
    specs = {
        "router": P(None, None),
        "w_gate": P(e, None, fs), "w_up": P(e, None, fs), "w_down": P(e, fs, None),
    }
    if sig:
        specs["router_bias"] = P(None)
    if moe_cfg.num_shared_experts > 0:
        fsh = moe_cfg.num_shared_experts * moe_cfg.d_ff_shared
        sh = maybe(shard_dim(fsh, tp))
        params["shared"] = {
            "w_gate": dense_init(ks[4], (d_model, fsh), d_model, dtype),
            "w_up": dense_init(ks[5], (d_model, fsh), d_model, dtype),
            "w_down": dense_init(jax.random.fold_in(ks[5], 1), (fsh, d_model), fsh, dtype),
        }
        specs["shared"] = {"w_gate": P(None, sh), "w_up": P(None, sh),
                           "w_down": P(sh, None)}
        if moe_cfg.shared_gate:
            params["shared"]["gate"] = dense_init(
                jax.random.fold_in(ks[4], 1), (d_model, 1), d_model,
                jnp.float32)
            specs["shared"]["gate"] = P(None, None)
    return params, specs


def _router_probs(params, x_flat, moe_cfg):
    """x_flat: (T, d) -> (probs (T, E) f32 with pads masked, logits)."""
    E_pad = params["router"].shape[1]
    logits = x_flat.astype(jnp.float32) @ params["router"]
    pad_mask = jnp.arange(E_pad) < moe_cfg.num_experts
    logits = jnp.where(pad_mask[None, :], logits, -1e30)
    return jax.nn.softmax(logits, axis=-1), logits


def _topk_weights(probs, top_k: int):
    """(T, E) -> sparse weight matrix (T, E): renormalized top-k probs."""
    T, E = probs.shape
    vals, idx = jax.lax.top_k(probs, top_k)                 # (T, k)
    vals = vals / jnp.maximum(jnp.sum(vals, axis=-1, keepdims=True), 1e-9)
    w = jnp.zeros((T, E), jnp.float32)
    w = w.at[jnp.arange(T)[:, None], idx].set(vals)
    return w


def _aux_losses(probs, w, logits, moe_cfg):
    """GShard load-balance loss + router z-loss."""
    E = moe_cfg.num_experts
    frac_routed = jnp.mean((w > 0).astype(jnp.float32), axis=0) * E   # (E_pad,)
    mean_prob = jnp.mean(probs, axis=0) * E
    lb = jnp.sum(frac_routed * mean_prob) / E
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return moe_cfg.router_aux_loss * lb + moe_cfg.router_z_loss * z


def _expert_ffn(params, xe):
    """xe: (E, C, d) -> (E, C, d); batched-over-experts SwiGLU."""
    g = jnp.einsum("ecd,edf->ecf", xe, params["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", xe, params["w_up"])
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, params["w_down"])


def apply_moe(params, x, moe_cfg, *, capacity_factor: float = 0.0,
              impl: str = "gather") -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d). Returns (out (B, S, d), aux_loss scalar).
    capacity_factor 0 => take moe_cfg.capacity_factor."""
    capacity_factor = capacity_factor or moe_cfg.capacity_factor
    B, S, d = x.shape
    T = B * S
    x_flat = x.reshape(T, d)
    probs, logits = _router_probs(params, x_flat, moe_cfg)
    w = _topk_weights(probs, moe_cfg.top_k)                 # (T, E_pad)
    aux = _aux_losses(probs, w, logits, moe_cfg)
    E_pad = w.shape[1]

    if impl == "dense":
        # oracle: all tokens through all experts, weighted combine
        g = jnp.einsum("td,edf->tef", x_flat, params["w_gate"])
        u = jnp.einsum("td,edf->tef", x_flat, params["w_up"])
        y = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * u, params["w_down"])
        out = jnp.einsum("ted,te->td", y.astype(jnp.float32), w)
    else:
        # capacity-based: per-expert top-C tokens by routing weight
        C = max(1, int(math.ceil(moe_cfg.top_k * T / moe_cfg.num_experts
                                 * capacity_factor)))
        C = min(C, T)
        w_e = w.T                                           # (E_pad, T)
        top_w, top_idx = jax.lax.top_k(w_e, C)              # (E_pad, C)
        xe = jnp.take(x_flat, top_idx.reshape(-1), axis=0)
        xe = xe.reshape(E_pad, C, d)                        # expert-batched gather
        ye = _expert_ffn(params, xe).astype(jnp.float32)
        ye = ye * top_w[..., None]                          # dropped tokens have w=0
        out = jnp.zeros((T, d), jnp.float32)
        out = out.at[top_idx.reshape(-1)].add(ye.reshape(E_pad * C, d))

    if "shared" in params:
        sp = params["shared"]
        shared = (jax.nn.silu(x_flat @ sp["w_gate"]) * (x_flat @ sp["w_up"])) @ sp["w_down"]
        gate = jax.nn.sigmoid(x_flat.astype(jnp.float32) @ sp["gate"])
        out = out + gate * shared.astype(jnp.float32)

    return out.reshape(B, S, d).astype(x.dtype), aux


# -- one chip's share of an expert-parallel layer -----------------------------


def _ragged(x, w, group_sizes):
    """Rows of x, grouped in order by ``group_sizes``, times their group's
    matrix of w; rows past the last group are zero."""
    y = jax.lax.ragged_dot(x, w, group_sizes)
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.where(rows < jnp.sum(group_sizes), y, jnp.zeros((), y.dtype))


@custom_vmap
def _grouped(x, w, group_sizes):
    return _ragged(x, w, group_sizes)


@_grouped.def_vmap
def _grouped_vmap(axis_size, in_batched, x, w, group_sizes):
    """The workers' products one after another, each with its own groups,
    against the one shared w (``ragged_dot`` batches only a batched w)."""
    x_b, w_b, g_b = in_batched
    if w_b:
        raise NotImplementedError("grouped_matmul batches rows, not weights")
    if not x_b:
        x = jnp.broadcast_to(x, (axis_size,) + x.shape)
    if not g_b:
        group_sizes = jnp.broadcast_to(group_sizes,
                                       (axis_size,) + group_sizes.shape)
    return jax.lax.map(lambda a: _grouped(a[0], w, a[1]),
                       (x, group_sizes)), True


@jax.custom_vjp
def grouped_matmul(x, w, group_sizes):
    """(m, d) rows sorted by group times (g, d, f) group matrices -> (m, f),
    rows past sum(group_sizes) zero. Its compute follows the grouped rows
    (``ragged_dot``; a Mosaic kernel on a TPU). The weights are frozen: the
    product gives no gradient to ``w``, only to ``x``."""
    return _grouped(x, w, group_sizes)


def _grouped_fwd(x, w, group_sizes):
    return _grouped(x, w, group_sizes), (w, group_sizes)


def _grouped_bwd(res, ct):
    w, group_sizes = res
    return _grouped(ct, jnp.swapaxes(w, 1, 2), group_sizes), None, None


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)


def route_sigmoid(params, x_flat, moe_cfg):
    """DeepSeek-V3 routing: experts chosen by the top-k of sigmoid scores
    plus the selection bias; their weights are the chosen scores (without
    the bias) normalised to sum to one and scaled. Returns ((T, k) expert
    ids, (T, k) f32 weights)."""
    logits = jnp.dot(x_flat, params["router"],
                     preferred_element_type=jnp.float32)
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + params["router_bias"], moe_cfg.top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * moe_cfg.routed_scaling


def held_counters(group_sizes) -> dict:
    """A layer's counters: token-expert pairs the held experts computed,
    and the largest number any one of them computed."""
    return {"moe.pairs_held": jnp.sum(group_sizes),
            "moe.max_expert_load": jnp.max(group_sizes)}


def merge_counters(a: dict, b: dict) -> dict:
    """Counters of two layers (or workers) together: pairs add up, the
    largest load is the larger."""
    return {"moe.pairs_held": a["moe.pairs_held"] + b["moe.pairs_held"],
            "moe.max_expert_load": jnp.maximum(a["moe.max_expert_load"],
                                               b["moe.max_expert_load"])}


def reduce_counters(c: dict) -> dict:
    """``merge_counters`` over a leading axis."""
    return {"moe.pairs_held": jnp.sum(c["moe.pairs_held"]),
            "moe.max_expert_load": jnp.max(c["moe.max_expert_load"])}


def zero_counters() -> dict:
    return {"moe.pairs_held": jnp.zeros((), jnp.int32),
            "moe.max_expert_load": jnp.zeros((), jnp.int32)}


def apply_moe_held(params, x, moe_cfg) -> Tuple[jax.Array, dict]:
    """x: (B, S, d) -> (this chip's share of the layer's output (B, S, d),
    counters). The share is the held experts' weighted outputs for the
    pairs routed to them, plus the shared experts (ungated, DeepSeek-V3)."""
    lo, n = moe_cfg.experts_held
    B, S, d = x.shape
    T, k = B * S, moe_cfg.top_k
    x_flat = x.reshape(T, d)
    with jax.named_scope("moe_route"):
        idx, w = route_sigmoid(params, x_flat, moe_cfg)
        local = idx.reshape(-1) - lo
        held = (local >= 0) & (local < n)
        # held pairs first, by expert; the absent ones (group n) last
        order = jnp.argsort(jnp.where(held, local, n), stable=True)
        sizes = jnp.sum(jax.nn.one_hot(local, n, dtype=jnp.int32)
                        * held[:, None], axis=0)
        where = jnp.zeros_like(order).at[order].set(
            jnp.arange(T * k, dtype=order.dtype))
    with jax.named_scope("moe_experts"):
        xs = jnp.take(x_flat, order // k, axis=0)
        g = grouped_matmul(xs, params["w_gate"], sizes)
        u = grouped_matmul(xs, params["w_up"], sizes)
        ys = grouped_matmul(jax.nn.silu(g) * u, params["w_down"], sizes)
        y = jnp.take(ys, where, axis=0).reshape(T, k, d)
        # the f32 weights scale each pair's output, as DeepSeek-V3 does;
        # absent experts' rows are zero
        out = jnp.sum(y.astype(jnp.float32) * w[..., None], axis=1)
    if "shared" in params:
        with jax.named_scope("shared_experts"):
            sp = params["shared"]
            h = jax.nn.silu(x_flat @ sp["w_gate"]) * (x_flat @ sp["w_up"])
            out = out + jnp.dot(h, sp["w_down"],
                                preferred_element_type=jnp.float32)
    return out.reshape(B, S, d).astype(x.dtype), held_counters(sizes)
