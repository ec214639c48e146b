"""Plain reference of one federated round, written from the protocol's
description and independent of the program under test.

Each worker takes one optimizer step from the global params on its own
rows (SGD with momentum, or AdamW; gradients clipped by global norm), and
its post-step loss is evaluated on the same rows. The updates are scored
(cosine to the leave-one-out consensus, norm plausibility against the
median, relative loss improvement), workers under the trust threshold are
filtered out, and the survivors are averaged by score. The asynchronous
round adds a staleness discount and per-worker pending buffers that fold
in the updates of workers that have not arrived yet.

Everything here is float32; matmuls take the precision of the caller's
``jax.default_matmul_precision`` (``highest`` for the reference). For the
control, ``quant`` rounds what a lower compute precision would (the
model's activations, matmul operands and gradients) and ``store`` what
a lower storage precision would (params, updates, optimizer state); the
identity gives the reference itself. The model's own loss comes from the
configuration's reference module (``configs/<name>.py``).
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


def identity(x):
    return x


def quantizer(dtype: str | None) -> Callable:
    """Round to ``dtype`` and back to f32; ``None`` leaves values exact.

    bfloat16 is a plain cast; ``bfloat16_3x`` keeps a bfloat16 and its
    bfloat16 remainder. 8-bit floats and int8 scale each tensor by
    its largest magnitude first, as a real low-precision path would, so
    the control measures rounding and not overflow."""
    if dtype is None:
        return identity
    if dtype == "bfloat16_3x":
        # a bfloat16 and a bfloat16 remainder: the operand that three
        # bfloat16 passes (precision ``high``) multiply
        def q3(x):
            hi = x.astype(jnp.bfloat16).astype(jnp.float32)
            return hi + (x - hi).astype(jnp.bfloat16).astype(jnp.float32)
        return q3
    dt = jnp.dtype(dtype)
    if dt == jnp.bfloat16:
        def q(x):
            return x.astype(jnp.bfloat16).astype(jnp.float32)
        return q
    top = (float(jnp.finfo(dt).max) if jnp.issubdtype(dt, jnp.floating)
           else float(jnp.iinfo(dt).max))

    def q(x):
        x = x.astype(jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
        y = x / scale
        if not jnp.issubdtype(dt, jnp.floating):
            y = jnp.round(y)
        return y.astype(dt).astype(jnp.float32) * scale
    return q


def ste(q: Callable) -> Callable:
    """``q`` in the forward pass, the identity in the backward pass."""
    if q is identity:
        return q

    def f(x):
        return x + jax.lax.stop_gradient(q(x) - x)
    return f


class Protocol(NamedTuple):
    """The federated job's rules, as the traffic file states them."""
    w_cosine: float
    w_norm: float
    w_loss: float
    trust_threshold: float
    soft_trust_weighting: bool
    staleness_alpha: float


class Optim(NamedTuple):
    name: str            # "sgd" | "adamw"
    lr: float
    momentum: float = 0.0
    dampening: float = 0.0
    nesterov: bool = False
    weight_decay: float = 0.0
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    grad_clip: float = 0.0


def opt_init(params, opt: Optim):
    z = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    if opt.name == "sgd":
        return {"buf": z}
    return {"m": z, "v": jax.tree.map(jnp.zeros_like, z),
            "count": jnp.zeros((), jnp.float32)}


def clip(grads, max_norm: float):
    if not max_norm:
        return grads
    sq = sum(jnp.sum(g * g) for g in jax.tree.leaves(grads))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(jnp.sqrt(sq), 1e-12))
    return jax.tree.map(lambda g: g * scale, grads)


def opt_step(params, grads, state, opt: Optim, q: Callable = identity):
    """One optimizer step in f32 (PyTorch SGD semantics; AdamW with bias
    correction and decoupled decay)."""
    if opt.name == "sgd":
        def upd(p, g, b):
            if opt.weight_decay:
                g = g + opt.weight_decay * p
            b = q(opt.momentum * b + (1.0 - opt.dampening) * g)
            step = g + opt.momentum * b if opt.nesterov else b
            return q(p - opt.lr * step), b
        out = jax.tree.map(upd, params, grads, state["buf"])
        pick = lambda i: jax.tree.map(lambda t: t[i], out,  # noqa: E731
                                      is_leaf=lambda x: isinstance(x, tuple))
        return pick(0), {"buf": pick(1)}
    count = state["count"] + 1.0
    c1 = 1.0 - opt.b1 ** count
    c2 = 1.0 - opt.b2 ** count

    def upd(p, g, m, v):
        m = q(opt.b1 * m + (1.0 - opt.b1) * g)
        v = q(opt.b2 * v + (1.0 - opt.b2) * g * g)
        step = (m / c1) / (jnp.sqrt(v / c2) + opt.eps)
        if opt.weight_decay:
            step = step + opt.weight_decay * p
        return q(p - opt.lr * step), m, v
    out = jax.tree.map(upd, params, grads, state["m"], state["v"])
    pick = lambda i: jax.tree.map(lambda t: t[i], out,  # noqa: E731
                                  is_leaf=lambda x: isinstance(x, tuple))
    return pick(0), {"m": pick(1), "v": pick(2), "count": count}


def first_grad(state, opt: Optim):
    """The gradient the optimizer was given on its first step, read back
    from its state after that step."""
    if opt.name == "sgd":
        return jax.tree.map(lambda b: b / (1.0 - opt.dampening), state["buf"])
    return jax.tree.map(lambda m: m / (1.0 - opt.b1), state["m"])


def scores(updates, loss_pre, loss_post, pr: Protocol):
    """Trust score per worker from (W, ...) update leaves."""
    leaves = jax.tree.leaves(updates)
    W = leaves[0].shape[0]
    flat = [u.reshape(W, -1) for u in leaves]
    total = [jnp.sum(u, axis=0) for u in flat]
    sq_u = sum(jnp.sum(u * u, axis=1) for u in flat)
    # leave-one-out consensus c_w = (Σ_v u_v − u_w) / (W − 1)
    dot = sum(jnp.sum(u * (t[None] - u), axis=1) for u, t in zip(flat, total))
    sq_c = sum(jnp.sum(jnp.square(t[None] - u), axis=1)
               for u, t in zip(flat, total))
    if W > 1:
        dot, sq_c = dot / (W - 1), sq_c / (W - 1) ** 2
    else:
        dot, sq_c = sq_u, sq_u
    norm = jnp.sqrt(sq_u)
    cos = jnp.clip(dot / jnp.maximum(norm * jnp.sqrt(sq_c), 1e-12), 0.0, 1.0)
    med = jnp.median(norm)
    plaus = jnp.exp(-jnp.abs(jnp.log(jnp.maximum(norm, 1e-12)
                                      / jnp.maximum(med, 1e-12))))
    gain = loss_pre - loss_post
    loss_term = jnp.clip(gain / jnp.maximum(jnp.max(gain), 1e-12), 0.0, 1.0)
    s = pr.w_cosine * cos + pr.w_norm * plaus + pr.w_loss * loss_term
    return s / (pr.w_cosine + pr.w_norm + pr.w_loss)


def weights(s, pr: Protocol, mask=None, staleness=None):
    """Aggregation weights: the trust filter, score weighting, arrivals
    and the staleness discount, normalised to sum to one."""
    w = (s >= pr.trust_threshold).astype(jnp.float32)
    if pr.soft_trust_weighting:
        w = w * s
    present = jnp.ones_like(s) if mask is None else mask.astype(jnp.float32)
    w = w * present
    w = jnp.where(jnp.sum(w) > 0, w, present)      # all filtered: uniform
    w = w / jnp.maximum(jnp.sum(w), 1e-12)
    if staleness is not None:
        w = w * (1.0 + staleness.astype(jnp.float32)) ** (-pr.staleness_alpha)
        w = w / jnp.maximum(jnp.sum(w), 1e-12)
    return w


class RoundOut(NamedTuple):
    params: object       # new global params (f32)
    opt: object          # per-worker optimizer state, leading W
    scores: jax.Array    # (W,)
    weights: jax.Array   # (W,)
    loss_pre: jax.Array  # (W,)
    loss_post: jax.Array  # (W,)
    pending: object      # async: per-worker buffers (W, ...); else None


def make_round(loss_fn: Callable, opt: Optim, pr: Protocol, *,
               quant: Callable = identity, store: Callable = identity,
               chunk: int = 0, score_loss_post: bool = True):
    """Build ``round(params, opt_w, batch, keys, mask, staleness,
    pending)``: one federated round for W workers. ``batch`` leaves have a
    leading W; ``keys`` is (W, 2). ``loss_fn(params, rows, key, qm)`` is
    the model reference, where ``qm`` rounds matmul operands.
    ``chunk`` > 0 runs workers ``chunk`` at a time, so that the reference
    fits in memory beside nothing else; 0 runs them all at once."""
    qm = ste(quant)

    def worker(params, state, rows, key):
        def f(p):
            return loss_fn(jax.tree.map(qm, p), rows, key, qm)
        l_pre, g = jax.value_and_grad(f)(params)
        g = clip(jax.tree.map(quant, g), opt.grad_clip)
        new_p, state = opt_step(params, g, state, opt, store)
        l_post = f(new_p) if score_loss_post else l_pre
        u = jax.tree.map(lambda a, b: store(a - b), new_p, params)
        return u, state, l_pre, l_post

    def all_workers(params, opt_w, batch, keys):
        vm = jax.vmap(worker, in_axes=(None, 0, 0, 0))
        W = keys.shape[0]
        if not chunk or chunk >= W:
            return vm(params, opt_w, batch, keys)
        n = W // chunk

        def split(t):
            return t.reshape((n, chunk) + t.shape[1:])

        out = jax.lax.map(lambda a: vm(params, *a),
                          jax.tree.map(split, (opt_w, batch, keys)))
        return jax.tree.map(lambda t: t.reshape((W,) + t.shape[2:]), out)

    def fl_round(params, opt_w, batch, keys, mask=None, staleness=None,
                 pending=None):
        u, opt_w, l_pre, l_post = all_workers(params, opt_w, batch, keys)
        s = scores(u, l_pre, l_post, pr)
        w = weights(s, pr, mask, staleness)
        if pending is not None:
            total = jax.tree.map(lambda p, x: p + x, pending, u)
            keep = 1.0 - mask.astype(jnp.float32)
            pending = jax.tree.map(
                lambda t: t * keep.reshape((-1,) + (1,) * (t.ndim - 1)),
                total)
        else:
            total = u
        agg = jax.tree.map(lambda t: jnp.tensordot(w, t, axes=(0, 0)), total)
        new = jax.tree.map(lambda p, a: store(p + a), params, agg)
        return RoundOut(new, opt_w, s, w, l_pre, l_post, pending)

    return fl_round


def leaf_norms(tree) -> dict:
    """{path: float norm} of each leaf, in f32."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): float(jnp.sqrt(jnp.sum(jnp.square(
        jnp.asarray(x, jnp.float32))))) for p, x in flat}


def worst_leaf_gap(prog: dict, ref: dict, skip=()) -> float:
    """Largest gap between the program's and the reference's norm of a
    leaf, over the larger of that leaf's reference norm and the median
    leaf's. Leaves named in ``skip`` are left out."""
    keys = [k for k in ref if k not in skip]
    med = float(np.median([ref[k] for k in keys]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30) for k in keys)
