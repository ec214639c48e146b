"""The jit-compiled SDFL-B round — the framework's ``train_step``.

Workers carry an explicit leading dim W on params/optimizer-state/batch
(W = num_clusters × workers_per_cluster [× pods]). On the production mesh W
is sharded over the ``data`` (× ``pod``) axes, so "worker w" is a
data-parallel slot whose model is TP-sharded over ``model``. Because the
worker dim is a *batch* dim (vmap), per-worker gradients stay separate —
no implicit cross-worker psum — and the paper's aggregation (trust-weighted,
cluster-hierarchical, optionally asynchronous) is applied explicitly:

  1. broadcast global params to all workers
  2. ``local_steps`` of per-worker SGD(momentum) on the worker's own shard
  3. per-worker update u_w = params_w − global
  4. trust statistics + scores (core.trust — Algorithm 1's evaluation)
  5. hierarchy.aggregate: intra-cluster FedAvg (cluster head) then
     trust-weighted head↔head exchange; async mode folds in staleness
     discounting + pending buffers (core.async_agg)
  6. new global = global + aggregate

Steps 3–5 have two implementations. The per-leaf reference streams the
W×D update volume ~5 times (a full updates pytree, then three reductions
per leaf, then the aggregate). The fused flat-pack path
(``FederationConfig.fused_trust_path``, auto-on for unsharded flat/CNN
trees) computes the deltas directly into ONE contiguous (W, D) matrix
(``kernels.pack``) and chains the Pallas trust kernels
(``kernels.fused_round``) — two streamed passes total, the pytree
reassembled exactly once for the global update. Both paths share the
score/weight math in ``core.trust``/``core.async_agg`` and are
property-tested equivalent (``tests/test_fused_round.py``).

A model with a frozen part (``ModelConfig.lora``) federates only its
trained leaves: ``global_params``, the optimizer state and the updates are
the adapters, and the frozen base enters the round once as ``frozen``,
shared by every worker (closed over by the vmapped step, so not
broadcast). A model without one passes no ``frozen`` and compiles to the
program it always did.

Host-level protocol work (contract settlement, ledger blocks, IPFS
publication, head rotation bookkeeping) happens *between* jitted rounds in
``core.protocol``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import FederationConfig, ModelConfig, TrainConfig
from repro.core import async_agg, hierarchy, trust
from repro.kernels import fused_round, ops, pack
from repro.models import api
from repro.optim import clip_grads, init_opt, opt_update


class RoundOutput(NamedTuple):
    global_params: object
    opt_state: object
    scores: jax.Array          # (W,) trust scores S(w)
    weights: jax.Array         # (W,) effective aggregation weights
    losses: jax.Array          # (W,) final local loss per worker
    metrics: dict
    counters: dict = {}        # the model's device counters over the
                               # cohort's steps (api.reduce_counters)


def num_workers(fed: FederationConfig, *, pods: int = 1) -> int:
    return fed.num_clusters * fed.workers_per_cluster * pods


def fused_round_enabled(cfg: ModelConfig, fed: FederationConfig, params,
                        *, constrained: bool = False) -> bool:
    """Static (trace-time) decision for the flat-pack fused trust path.

    ``auto`` engages only where flattening is free: an unsharded
    (no mesh constraints — reshaping a model-sharded leaf to (W, D)
    would force a full all-gather) flat/CNN param tree with one leaf
    dtype. ``on`` forces it for any packable tree; ``off`` keeps the
    per-leaf reference everywhere.
    """
    knob = fed.fused_trust_path
    if knob == "off":
        return False
    ok = pack.packable(params)
    if knob == "on":
        if not ok:
            raise ValueError(
                "fused_trust_path='on' requires a packable param tree "
                "(uniform floating leaf dtype)")
        return True
    if knob != "auto":
        raise ValueError(f"fused_trust_path must be auto|on|off, "
                         f"got {knob!r}")
    return ok and cfg.family == "cnn" and not constrained


def init_async_state_for(cfg: ModelConfig, fed: FederationConfig,
                         global_params, W: int) -> async_agg.AsyncState:
    """Async state matching the path ``make_fl_round`` will take: on the
    fused path the pending buffer is a flat (W_pad, D_pad) f32 matrix
    (padded once to the async kernel's tile grid — see
    ``fused_round.pending_shape``); otherwise the per-leaf pytree."""
    if fused_round_enabled(cfg, fed, global_params):
        spec = pack.pack_spec(global_params)
        return async_agg.AsyncState(
            staleness=jnp.zeros((W,), jnp.int32),
            pending=jnp.zeros(fused_round.pending_shape(W, spec.total),
                              jnp.float32))
    updates_like = jax.tree.map(
        lambda x: jnp.zeros((W,) + x.shape, jnp.float32), global_params)
    return async_agg.init_async_state(updates_like, W)


def make_fl_round(cfg: ModelConfig, fed: FederationConfig, tc: TrainConfig,
                  worker_constraint=None, param_constraint=None):
    """Builds the synchronous FL-round function (jit-able / lowerable).

    ``worker_constraint``: optional fn(tree_with_leading_W_dim) -> tree that
    applies sharding constraints pinning the worker dim to the data mesh
    axes (launch/specs.py builds it). Without it GSPMD may replicate every
    worker's parameter copy on every data slot — catastrophic at scale.

    ``param_constraint``: optional fn(per-worker param tree) -> tree applied
    *inside* the differentiated worker loss. Cotangents inherit sharding
    constraints, so this pins the per-layer grad stacks to the parameter
    sharding (otherwise the backward scan may emit fully-replicated f32
    grad stacks).
    """
    loss_fn = api.loss_fn(cfg, remat=tc.remat, kv_chunk=tc.kv_chunk)
    wsc = worker_constraint or (lambda t: t)
    pwsc = param_constraint or (lambda t: t)
    constrained = (worker_constraint is not None
                   or param_constraint is not None)

    def worker_train(params, opt, batch, rng, frozen):
        """One worker: ``local_steps`` SGD steps on its own data."""

        def one_step(carry, step_batch):
            p, o, r = carry
            r, sub = (jax.random.split(r) if r is not None else (None, None))
            (l, m), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                p, step_batch, sub, frozen)
            grads = clip_grads(grads, tc.grad_clip)
            p, o = opt_update(p, grads, o, tc)
            return (p, o, r), l

        if tc.local_steps == 1:
            step_batch = jax.tree.map(lambda x: x[0], batch)
            (p, o, _), l = one_step((params, opt, rng), step_batch)
            losses = l[None]
        else:
            (p, o, _), losses = jax.lax.scan(one_step, (params, opt, rng), batch)
        return p, o, losses

    def fl_round(global_params, opt_state, batch, rngs=None,
                 participation=None, async_state=None, frozen=None):
        """batch leaves: (W, local_steps, per_worker_batch, ...).
        participation: optional (W,) 0/1; async_state: async_agg.AsyncState;
        frozen: the model's frozen base (``api.init_frozen``), or None.
        """
        W = jax.tree.leaves(batch)[0].shape[0]
        # trace-time path selection: dtypes/structure only, no data
        use_fused = fused_round_enabled(cfg, fed, global_params,
                                        constrained=constrained)
        params_w = wsc(hierarchy.broadcast_to_workers(global_params, W))
        rngs_w = (jax.random.split(rngs, W) if rngs is not None else None)
        if tc.local_steps == 1:
            # single local step: keep only grad computation inside vmap so
            # the per-worker grads can be sharding-constrained before the
            # (elementwise, stack-friendly) optimizer update — otherwise the
            # stacked f32 grads replicate across the model axis.
            def worker_grad(p, b, r):
                step_batch = jax.tree.map(lambda x: x[0], b)

                def loss_c(p_, b_, r_):
                    return loss_fn(pwsc(p_), b_, r_, frozen)
                (l, m), g = jax.value_and_grad(loss_c, has_aux=True)(
                    p, step_batch, r)
                return clip_grads(g, tc.grad_clip), l, m.get("counters", {})
            vm = jax.vmap(worker_grad,
                          in_axes=(0, 0, 0 if rngs is not None else None))
            with jax.named_scope("worker_grad"):
                grads, l_pre, counters = vm(params_w, batch, rngs_w)
                if counters:
                    counters = api.reduce_counters(counters)
            with jax.named_scope("optimizer"):
                new_p, new_opt = opt_update(params_w, wsc(grads), opt_state,
                                            tc)
            if fed.w_loss > 0:
                # contribution quality needs a live loss delta: re-evaluate
                # the SAME batch (and dropout rng — the mask cancels) at the
                # post-step params. Without this, a single local step would
                # yield losses[:,0] == losses[:,-1] and the paper's
                # loss-improvement term would silently contribute nothing.
                def worker_loss(p, b, r):
                    step_batch = jax.tree.map(lambda x: x[0], b)
                    return loss_fn(pwsc(p), step_batch, r, frozen)[0]
                vl = jax.vmap(worker_loss,
                              in_axes=(0, 0, 0 if rngs is not None else None))
                with jax.named_scope("w_loss_eval"):
                    l_post = vl(new_p, batch, rngs_w)
                losses = jnp.stack([l_pre, l_post], axis=1)
            else:
                losses = l_pre[:, None]
        else:
            vm = jax.vmap(worker_train, in_axes=(
                0, 0, 0, 0 if rngs is not None else None, None))
            with jax.named_scope("worker_grad"):   # local steps + optimizer
                new_p, new_opt, losses = vm(params_w, opt_state, batch,
                                            rngs_w, frozen)
            counters = {}
        new_p = wsc(new_p)

        metrics = {"mean_loss": jnp.mean(losses[:, -1]),
                   "mean_loss_delta": jnp.mean(losses[:, 0] - losses[:, -1])}
        if use_fused:
            # flat-pack fused path: deltas land directly in ONE contiguous
            # (W, D) matrix (param dtype — bf16 deltas carry full *relative*
            # precision), trust stats + weighted aggregation chain the
            # fused kernels (2 streamed HBM passes over the update volume),
            # and the pytree is reassembled exactly once from the (D,)
            # aggregate. Every aggregation ``mode`` telescopes to the same
            # Σ w·u, so the fused sum is value-identical to the hierarchy.
            spec = pack.pack_spec(global_params)
            with jax.named_scope("trust"):
                upd_flat = pack.pack_delta(new_p, global_params, spec)
                stats = trust.update_stats_flat(upd_flat,
                                                losses[:, 0], losses[:, -1])
                scores = trust.scores_from_stats(stats, fed)
            with jax.named_scope("aggregate"):
                if fed.async_mode:
                    assert async_state is not None \
                        and participation is not None
                    weights = async_agg.effective_weights(
                        scores, participation, async_state.staleness, fed)
                    keep = 1.0 - participation.astype(jnp.float32)
                    agg_flat, new_pending = ops.fused_async_agg(
                        upd_flat, async_state.pending, weights, keep)
                    new_staleness = jnp.where(participation > 0, 0,
                                              async_state.staleness + 1)
                    new_async = async_agg.AsyncState(new_staleness,
                                                     new_pending)
                else:
                    weights = trust.trust_weights(
                        scores, fed, participation=participation)
                    agg_flat = ops.fused_agg(upd_flat, weights)
                    new_async = async_state
                agg = pack.unpack_vector(agg_flat, spec)
        else:
            # per-leaf reference: deltas are stored in the param dtype (bf16
            # deltas carry full *relative* precision; trust stats and
            # aggregation upcast per-leaf)
            with jax.named_scope("trust"):
                updates = wsc(jax.tree.map(
                    lambda a, g: (a.astype(jnp.float32)
                                  - g.astype(jnp.float32)[None]
                                  ).astype(a.dtype),
                    new_p, global_params))
                stats = trust.update_stats(updates, losses[:, 0],
                                           losses[:, -1])
                scores = trust.scores_from_stats(stats, fed)

            with jax.named_scope("aggregate"):
                if fed.async_mode:
                    # first-class async round variant: staleness-weighted
                    # buffered aggregation over the arrived cohort
                    # (core.async_agg)
                    assert async_state is not None \
                        and participation is not None
                    agg, new_async, weights = async_agg.async_round(
                        updates, scores, participation, async_state, fed)
                else:
                    weights = trust.trust_weights(
                        scores, fed, participation=participation)
                    if fed.mode == "head_gather":
                        agg = hierarchy.aggregate_head_gather(
                            updates, weights, fed)
                    elif fed.mode == "two_stage":
                        agg = hierarchy.aggregate(updates, weights, fed)
                    else:   # "allreduce": one collective, same value
                        agg = hierarchy.aggregate_fused(updates, weights)
                    new_async = async_state

        with jax.named_scope("aggregate"):
            new_global = jax.tree.map(
                lambda g, a: (g.astype(jnp.float32) + a).astype(g.dtype),
                global_params, agg)
        out = RoundOutput(new_global, new_opt, scores, weights,
                          losses[:, -1], metrics, counters)
        if fed.async_mode:
            return out, new_async
        return out

    return fl_round


def init_worker_opt(global_params, fed: FederationConfig, tc: TrainConfig,
                    *, pods: int = 1):
    """Per-worker optimizer state: leading W dim on every leaf."""
    W = num_workers(fed, pods=pods)
    single = init_opt(global_params, tc)
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (W,) + x.shape),
                        single)
