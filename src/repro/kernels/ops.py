"""Public wrappers around the Pallas kernels.

Lowered for a TPU the kernels compile natively; lowered for any other
platform they run with ``interpret=True`` (the body executes on CPU for
correctness). The platform the caller's program is lowered for decides
(``tpu.on_tpu``), not the process's default backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.trust_agg import trust_agg as _trust_agg
from repro.kernels.trust_score import trust_score_stats as _trust_score_stats
from repro.kernels.swa_decode import swa_decode as _swa_decode
from repro.kernels.ssd_scan import ssd_scan as _ssd_scan
# fused trust-round chain (flat-pack path) — backend-dispatching wrappers
from repro.kernels.fused_round import (fused_agg, fused_async_agg,  # noqa: F401
                                       fused_stats, pending_shape)
from repro.kernels.tpu import on_tpu


def _native_or_interpreted(kernel, *args, **static):
    return on_tpu(functools.partial(kernel, **static),
                  functools.partial(kernel, **static, interpret=True), *args)


def trust_weighted_aggregate(updates, weights):
    """(W, D) updates × (W,) weights -> (D,) f32 aggregate."""
    return _native_or_interpreted(_trust_agg, updates, weights)


def trust_stats(updates):
    """(W, D) -> (dot (W,), sq_u (W,), sq_c ()) vs consensus mean."""
    return _native_or_interpreted(_trust_score_stats, updates)


def sliding_window_decode(q, k_cache, v_cache, cur_index, *, window: int,
                          block_s: int = 512):
    """Single-token sliding-window decode attention (B,H,hd)."""
    return _native_or_interpreted(_swa_decode, q, k_cache, v_cache,
                                  jnp.asarray(cur_index, jnp.int32),
                                  window=window, block_s=block_s)


def ssd_chunk_scan(q, k, v, a, i, *, chunk: int = 256):
    """Fused SSD/decay-attention recurrence (Mamba2/mLSTM hot loop):
    (B,S,H,dk)×(B,S,H,dv) with per-step log-decay a and input gate i."""
    return _native_or_interpreted(_ssd_scan, q, k, v, a, i, chunk=chunk)


def aggregate_pytree(updates, weights):
    """Trust-weighted aggregation over a pytree with leading worker dim —
    flattens to one (W, D) matrix per leaf and runs the kernel; small leaves
    fall back to einsum (kernel launch not worth it)."""
    def leaf(u):
        W = u.shape[0]
        flat = u.reshape(W, -1)
        if flat.shape[1] < 1024:
            return jnp.einsum("w,wd->d", weights.astype(jnp.float32),
                              flat.astype(jnp.float32)).reshape(u.shape[1:])
        return trust_weighted_aggregate(flat, weights).reshape(u.shape[1:])
    return jax.tree.map(leaf, updates)
