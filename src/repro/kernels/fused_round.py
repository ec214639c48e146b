"""Fused device-resident trust round — one-sweep scoring + aggregation.

The per-leaf reference path in ``core.fl_step`` streams the W×D update
volume ~5 times per round (dot/sq_u/sq_c reductions in
``trust.update_stats``, then the weighted aggregate). The aggregation
weights depend on *global* statistics of the whole matrix, so one pass is
information-theoretically impossible without a W×D intermediate — the
floor is two streamed passes, and this module hits it:

  pass 1  ``fused_stats``     one HBM sweep producing dot/sq_u/sq_c
                              (the ``trust_score`` kernel: consensus
                              recomputed in-VMEM per tile, no second
                              stream of c)
  (O(W))  score/weight math   ``trust.scores_from_stats`` +
                              ``trust_weights`` — W-sized, runs off the
                              hot path, pipelined by XLA against the
                              second pass's prologue
  pass 2  ``fused_agg``       one MXU sweep for the weighted aggregate
                              (sync), or ``fused_async_agg`` — a NEW
                              kernel that in the same sweep folds the
                              pending buffer (total = pending + update),
                              emits the staleness-discounted aggregate
                              AND the flushed new pending, so the async
                              path's buffer logic costs no extra pass
                              over the update matrix

Dispatch (``tpu.on_tpu``): a program lowered for a TPU runs the Pallas
kernels; lowered for any other platform it runs the flat-jnp references
(``kernels.ref``), the identical packed math. The platform the round is
compiled for decides, when it is lowered — not the process's backend at
import. ``SDFLB_FUSED_INTERPRET=1`` sends the non-TPU branch through the
interpreted kernels instead (kernel-correctness end to end on CPU).

Tiling: each sync kernel sizes its tiles against the chip's scoped VMEM
(``trust_score.tiles`` / ``trust_agg.tiles``): a full-W column strip at
the widest D tile that fits, and W tiles as well where even a 128-lane
strip does not — so every cohort size compiles. Only a W-tiled
``trust_score`` sweeps the matrix twice (column sums, then statistics).
The async kernel tiles BOTH dims (grid = D-tiles × W-tiles, aggregate
accumulated over the inner W axis); its pending buffer persists padded
to the tile grid (``pending_shape``) so no per-round pad/slice copies are
needed.

``streamed_bytes``/``update_passes`` compute the chain's exact HBM
traffic from the BlockSpec geometry (every index map visits each element
once per call) — XLA's ``cost_analysis`` cannot see through a fused
kernel body, so the benchmark gate counts the kernel's bytes this way
and uses cost_analysis only for the unfused comparison.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref, trust_score
from repro.kernels.tpu import on_tpu, round_up
from repro.kernels.trust_agg import trust_agg
from repro.kernels.trust_score import trust_score_stats

SUBLANE = 8

# -- async kernel geometry ----------------------------------------------------

BLOCK_W = 256      # W tile of the async kernel (sublane-aligned)
BLOCK_D_ASYNC = 512


def pending_shape(W: int, D: int) -> tuple:
    """Persistent (W_pad, D_pad) storage shape of the flat async pending
    buffer — padded once at init to the async kernel's tile grid so
    rounds never pad/slice the (W, D) volume."""
    bw = min(BLOCK_W, round_up(W, SUBLANE))
    return (round_up(W, bw), round_up(D, BLOCK_D_ASYNC))


# -- the async fused kernel ---------------------------------------------------


def _async_kernel(w_ref, keep_ref, upd_ref, pend_ref, agg_ref, newp_ref):
    """One (BW, BD) tile: total = pending + update; emit the flushed new
    pending and accumulate the weighted aggregate over the inner W axis.

    w_ref: (1, BW) weight slice · keep_ref: (BW, 1) keep mask slice
    upd_ref/pend_ref/newp_ref: (BW, BD) · agg_ref: (1, BD) accumulator.
    """
    wi = pl.program_id(1)                    # inner: W tiles
    u = upd_ref[...].astype(jnp.float32)
    total = pend_ref[...] + u
    newp_ref[...] = total * keep_ref[...]
    part = jnp.dot(w_ref[...], total, preferred_element_type=jnp.float32)

    @pl.when(wi == 0)
    def _init():
        agg_ref[...] = part

    @pl.when(wi > 0)
    def _acc():
        agg_ref[...] += part


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_async_agg_kernel(updates, pending, weights, keep, *,
                           interpret: bool = False):
    """updates: (W, D); pending: ``pending_shape(W, D)`` f32;
    weights/keep: (W,) → (agg (D,) f32, new_pending (W_pad, D_pad) f32).

    One streamed pass over the update volume computes the weighted
    aggregate of (pending + update) AND the flushed pending
    (``total·keep``) — the async path's whole post-score data motion.
    """
    W, D = updates.shape
    Wp, Dp = pending.shape
    assert (Wp, Dp) == pending_shape(W, D), \
        f"pending {pending.shape} != pending_shape({W},{D})"
    bw = min(BLOCK_W, Wp)
    bd = min(BLOCK_D_ASYNC, Dp)
    upd = jnp.pad(updates, ((0, Wp - W), (0, Dp - D)))
    w_row = jnp.pad(weights.astype(jnp.float32), (0, Wp - W)).reshape(1, Wp)
    keep_col = jnp.pad(keep.astype(jnp.float32), (0, Wp - W)).reshape(Wp, 1)

    agg, newp = pl.pallas_call(
        _async_kernel,
        grid=(Dp // bd, Wp // bw),           # W tiles innermost: accumulate
        in_specs=[
            pl.BlockSpec((1, bw), lambda d, w: (0, w),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bw, 1), lambda d, w: (w, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bw, bd), lambda d, w: (w, d),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bw, bd), lambda d, w: (w, d),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bd), lambda d, w: (0, d),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bw, bd), lambda d, w: (w, d),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, Dp), jnp.float32),
            jax.ShapeDtypeStruct((Wp, Dp), jnp.float32),
        ],
        interpret=interpret,
        name="fused_async_agg_kernel",
    )(w_row, keep_col, upd, pending)
    return agg[0, :D], newp


# -- dispatching public entry points ------------------------------------------


def fused_stats(updates: jax.Array):
    """Pass 1: (W, D) → (dot (W,), sq_u (W,), sq_c ()) vs the inclusive
    consensus, in one HBM sweep."""
    return on_tpu(trust_score_stats, ref.trust_score_ref, updates)


def fused_agg(updates: jax.Array, weights: jax.Array) -> jax.Array:
    """Pass 2 (sync): (W, D) × (W,) → (D,) f32 weighted aggregate."""
    return on_tpu(trust_agg, ref.trust_agg_ref, updates, weights)


def _async_agg_ref(updates, pending, weights, keep):
    """Flat-jnp twin of ``fused_async_agg_kernel`` on the same padded
    pending geometry."""
    W, D = updates.shape
    Wp, Dp = pending.shape
    upd = jnp.pad(updates, ((0, Wp - W), (0, Dp - D)))
    wp = jnp.pad(weights.astype(jnp.float32), (0, Wp - W))
    kp = jnp.pad(keep.astype(jnp.float32), (0, Wp - W))
    agg, newp = ref.fused_async_agg_ref(upd, pending, wp, kp)
    return agg[:D], newp


def fused_async_agg(updates, pending, weights, keep):
    """Pass 2 (async): see ``fused_async_agg_kernel``."""
    return on_tpu(fused_async_agg_kernel, _async_agg_ref,
                  updates, pending, weights, keep)


# -- exact HBM accounting (BlockSpec geometry) --------------------------------


def streamed_bytes(W: int, D: int, dtype, *, async_mode: bool = False):
    """Exact per-round HBM traffic of the fused chain, from the kernels'
    BlockSpec geometry (each index map visits every element exactly once
    per call). Returns {update_read, other, total} in bytes."""
    isz = jnp.dtype(dtype).itemsize
    upd = W * D * isz
    st = trust_score.tiles(W, D, isz)
    stats_sweeps = 1 if st.nw == 1 else 2          # W-tiled: sums, then stats
    n_d = -(-D // st.bd)
    stats_out = (2 * n_d * st.w_pad + D) * 4      # partial rows, consensus
    update_read = (stats_sweeps + 1) * upd        # stats sweeps + agg pass
    if async_mode:
        Wp, Dp = pending_shape(W, D)
        other = (Wp * Dp * 4) * 2 + Dp * 4 \
            + (2 * Wp) * 4 + stats_out            # pending r/w, agg, rows
    else:
        other = D * 4 + W * 4 + stats_out         # aggregate out, weights
    return {"update_read": float(update_read), "other": float(other),
            "total": float(update_read + other)}


def update_passes(W: int, D: int, dtype, *, async_mode: bool = False
                  ) -> float:
    """How many times the fused chain streams the W×D update volume
    (the benchmark/CI gate asserts ≤ 2)."""
    isz = jnp.dtype(dtype).itemsize
    return streamed_bytes(W, D, dtype,
                          async_mode=async_mode)["update_read"] / (W * D * isz)
