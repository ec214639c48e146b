"""Pallas TPU kernel: trust-weighted aggregation of W worker updates.

The cluster-head hot loop — ``out[d] = Σ_w weights[w] · updates[w, d]`` over
the flattened update matrix. One HBM pass over the (W, D) matrix instead of
W separate accumulations: a (1, BW) × (BW, BD) MXU matmul per VMEM tile,
accumulated over the W tiles of each D tile (``tiles``: one full-W tile
where the scoped-VMEM budget allows). The last D tile may run past D; the
stray lanes land in output columns that are never written back, so the
matrix is never padded along D.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tpu import LANE, Tiles, plan_tiles


def vmem_bytes(bw: int, bd: int, itemsize: int) -> int:
    """VMEM the kernel holds at tile (bw, bd): the double-buffered input
    tile, its f32 upcast, and the double-buffered (1, bw) weight row and
    (1, bd) output (each padded to 8 sublanes) plus the (1, bd) partial."""
    return (2 * bw * bd * itemsize + bw * bd * 4
            + 2 * 8 * bw * 4 + 3 * 8 * bd * 4)


def tiles(W: int, D: int, itemsize: int) -> Tiles:
    return plan_tiles(W, D, lambda bw, bd: vmem_bytes(bw, bd, itemsize),
                      row_align=LANE)


def _kernel(w_ref, upd_ref, out_ref):
    # w_ref: (1, BW) f32 ; upd_ref: (BW, BD) ; out_ref: (1, BD) f32
    part = jnp.dot(w_ref[...], upd_ref[...].astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = part

    @pl.when(pl.program_id(1) > 0)
    def _acc():
        out_ref[...] += part


@functools.partial(jax.jit,
                   static_argnames=("block_w", "block_d", "interpret"))
def trust_agg(updates: jax.Array, weights: jax.Array, *,
              block_w: int | None = None, block_d: int | None = None,
              interpret: bool = False) -> jax.Array:
    """updates: (W, D) any float dtype; weights: (W,) -> (D,) f32.

    ``block_w``/``block_d`` override the planned tile (tests use them to
    reach the W-tiled geometry at small sizes); ``block_w`` must be W or
    a multiple of 128. Padded rows carry zero weight."""
    W, D = updates.shape
    t = tiles(W, D, jnp.dtype(updates.dtype).itemsize)
    if block_w is not None or block_d is not None:
        bw = block_w or t.bw
        t = Tiles(bw=bw, bd=block_d or t.bd, nw=-(-W // bw))
    assert t.bw % LANE == 0 or (t.nw == 1 and t.bw <= LANE), t
    if t.w_pad != W:
        updates = jnp.pad(updates, ((0, t.w_pad - W), (0, 0)))
    w_row = jnp.pad(weights.astype(jnp.float32),
                    (0, t.w_pad - W)).reshape(1, t.w_pad)

    out = pl.pallas_call(
        _kernel,
        grid=(-(-D // t.bd), t.nw),          # W tiles innermost: accumulate
        in_specs=[
            pl.BlockSpec((1, t.bw), lambda d, w: (0, w),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((t.bw, t.bd), lambda d, w: (w, d),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, t.bd), lambda d, w: (0, d),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, D), jnp.float32),
        interpret=interpret,
        name="trust_agg",
    )(w_row, updates)
    return out[0]
