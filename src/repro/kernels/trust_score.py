"""Pallas TPU kernel: fused per-worker trust statistics.

One HBM sweep over the (W, D) update matrix produces, against the consensus
c = mean_w u_w:

    dot[w] = <u_w, c>      sq_u[w] = ‖u_w‖²      sq_c = ‖c‖²

i.e. everything ``EvaluatePerformance`` needs for the cosine + norm terms,
without W+2 separate reductions. The consensus tile is recomputed in-VMEM
from the column strip (a row sum over the strip) — cheaper than a second
HBM stream of c.

Tiling (``tiles``): a full-W column strip (W, BD) streams through VMEM per
grid step, BD as wide as the scoped-VMEM budget allows. The body walks the
strip in 128-row chunks, so its f32 temporaries stay O(128·BD) whatever W
is. Where even a 128-lane strip does not fit (W > 10.6k f32, > 19.3k bf16
at the 12 MiB budget), W is tiled too and each strip is swept twice: once
for the column sums, once for the statistics. Each grid step writes its own (1, BW) partial rows, summed over
the D tiles outside the kernel, so no output is revisited out of order.
The last D tile may run past D: its stray lanes are zeroed, and the matrix
is never padded along D.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tpu import Tiles, plan_tiles

ROWS = 128      # rows per in-kernel chunk (a multiple of the lane width)


def vmem_bytes(bw: int, bd: int, itemsize: int) -> int:
    """VMEM the kernel holds at tile (bw, bd): the double-buffered input
    tile, one chunk's f32 temporaries (upcast, u·c, u·u), the
    double-buffered (1, bw) dot/sq_u partial rows and the (1, bd)
    consensus output + column-sum scratch, each padded to 8 sublanes."""
    rows = min(bw, ROWS)
    return (2 * bw * bd * itemsize + 3 * rows * bd * 4
            + 2 * 2 * 8 * bw * 4 + 3 * 8 * bd * 4)


def tiles(W: int, D: int, itemsize: int) -> Tiles:
    return plan_tiles(W, D, lambda bw, bd: vmem_bytes(bw, bd, itemsize),
                      row_align=ROWS)


def _kernel(upd_ref, dot_ref, squ_ref, con_ref, csum_ref, *, W, D,
            two_sweeps):
    # grid indices are read here, outside every nested body
    d, sweep, wi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    bw, bd = upd_ref.shape
    rows = min(bw, ROWS)
    n_chunks = bw // rows

    def chunk_rows(r):
        if n_chunks == 1:        # one chunk: static, any row count
            return pl.ds(0, rows)
        return pl.ds(pl.multiple_of(r * rows, rows), rows)

    def for_chunks(body, init):
        if n_chunks == 1:
            return body(0, init)
        return jax.lax.fori_loop(0, n_chunks, body, init)

    def chunk(r):
        u = upd_ref[chunk_rows(r), :].astype(jnp.float32)
        if D % bd:   # the last D tile reads past the matrix: zero those lanes
            col = d * bd + jax.lax.broadcasted_iota(jnp.int32, (1, bd), 1)
            u = jnp.where(col < D, u, 0.0)
        return u

    def sum_columns():
        @pl.when(wi == 0)
        def _init():
            csum_ref[...] = jnp.zeros_like(csum_ref)

        csum_ref[...] += for_chunks(
            lambda r, acc: acc + jnp.sum(chunk(r), axis=0, keepdims=True),
            jnp.zeros((1, bd), jnp.float32))

    def statistics():
        c = csum_ref[...] * (1.0 / W)               # (1, BD) consensus tile
        con_ref[...] = c

        def body(r, carry):
            u = chunk(r)
            lanes = chunk_rows(r)
            dot_ref[:, lanes] = jnp.sum(u * c, axis=1)[None, :]
            squ_ref[:, lanes] = jnp.sum(u * u, axis=1)[None, :]
            return carry

        for_chunks(body, 0)

    if two_sweeps:
        pl.when(sweep == 0)(sum_columns)
        pl.when(sweep == 1)(statistics)
    else:
        sum_columns()
        statistics()


@functools.partial(jax.jit,
                   static_argnames=("block_w", "block_d", "interpret"))
def trust_score_stats(updates: jax.Array, *, block_w: int | None = None,
                      block_d: int | None = None, interpret: bool = False):
    """updates: (W, D) -> (dot (W,), sq_u (W,), sq_c ()) in f32.

    ``block_w``/``block_d`` override the planned tile (tests use them to
    reach the W-tiled geometry at small sizes); ``block_w`` must be W or
    a multiple of 128."""
    W, D = updates.shape
    t = tiles(W, D, jnp.dtype(updates.dtype).itemsize)
    if block_w is not None or block_d is not None:
        bw = block_w or t.bw
        t = Tiles(bw=bw, bd=block_d or t.bd, nw=-(-W // bw))
    assert t.bw % ROWS == 0 or (t.nw == 1 and t.bw <= ROWS), t
    if t.w_pad != W:
        updates = jnp.pad(updates, ((0, t.w_pad - W), (0, 0)))
    n_d = -(-D // t.bd)
    sweeps = 1 if t.nw == 1 else 2

    dot, squ, con = pl.pallas_call(
        functools.partial(_kernel, W=W, D=D, two_sweeps=sweeps == 2),
        grid=(n_d, sweeps, t.nw),
        in_specs=[pl.BlockSpec((t.bw, t.bd), lambda d, s, w: (w, d),
                               memory_space=pltpu.VMEM)],
        out_specs=[
            # the first sweep of a two-sweep strip writes nothing: park its
            # partial rows on the block the second sweep starts with
            pl.BlockSpec((None, 1, t.bw), lambda d, s, w: (d, 0, w * s),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((None, 1, t.bw), lambda d, s, w: (d, 0, w * s),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, t.bd), lambda d, s, w: (0, d),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_d, 1, t.w_pad), jnp.float32),
            jax.ShapeDtypeStruct((n_d, 1, t.w_pad), jnp.float32),
            jax.ShapeDtypeStruct((1, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, t.bd), jnp.float32)],
        interpret=interpret,
        name="trust_score_stats",
    )(updates)
    return (jnp.sum(dot[:, 0, :W], axis=0), jnp.sum(squ[:, 0, :W], axis=0),
            jnp.sum(con * con))
