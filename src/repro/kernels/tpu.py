"""What the Pallas kernels need to know about the chip they compile for.

``on_tpu``
    Per-lowering dispatch: a kernel where the program is lowered for a TPU,
    its reference everywhere else. The platform the program is compiled for
    decides, not the process's default backend, so importing ``repro``
    starts no backend and a compile for a described (unattached) TPU keeps
    the kernel.

``plan_tiles``
    Tile geometry for kernels that stream a (W, D) matrix, sized against
    the chip's scoped VMEM: the largest lane-aligned D tile at which a
    full-W column strip fits, or, where even a one-lane strip does not,
    W tiles as well.
"""
from __future__ import annotations

import os
from typing import Callable, NamedTuple

import jax

LANE = 128
MAX_BLOCK_D = 2048
# v5e's default scoped-VMEM limit is 16 MiB; the kernels' own accounting
# (pipeline buffers + f32 temporaries) stays under 12 MiB so Mosaic's
# internal scratch and layout padding keep 4 MiB.
VMEM_BUDGET = 12 * 1024 * 1024


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def on_tpu(kernel: Callable, reference: Callable, *args):
    """``kernel(*args)`` when lowered for a TPU, ``reference(*args)``
    otherwise. ``SDFLB_FUSED_INTERPRET=1`` sends the non-TPU branch
    through the interpreted kernel instead (``kernel`` is then called with
    ``interpret=True``), so CPU runs can exercise the kernel bodies end to
    end."""
    if os.environ.get("SDFLB_FUSED_INTERPRET", "") == "1":
        def reference(*a):
            return kernel(*a, interpret=True)
    return jax.lax.platform_dependent(*args, tpu=kernel, default=reference)


class Tiles(NamedTuple):
    bw: int      # rows per tile (== padded W when ``nw == 1``)
    bd: int      # lanes per tile
    nw: int      # W tiles; the padded cohort is ``nw * bw`` rows

    @property
    def w_pad(self) -> int:
        return self.nw * self.bw


def plan_tiles(W: int, D: int, vmem: Callable[[int, int], int], *,
               row_align: int) -> Tiles:
    """Geometry for a kernel whose tile (bw, bd) holds ``vmem(bw, bd)``
    bytes of VMEM. Prefers one full-W strip (bw = W, rounded up to
    ``row_align`` when W exceeds it) at the widest D tile that fits the
    budget; where even a 128-lane strip does not fit, tiles W into
    ``row_align``-aligned blocks at a 512-lane D tile."""
    d_cap = min(MAX_BLOCK_D, round_up(D, LANE))
    bw = W if W <= row_align else round_up(W, row_align)
    for bd in range(d_cap, 0, -LANE):
        if vmem(bw, bd) <= VMEM_BUDGET:
            return Tiles(bw=bw, bd=bd, nw=1)
    bd = min(4 * LANE, d_cap)
    bw_max = row_align
    while vmem(bw_max + row_align, bd) <= VMEM_BUDGET:
        bw_max += row_align
    # fewest W tiles, allowing up to twice that many where it pads less
    chunks, per_tile = -(-W // row_align), bw_max // row_align
    least = -(-chunks // per_tile)
    nw = min(range(least, 2 * least + 1),
             key=lambda n: (n * -(-chunks // n), n))
    return Tiles(bw=row_align * -(-chunks // nw), bd=bd, nw=nw)
