"""moe_roofline.moonlight: the held experts' grouped products' share of
their roofline, in the moonlight-16b-a3b cell (moves samples_per_s). The
FLOPs the round's token-expert pairs need (the configuration's
``held_expert_flops`` per pair, times the program's ``moe.pairs_held``
counter of each traced round) over the chip's bf16 peak, over the summed
device time of the grouped products in the trace. At these sizes the
products are bound by compute: a held expert's pairs, ~3,000 rows of
2,048, reuse each weight ~3,000 times, far above the v5e's ~240 FLOPs a
byte of HBM.

On a TPU (JAX 0.9) ``jax.lax.ragged_dot`` runs as Mosaic kernels named
``ragged-dot-*`` on the ``XLA Ops`` line: the products and the small
kernel that lays out their groups. A program without the counter (one
that holds no expert share) leaves this reader silent."""
GROUPED_PRODUCTS = r"^%ragged-dot"


def read(run):
    if run.trace is None:
        return None
    pairs = [getattr(r, "counters", {}).get("moe.pairs_held")
             for r in run.traced_rounds]
    if not pairs or None in pairs:
        return None
    t = run.trace.op_s(GROUPED_PRODUCTS)
    if t <= 0:
        return None
    cell = run.cell
    flops = cell.model.held_expert_flops(cell.config, cell.traffic) \
        * sum(pairs)
    return 100.0 * flops / (t * run.peaks["bf16_flops_per_s"])
