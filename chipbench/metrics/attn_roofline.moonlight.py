"""attn_roofline.moonlight: MLA's causal training attention's share of its
roofline, in the moonlight-16b-a3b cell (moves samples_per_s, here
tokens). The FLOPs the traced rounds' tokens need of causal attention
(per token and layer the score and value products over half the
sequence, 2 * (seq_len / 2) * heads * (qk + v head dims), taken once in
the step's forward, twice in its backward and once more in the forward of
the post-step loss where the job scores it) over the chip's bf16 peak,
over the summed device time of the attention kernels in the trace. The
kernels recompute more than this (the layer's remat forward, the scores
again in each backward kernel, the diagonal blocks whole), so the share
stays under 100%. Compute is the bound: each 1,024-row block of keys and
values fetched from HBM serves a resident block of 1,024 queries, ~1,000
FLOPs a byte, about four times the v5e's ~240 FLOPs a byte.

On a TPU (JAX 0.9) the bundled splash attention runs as Pallas kernels
named ``splash_mha_fwd_*`` (forward), ``splash_mha_dq_*`` and
``splash_mha_dkv_*`` (backward) on the ``XLA Ops`` line. A program that
runs attention another way (the blocked scan, on the chip before this
kernel) leaves this reader silent."""
ATTENTION_KERNELS = r"^%splash_mha_(fwd|dq|dkv)"


def attention_flops_per_token(c: dict, traffic: dict) -> int:
    passes = 3 + (traffic["protocol"]["w_loss"] > 0)
    head = c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"]
    return (passes * c["num_hidden_layers"] * 2 * (traffic["seq_len"] // 2)
            * c["num_attention_heads"] * head)


def read(run):
    if run.trace is None:
        return None
    t = run.trace.op_s(ATTENTION_KERNELS)
    if t <= 0:
        return None
    cell = run.cell
    flops = attention_flops_per_token(cell.config, cell.traffic) \
        * run.samples(run.traced_rounds)
    return 100.0 * flops / (t * run.peaks["bf16_flops_per_s"])
