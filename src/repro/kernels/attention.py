"""Causal self-attention on the TPU: the bundled splash (flash) attention
kernel, for MLA's training path.

``causal_attention(q, k, v)``
    q, k (B, S, H, dqk), v (B, S, H, dv) -> (B, S, H, dv), each query
    attending to itself and every earlier position. q is scaled by
    1/sqrt(dqk) in its own dtype. The score products and the backward's
    products take bf16 operands and accumulate in f32; the forward's
    probabilities meet v in f32; the softmax statistics are f32. The kernel
    (``jax.experimental.pallas.ops.tpu.splash_attention``) walks only the
    (query block, key block) tiles under the diagonal, keeps its running
    max, sum and output in VMEM, takes v at its own head size, and has
    its own backward (a dq kernel and a dk/dv kernel that recompute the
    scores from the saved log-sum-exp).

``fits(S)``
    Whether the block rule tiles causal self-attention over positions
    0..S-1 into more than one block. The kernel takes no positions: the
    caller engages it only for that attention, and only where the
    partitioner does not split the program over several devices (a Mosaic
    kernel cannot be partitioned automatically).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as splash

# The tile edge: query and key blocks of 1024 in every kernel. Timed on a
# TPU v5e at four silos x 8,192 tokens x 16 heads (q and k heads of 192, v of
# 128), forward / forward and backward: 1024 x 1024 tiles 16.6 / 59.4 ms,
# 1024 queries x 512 keys 17.9 / 66.5, 512 x 512 18.7 / 67.5, 256 x 256
# 38.7 / 117.7. Fewer, larger tiles cut the per-step overhead and the
# re-reads of k and v; 2048-row query tiles overflow VMEM at head 192.
BLOCK = 1024


def block(S: int) -> int:
    """The block edge for sequence length ``S``: ``BLOCK``, or the largest
    power-of-two multiple of 128 below it that leaves two blocks."""
    return min(BLOCK, max(128, 1 << (S // 2).bit_length() - 1))


def fits(S: int) -> bool:
    b = block(S)
    return S % b == 0 and S > b


def causal_attention(q, k, v, *, interpret=False):
    B, S, H, hd = q.shape
    b = block(S)
    blocks = splash.BlockSizes(block_q=b, block_kv=b, block_kv_compute=b,
                               block_q_dkv=b, block_kv_dkv=b,
                               block_kv_dkv_compute=b,
                               block_q_dq=b, block_kv_dq=b)
    mask = splash.MultiHeadMask([splash.CausalMask((S, S))] * H)
    kernel = splash.make_splash_mha(mask, block_sizes=blocks, head_shards=1,
                                    q_seq_shards=1, interpret=interpret)
    heads_major = lambda x: jnp.swapaxes(x, 1, 2)      # (B, H, S, .)
    o = jax.vmap(kernel)(heads_major(q * (1.0 / math.sqrt(hd))),
                         heads_major(k), heads_major(v))
    return heads_major(o)
