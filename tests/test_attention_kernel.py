"""The causal splash attention kernel (``repro.kernels.attention``) in
interpret mode against a naive f32 causal attention: the forward and the
gradients of q, k and v, with a leading silo axis under ``jax.vmap`` as
the federated round takes it.

The kernel reads bf16 operands and accumulates in f32, so the forward
holds to 1e-2 absolute (outputs of order 1) and the gradients to 1e-2
relative in norm: bf16 rounding of the inputs and of P and dS before
their products, measured at 0.2-0.5%.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import attention as A


def naive(q, k, v):
    """(B, S, H, hd) causal softmax attention in f32."""
    S, hd = q.shape[1], q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(hd)
    s = jnp.where(np.tril(np.ones((S, S), bool)), s, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1),
                      v.astype(jnp.float32))


@pytest.mark.parametrize("S", [256, 512])
@pytest.mark.parametrize("hd,vd", [(192, 128), (64, 64)],
                         ids=["qk192-v128", "qk64-v64"])
def test_kernel_matches_naive_causal_attention(S, hd, vd):
    silos, B, H = 2, 1, 2
    ks = jax.random.split(jax.random.PRNGKey(S + hd), 4)
    q, k = (jax.random.normal(x, (silos, B, S, H, hd), jnp.bfloat16)
            for x in ks[:2])
    v = jax.random.normal(ks[2], (silos, B, S, H, vd), jnp.bfloat16)
    ct = jax.random.normal(ks[3], (silos, B, S, H, vd), jnp.float32)

    def kernel(q, k, v):
        return A.causal_attention(q, k, v, interpret=True)

    got = jax.vmap(kernel)(q, k, v)
    assert got.shape == (silos, B, S, H, vd) and got.dtype == jnp.bfloat16
    want = jax.vmap(naive)(q, k, v)
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) < 1e-2

    def grads(f):
        return jax.grad(lambda *a: jnp.sum(
            jax.vmap(f)(*a).astype(jnp.float32) * ct), (0, 1, 2))(q, k, v)
    for g, w in zip(grads(kernel), grads(naive)):
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        assert float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w)) < 1e-2


@pytest.mark.parametrize("S,engaged", [
    (256, True), (8192, True), (128, False), (320, False)])
def test_kernel_engages_on_tiled_lengths(S, engaged):
    assert A.fits(S) is engaged
    assert S % A.block(S) == 0 or not engaged


def test_block_rule():
    """Two blocks or more: 128 at S = 256, 256 at S = 512, at most
    ``BLOCK``."""
    assert [A.block(S) for S in (256, 512, 2048, 8192)] == [128, 256, 1024,
                                                           A.BLOCK]
