"""Model-component correctness: blocked attention vs direct softmax, SSD
chunked scan vs naive recurrence, MoE gather vs dense oracle, sliding
window masks, RoPE properties."""

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import example, given, settings, strategies as st

from repro.configs.base import MoEConfig
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as S


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def _naive_attention(q, k, v, causal=True, window=0):
    B, Sq, H, hd = q.shape
    KV, vd = k.shape[2], v.shape[-1]
    G = H // KV
    qs = q.reshape(B, Sq, KV, G, hd).astype(jnp.float32) / np.sqrt(hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qs, k.astype(jnp.float32))
    pos = jnp.arange(Sq)
    mask = jnp.ones((Sq, Sq), bool)
    if causal:
        mask &= pos[None, :] <= pos[:, None]
    if window:
        mask &= (pos[:, None] - pos[None, :]) < window
    s = jnp.where(mask[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, vd)


@settings(max_examples=10, deadline=None)
@given(seq=st.sampled_from([128, 256]), kv_chunk=st.sampled_from([32, 64]),
       window=st.sampled_from([0, 48]), seed=st.integers(0, 100),
       vd=st.sampled_from([32, 16]))
@example(seq=128, kv_chunk=32, window=0, seed=3, vd=16)     # v narrower
@example(seq=128, kv_chunk=128, window=0, seed=4, vd=16)    # than q and k
def test_blocked_attention_matches_naive(seq, kv_chunk, window, seed, vd):
    key = jax.random.PRNGKey(seed)
    B, H, KV, hd = 2, 4, 2, 32
    q = jax.random.normal(key, (B, seq, H, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, seq, KV, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, seq, KV, vd))
    pos = jnp.arange(seq)
    out = L.blocked_attention(q, k, v, q_positions=pos, kv_positions=pos,
                              causal=True, window=window, kv_chunk=kv_chunk)
    expect = _naive_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# decay attention (SSD core): chunked == naive sequential recurrence
# ---------------------------------------------------------------------------

def _naive_decay_attention(q, k, v, a, i):
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    h = np.zeros((B, H, dk, dv), np.float64)
    out = np.zeros((B, T, H, dv), np.float64)
    qn, kn, vn = np.asarray(q, np.float64), np.asarray(k, np.float64), np.asarray(v, np.float64)
    an, inn = np.asarray(a, np.float64), np.asarray(i, np.float64)
    for t in range(T):
        h = h * np.exp(an[:, t])[..., None, None] + \
            inn[:, t][..., None, None] * kn[:, t][..., :, None] * vn[:, t][..., None, :]
        out[:, t] = np.einsum("bhd,bhdv->bhv", qn[:, t], h)
    return out


@settings(max_examples=8, deadline=None)
@given(chunk=st.sampled_from([8, 16, 32]), T=st.sampled_from([32, 64]),
       seed=st.integers(0, 50))
def test_chunked_decay_attention_matches_recurrence(chunk, T, seed):
    key = jax.random.PRNGKey(seed)
    B, H, dk, dv = 2, 3, 8, 5
    q = jax.random.normal(key, (B, T, H, dk))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, T, H, dk))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, T, H, dv))
    a = -jax.random.uniform(jax.random.fold_in(key, 3), (B, T, H)) * 0.5
    i = jax.random.uniform(jax.random.fold_in(key, 4), (B, T, H))
    out = S.chunked_decay_attention(q, k, v, a, i, chunk=chunk)
    expect = _naive_decay_attention(q, k, v, a, i)
    np.testing.assert_allclose(np.asarray(out, np.float64), expect,
                               rtol=1e-3, atol=1e-3)


def test_decay_attention_step_streams_like_chunked():
    """Prefill state hand-off: chunked(T) == chunked(T/2) + steps."""
    key = jax.random.PRNGKey(0)
    B, T, H, dk, dv = 1, 16, 2, 4, 3
    q = jax.random.normal(key, (B, T, H, dk))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, T, H, dk))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, T, H, dv))
    a = -jax.random.uniform(jax.random.fold_in(key, 3), (B, T, H)) * 0.3
    i = jnp.ones((B, T, H))
    full = S.chunked_decay_attention(q, k, v, a, i, chunk=4)
    half, state = S.chunked_decay_attention(
        q[:, :8], k[:, :8], v[:, :8], a[:, :8], i[:, :8], chunk=4,
        return_state=True)
    outs = [half]
    for t in range(8, T):
        y, state = S.decay_attention_step(q[:, t], k[:, t], v[:, t],
                                          a[:, t], i[:, t], state)
        outs.append(y[:, None])
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, 1)),
                               np.asarray(full), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# MoE: gather (capacity) impl == dense mask oracle when nothing drops
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(e=st.sampled_from([4, 8]), k=st.sampled_from([1, 2]),
       seed=st.integers(0, 50))
def test_moe_gather_matches_dense(e, k, seed):
    key = jax.random.PRNGKey(seed)
    d, f, B, Sq = 16, 32, 2, 24
    cfg = MoEConfig(num_experts=e, top_k=k, d_ff_expert=f,
                    capacity_factor=float(e) / k)   # no drops
    params, _ = MOE.init_moe(key, d, cfg, tp=1, dtype=jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (B, Sq, d))
    out_g, aux_g = MOE.apply_moe(params, x, cfg, impl="gather")
    out_d, aux_d = MOE.apply_moe(params, x, cfg, impl="dense")
    np.testing.assert_allclose(np.asarray(out_g), np.asarray(out_d),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(float(aux_g), float(aux_d), rtol=1e-5)


def test_moe_padded_experts_never_selected():
    key = jax.random.PRNGKey(0)
    cfg = MoEConfig(num_experts=3, top_k=2, d_ff_expert=8)
    params, _ = MOE.init_moe(key, 8, cfg, tp=4, dtype=jnp.float32)
    assert params["router"].shape[1] == 4        # padded to tp multiple
    x = jax.random.normal(key, (1, 16, 8))
    probs, _ = MOE._router_probs(params, x.reshape(16, 8), cfg)
    assert float(jnp.max(probs[:, 3])) < 1e-12   # pad expert masked


def test_moe_capacity_drops_are_bounded():
    """With cf=1.0 and adversarially-uniform tokens, outputs stay finite and
    dropped tokens fall back to shared/zero path."""
    key = jax.random.PRNGKey(0)
    cfg = MoEConfig(num_experts=4, top_k=2, d_ff_expert=8,
                    num_shared_experts=1, d_ff_shared=8, capacity_factor=1.0)
    params, _ = MOE.init_moe(key, 8, cfg, tp=1, dtype=jnp.float32)
    x = jnp.ones((2, 32, 8))
    out, aux = MOE.apply_moe(params, x, cfg)
    assert np.isfinite(np.asarray(out)).all()


# ---------------------------------------------------------------------------
# RoPE / norms
# ---------------------------------------------------------------------------

def test_rope_preserves_norm_and_relative_phase():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (1, 8, 2, 16))
    pos = jnp.arange(8)
    r = L.apply_rope(x, pos, 10_000.0)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(r), axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-5)
    # dot(q_m, k_n) depends only on m - n
    q = jax.random.normal(jax.random.fold_in(key, 1), (1, 1, 1, 16))
    k = jax.random.normal(jax.random.fold_in(key, 2), (1, 1, 1, 16))
    def dot_at(m, n):
        qm = L.apply_rope(q, jnp.array([m]), 1e4)
        kn = L.apply_rope(k, jnp.array([n]), 1e4)
        return float(jnp.sum(qm * kn))
    np.testing.assert_allclose(dot_at(5, 3), dot_at(9, 7), rtol=1e-4)


def test_rms_norm_unit_scale():
    x = jnp.full((2, 4, 8), 3.0)
    out = L.rms_norm(x, jnp.ones((8,)))
    np.testing.assert_allclose(np.asarray(out), 1.0, rtol=1e-4)


def test_slstm_state_streaming_matches_batch():
    """sLSTM full-sequence pass == two streamed halves."""
    key = jax.random.PRNGKey(0)
    d, H, B, T = 32, 4, 2, 12
    params, _ = S.init_slstm(key, d, H, tp=1, dtype=jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (B, T, d)) * 0.5
    full = S.apply_slstm(params, x, H)
    first, carry = S.apply_slstm(params, x[:, :6], H, return_state=True)
    second, _ = S.apply_slstm(params, x[:, 6:], H, carry=carry)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([first, second], 1)),
                               np.asarray(full), rtol=2e-4, atol=2e-4)
