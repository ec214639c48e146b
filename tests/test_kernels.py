"""Per-kernel allclose vs the pure-jnp oracles (interpret mode on CPU),
with hypothesis shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

jax.config.update("jax_enable_x64", False)


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# trust_agg
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(
    w=st.integers(2, 24),
    d=st.integers(1, 6000),
    dtype=st.sampled_from(["float32", "bfloat16"]),
    block_d=st.sampled_from([256, 1024, 2048]),
)
def test_trust_agg_sweep(w, d, dtype, block_d):
    key = jax.random.PRNGKey(w * 10007 + d)
    u = _rand(key, (w, d), jnp.dtype(dtype))
    wt = jax.random.uniform(jax.random.fold_in(key, 1), (w,))
    out = ops._trust_agg(u, wt, block_d=block_d, interpret=True)
    expect = ref.trust_agg_ref(u, wt)
    tol = 2e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("kernel", ["trust_agg", "trust_score"])
def test_w_tiled_kernels_match_ref(kernel, dtype):
    """Cohorts too wide for one VMEM strip tile W: three 128-row tiles
    (the last one padded) over ragged D tiles must match the oracles."""
    W, D = 300, 1100
    key = jax.random.PRNGKey(5)
    u = _rand(key, (W, D), jnp.dtype(dtype))
    tol = 1e-4 if dtype == "float32" else 5e-2
    if kernel == "trust_agg":
        wt = jax.random.uniform(jax.random.fold_in(key, 1), (W,))
        got = [ops._trust_agg(u, wt, block_w=128, block_d=512,
                              interpret=True)]
        expect = [ref.trust_agg_ref(u, wt)]
    else:
        got = ops._trust_score_stats(u, block_w=128, block_d=512,
                                     interpret=True)
        expect = ref.trust_score_ref(u)
    for g, e in zip(got, expect):
        np.testing.assert_allclose(np.asarray(g), np.asarray(e),
                                   rtol=tol, atol=tol * np.sqrt(D))


def test_trust_agg_matches_pytree_helper():
    key = jax.random.PRNGKey(0)
    tree = {"a": jax.random.normal(key, (4, 3, 700)),
            "b": jax.random.normal(jax.random.fold_in(key, 1), (4, 2048))}
    wt = jnp.array([0.1, 0.2, 0.3, 0.4])
    out = ops.aggregate_pytree(tree, wt)
    for k in tree:
        expect = ref.trust_agg_ref(tree[k].reshape(4, -1), wt).reshape(
            tree[k].shape[1:])
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(expect),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# trust_score
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(
    w=st.integers(2, 20),
    d=st.integers(2, 5000),
    dtype=st.sampled_from(["float32", "bfloat16"]),
)
def test_trust_score_sweep(w, d, dtype):
    key = jax.random.PRNGKey(w * 31 + d)
    u = _rand(key, (w, d), jnp.dtype(dtype))
    dot, squ, sqc = ops._trust_score_stats(u, interpret=True)
    rd, rs, rc = ref.trust_score_ref(u)
    tol = 1e-4 if dtype == "float32" else 5e-2
    np.testing.assert_allclose(np.asarray(dot), np.asarray(rd), rtol=tol, atol=tol * d)
    np.testing.assert_allclose(np.asarray(squ), np.asarray(rs), rtol=tol, atol=tol * d)
    np.testing.assert_allclose(np.asarray(sqc), np.asarray(rc), rtol=tol, atol=tol * d)


# ---------------------------------------------------------------------------
# swa_decode
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(
    b=st.integers(1, 3),
    kv=st.sampled_from([1, 2, 4]),
    g=st.sampled_from([1, 2, 4]),
    hd=st.sampled_from([64, 128]),
    nblocks=st.integers(2, 6),
    dtype=st.sampled_from(["float32", "bfloat16"]),
    data=st.data(),
)
def test_swa_decode_sweep(b, kv, g, hd, nblocks, dtype, data):
    block_s = 256
    S = nblocks * block_s
    window = data.draw(st.sampled_from([block_s, 2 * block_s, S]))
    cur = data.draw(st.integers(0, S - 1))
    H = kv * g
    key = jax.random.PRNGKey(b * 100 + kv * 10 + g + hd + nblocks)
    dt = jnp.dtype(dtype)
    q = _rand(key, (b, H, hd), dt)
    kc = _rand(jax.random.fold_in(key, 1), (b, S, kv, hd), dt)
    vc = _rand(jax.random.fold_in(key, 2), (b, S, kv, hd), dt)
    out = ops._swa_decode(q, kc, vc, cur, window=window, block_s=block_s,
                          interpret=True)
    expect = ref.swa_decode_ref(q, kc, vc, cur, window)
    tol = 2e-4 if dtype == "float32" else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=tol, atol=tol)


def test_swa_decode_matches_model_decode_attention():
    """The kernel must agree with the model's jnp decode attention path."""
    from repro.models.layers import decode_attention
    key = jax.random.PRNGKey(7)
    B, H, KV, hd, S, win = 2, 8, 2, 64, 1024, 512
    q = jax.random.normal(key, (B, 1, H, hd))
    kc = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KV, hd))
    vc = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KV, hd))
    for cur in [5, 511, 600, 1023]:
        a = decode_attention(q, kc, vc, cur_index=cur, window=win)[:, 0]
        b = ops._swa_decode(q[:, 0], kc, vc, cur, window=win, block_s=256,
                            interpret=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# ssd_scan (fused SSD chunk recurrence)
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(
    b=st.integers(1, 2),
    h=st.integers(1, 3),
    dk=st.sampled_from([8, 16]),
    dv=st.sampled_from([8, 16]),
    nc=st.integers(2, 4),
    chunk=st.sampled_from([16, 32]),
    dtype=st.sampled_from(["float32", "bfloat16"]),
)
def test_ssd_scan_sweep(b, h, dk, dv, nc, chunk, dtype):
    from repro.kernels.ssd_scan import ssd_scan
    from repro.models.ssm import chunked_decay_attention
    S = nc * chunk
    dt = jnp.dtype(dtype)
    key = jax.random.PRNGKey(b * 1000 + h * 100 + dk + dv + nc + chunk)
    q = _rand(key, (b, S, h, dk), dt)
    k = _rand(jax.random.fold_in(key, 1), (b, S, h, dk), dt)
    v = _rand(jax.random.fold_in(key, 2), (b, S, h, dv), dt)
    a = -jax.random.uniform(jax.random.fold_in(key, 3), (b, S, h)) * 0.4
    i = jax.random.uniform(jax.random.fold_in(key, 4), (b, S, h))
    out = ssd_scan(q, k, v, a.astype(dt), i.astype(dt), chunk=chunk,
                   interpret=True)
    ref_out = chunked_decay_attention(q, k, v, a, i, chunk=chunk)
    tol = 3e-4 if dtype == "float32" else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref_out, np.float32),
                               rtol=tol, atol=tol)


def test_ssd_scan_matches_naive_recurrence():
    """End-to-end: kernel == strict sequential recurrence (not just the
    chunked jnp path)."""
    from repro.kernels.ssd_scan import ssd_scan
    from repro.models.ssm import decay_attention_step
    key = jax.random.PRNGKey(0)
    B, S, H, dk, dv = 1, 64, 2, 8, 4
    q = jax.random.normal(key, (B, S, H, dk))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, dk))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, H, dv))
    a = -jax.random.uniform(jax.random.fold_in(key, 3), (B, S, H)) * 0.3
    i = jnp.ones((B, S, H))
    out = ssd_scan(q, k, v, a, i, chunk=16, interpret=True)
    state = jnp.zeros((B, H, dk, dv))
    ys = []
    for t in range(S):
        y, state = decay_attention_step(q[:, t], k[:, t], v[:, t],
                                        a[:, t], i[:, t], state)
        ys.append(y)
    ref_out = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=2e-4, atol=2e-4)
