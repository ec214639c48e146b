"""Fused device-resident trust round: flat-pack roundtrips, the async
Pallas kernel vs its jnp oracle, and property-tested equivalence of the
fused flat-pack path against the per-leaf reference — scores, penalization
weights, aggregates, and whole ``make_fl_round`` rounds (sync + async),
including the tamper case and the single-local-step loss-delta fix."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import FederationConfig, TrainConfig
from repro.configs.registry import get_config
from repro.core import async_agg, fl_step, hierarchy, trust
from repro.kernels import fused_round, ops, pack, ref, tpu, trust_agg, trust_score
from repro.models import api

jax.config.update("jax_enable_x64", False)


def _tree(key, W, dtype, sizes=((3, 70), (41,), (2, 5, 13))):
    ks = jax.random.split(key, len(sizes))
    return {f"l{i}": jax.random.normal(k, (W,) + s, jnp.float32).astype(dtype)
            for i, (k, s) in enumerate(zip(ks, sizes))}


def _template(tree):
    return jax.tree.map(lambda x: x[0], tree)


# ---------------------------------------------------------------------------
# pack: roundtrips + delta rule
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(w=st.integers(1, 17),
       dtype=st.sampled_from(["float32", "bfloat16"]),
       data=st.data())
def test_pack_roundtrip(w, dtype, data):
    nleaf = data.draw(st.integers(1, 4))
    sizes = tuple(tuple(data.draw(st.integers(1, 9))
                        for _ in range(data.draw(st.integers(1, 3))))
                  for _ in range(nleaf))
    tree = _tree(jax.random.PRNGKey(w), w, jnp.dtype(dtype), sizes)
    spec = pack.pack_spec(_template(tree))
    mat = pack.pack_stack(tree, spec)
    assert mat.shape == (w, spec.total) and mat.dtype == spec.dtype
    back = pack.unpack_stack(mat, spec)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(tree[k]))
    vec = pack.unpack_vector(mat[0], spec)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(vec[k]),
                                      np.asarray(tree[k][0]))


def test_pack_delta_matches_per_leaf_update_rule():
    """pack_delta must be bitwise the per-leaf rule:
    (new_f32 − global_f32).astype(param_dtype)."""
    for dtype in (jnp.float32, jnp.bfloat16):
        key = jax.random.PRNGKey(3)
        new_w = _tree(key, 5, dtype)
        g = _template(_tree(jax.random.fold_in(key, 1), 1, dtype))
        spec = pack.pack_spec(g)
        got = pack.pack_delta(new_w, g, spec)
        per_leaf = jax.tree.map(
            lambda a, b: (a.astype(jnp.float32)
                          - b.astype(jnp.float32)[None]).astype(a.dtype),
            new_w, g)
        expect = pack.pack_stack(per_leaf, spec)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(expect))


def test_pack_spec_static_and_shape_only():
    g = _template(_tree(jax.random.PRNGKey(0), 1, jnp.float32))
    spec = pack.pack_spec(g)
    assert spec.total == sum(spec.sizes)
    assert spec.offsets == tuple(np.cumsum((0,) + spec.sizes[:-1]))
    # shape-only: building from eval_shape structs gives the same layout
    spec2 = pack.pack_spec(jax.eval_shape(lambda t: t, g))
    assert spec2.shapes == spec.shapes and spec2.total == spec.total \
        and spec2.dtype == spec.dtype


def test_packable_rules():
    assert pack.packable({"a": jnp.zeros((2,), jnp.float32),
                          "b": jnp.zeros((3,), jnp.float32)})
    assert not pack.packable({"a": jnp.zeros((2,), jnp.float32),
                              "b": jnp.zeros((3,), jnp.bfloat16)})
    assert not pack.packable({"a": jnp.zeros((2,), jnp.int32)})
    assert not pack.packable({})


# ---------------------------------------------------------------------------
# the async fused kernel vs its jnp oracle (interpret mode)
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(w=st.integers(2, 40), d=st.integers(1, 3000),
       dtype=st.sampled_from(["float32", "bfloat16"]))
def test_async_kernel_matches_ref(w, d, dtype):
    key = jax.random.PRNGKey(w * 7919 + d)
    u = jax.random.normal(key, (w, d), jnp.float32).astype(jnp.dtype(dtype))
    wp, dp = fused_round.pending_shape(w, d)
    pend = jnp.zeros((wp, dp), jnp.float32).at[:w, :d].set(
        jax.random.normal(jax.random.fold_in(key, 1), (w, d)))
    wt = jax.random.uniform(jax.random.fold_in(key, 2), (w,))
    keep = (jax.random.uniform(jax.random.fold_in(key, 3), (w,))
            > 0.5).astype(jnp.float32)
    agg, newp = fused_round.fused_async_agg_kernel(u, pend, wt, keep,
                                                   interpret=True)
    upad = jnp.pad(u, ((0, wp - w), (0, dp - d)))
    ragg, rnewp = ref.fused_async_agg_ref(
        upad, pend, jnp.pad(wt, (0, wp - w)), jnp.pad(keep, (0, wp - w)))
    tol = 2e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(agg), np.asarray(ragg[:d]),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(newp), np.asarray(rnewp),
                               rtol=tol, atol=tol)
    # padded rows (keep=0 there) stay flushed: re-entrant rounds never
    # resurrect phantom workers
    assert not np.asarray(newp[w:]).any()


# ---------------------------------------------------------------------------
# fused chain vs the per-leaf reference (steps 3–5 of the round)
# ---------------------------------------------------------------------------

@settings(max_examples=12, deadline=None)
@given(w=st.sampled_from([2, 4, 33]),
       dtype=st.sampled_from(["float32", "bfloat16"]),
       masked=st.booleans())
def test_fused_matches_per_leaf_sync(w, dtype, masked):
    key = jax.random.PRNGKey(w * 131 + masked)
    upd = _tree(key, w, jnp.dtype(dtype))
    lb = jax.random.uniform(jax.random.fold_in(key, 1), (w,)) + 1.0
    la = lb - jax.random.uniform(jax.random.fold_in(key, 2), (w,))
    fed = FederationConfig(num_clusters=1, workers_per_cluster=w,
                           trust_threshold=0.3)
    mask = None
    if masked:
        mask = (jax.random.uniform(jax.random.fold_in(key, 3), (w,))
                > 0.4).astype(jnp.float32).at[0].set(1.0)

    stats_ref = trust.update_stats(upd, lb, la)
    scores_ref = trust.scores_from_stats(stats_ref, fed)
    weights_ref = trust.trust_weights(scores_ref, fed, participation=mask)
    agg_ref_t = hierarchy.aggregate_fused(upd, weights_ref)

    spec = pack.pack_spec(_template(upd))
    flat = pack.pack_stack(upd, spec)
    stats_f = trust.update_stats_flat(flat, lb, la)
    scores_f = trust.scores_from_stats(stats_f, fed)
    weights_f = trust.trust_weights(scores_f, fed, participation=mask)
    agg_f = pack.unpack_vector(ops.fused_agg(flat, weights_f), spec)

    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(scores_f), np.asarray(scores_ref),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(weights_f), np.asarray(weights_ref),
                               rtol=tol, atol=tol)
    for k in agg_f:
        np.testing.assert_allclose(
            np.asarray(agg_f[k], np.float32),
            np.asarray(agg_ref_t[k], np.float32), rtol=tol, atol=tol)


@settings(max_examples=10, deadline=None)
@given(w=st.sampled_from([2, 4, 33]),
       dtype=st.sampled_from(["float32", "bfloat16"]))
def test_fused_matches_per_leaf_async(w, dtype):
    """Async cohort round with staleness > 0 and a nonzero pending buffer:
    weights, aggregate, and flushed pending agree across paths."""
    key = jax.random.PRNGKey(w * 17)
    upd = _tree(key, w, jnp.dtype(dtype))
    lb = jax.random.uniform(jax.random.fold_in(key, 1), (w,)) + 1.0
    la = lb - 0.1
    fed = FederationConfig(num_clusters=1, workers_per_cluster=w,
                           trust_threshold=0.0, async_mode=True)
    mask = (jax.random.uniform(jax.random.fold_in(key, 2), (w,))
            > 0.5).astype(jnp.float32).at[0].set(1.0)
    staleness = jax.random.randint(jax.random.fold_in(key, 3), (w,), 0, 5)
    pending_t = jax.tree.map(
        lambda x: jax.random.normal(jax.random.fold_in(key, 4),
                                    x.shape, jnp.float32), upd)

    scores = trust.scores_from_stats(trust.update_stats(upd, lb, la), fed)
    agg_t, new_state_t, w_t = async_agg.async_round(
        upd, scores, mask, async_agg.AsyncState(staleness, pending_t), fed)

    spec = pack.pack_spec(_template(upd))
    flat = pack.pack_stack(upd, spec)
    wp, dp = fused_round.pending_shape(w, spec.total)
    pend_flat = jnp.zeros((wp, dp), jnp.float32).at[:w, :spec.total].set(
        pack.pack_stack(pending_t, spec, dtype=jnp.float32))
    scores_f = trust.scores_from_stats(
        trust.update_stats_flat(flat, lb, la), fed)
    w_f = async_agg.effective_weights(scores_f, mask, staleness, fed)
    agg_f, newp = ops.fused_async_agg(flat, pend_flat, w_f,
                                      1.0 - mask.astype(jnp.float32))

    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(np.asarray(w_f), np.asarray(w_t),
                               rtol=tol, atol=tol)
    agg_f_t = pack.unpack_vector(agg_f, spec)
    for k in agg_f_t:
        np.testing.assert_allclose(
            np.asarray(agg_f_t[k], np.float32),
            np.asarray(agg_t[k], np.float32), rtol=tol, atol=tol)
    newp_t = pack.unpack_stack(newp[:w, :spec.total], spec)
    for k in newp_t:
        np.testing.assert_allclose(
            np.asarray(newp_t[k]), np.asarray(new_state_t.pending[k]),
            rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# whole-round equivalence on the paper CNN (knob on vs off)
# ---------------------------------------------------------------------------

def _cnn_round_inputs(W, B=4, seed=0):
    cfg = get_config("paper-net")
    key = jax.random.PRNGKey(seed)
    gp, _ = api.init(cfg, key, tp=1)
    batch = {"images": jax.random.normal(key, (W, 1, B, 28, 28, 1)),
             "labels": jax.random.randint(jax.random.fold_in(key, 1),
                                          (W, 1, B), 0, 10)}
    return cfg, gp, batch


def _run_round(cfg, fed, gp, batch, *, rng, participation=None, rounds=1):
    tc = TrainConfig()
    W = batch["labels"].shape[0]
    opt = fl_step.init_worker_opt(gp, fed, tc)
    fn = jax.jit(fl_step.make_fl_round(cfg, fed, tc))
    outs = []
    if fed.async_mode:
        state = fl_step.init_async_state_for(cfg, fed, gp, W)
        for r in range(rounds):
            mask = participation[r]
            out, state = fn(gp, opt, batch, rng, mask, state)
            gp, opt = out.global_params, out.opt_state
            outs.append(out)
    else:
        for _ in range(rounds):
            out = fn(gp, opt, batch, rng, participation)
            gp, opt = out.global_params, out.opt_state
            outs.append(out)
    return outs


@pytest.mark.parametrize("W", [2, 4, 33])
@pytest.mark.parametrize("async_mode", [False, True])
def test_round_knob_equivalence(W, async_mode):
    cfg, gp, batch = _cnn_round_inputs(W)
    rng = jax.random.PRNGKey(7)
    if async_mode:
        k = jax.random.PRNGKey(W)
        part = [(jax.random.uniform(jax.random.fold_in(k, r), (W,))
                 > 0.4).astype(jnp.float32).at[0].set(1.0) for r in range(2)]
        rounds = 2   # round 2 consumes round 1's pending + staleness
    else:
        part, rounds = None, 1
    by_knob = {}
    for knob in ("off", "on"):
        fed = FederationConfig(num_clusters=1, workers_per_cluster=W,
                               trust_threshold=0.0, async_mode=async_mode,
                               fused_trust_path=knob)
        by_knob[knob] = _run_round(cfg, fed, gp, batch, rng=rng,
                                   participation=part, rounds=rounds)
    for a, b in zip(by_knob["off"], by_knob["on"]):
        np.testing.assert_allclose(np.asarray(a.scores),
                                   np.asarray(b.scores),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(a.weights),
                                   np.asarray(b.weights),
                                   rtol=1e-5, atol=1e-6)
        for la, lb in zip(jax.tree.leaves(a.global_params),
                          jax.tree.leaves(b.global_params)):
            np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                       rtol=1e-5, atol=1e-6)


def test_poisoned_worker_ranks_lowest_on_both_paths():
    """A −3× update flip must rank below every honest worker and be zeroed
    by the penalization filter — identically on both paths."""
    W, key = 8, jax.random.PRNGKey(11)
    base = _tree(key, 1, jnp.float32)
    honest = jax.tree.map(
        lambda b: b + 0.05 * jax.random.normal(jax.random.fold_in(key, 1),
                                               (W,) + b.shape[1:]), base)
    upd = jax.tree.map(lambda h, b: h.at[0].set(-3.0 * b[0]), honest, base)
    lb = jnp.full((W,), 2.0)
    la = jnp.full((W,), 1.5).at[0].set(2.2)     # attacker's loss got worse
    fed = FederationConfig(num_clusters=2, workers_per_cluster=4,
                           trust_threshold=0.5)

    s_ref = trust.scores_from_stats(trust.update_stats(upd, lb, la), fed)
    spec = pack.pack_spec(_template(upd))
    s_f = trust.scores_from_stats(
        trust.update_stats_flat(pack.pack_stack(upd, spec), lb, la), fed)
    for s in (s_ref, s_f):
        s = np.asarray(s)
        assert s[0] == s.min() and (s[1:] > s[0]).all()
    np.testing.assert_allclose(np.asarray(s_f), np.asarray(s_ref),
                               rtol=1e-5, atol=1e-6)
    for s in (s_ref, s_f):
        wts = np.asarray(trust.trust_weights(s, fed))
        assert wts[0] == 0.0 and (wts[1:] > 0).all()


# ---------------------------------------------------------------------------
# satellite: live loss delta at local_steps=1
# ---------------------------------------------------------------------------

def test_loss_delta_live_at_single_local_step():
    """Regression: with one local step the contribution-quality term used to
    see losses[:,0] == losses[:,-1] (a width-1 array) and contribute 0 for
    every worker. The post-step re-evaluation must yield a real delta."""
    W = 4
    cfg, gp, batch = _cnn_round_inputs(W, B=16)
    fed = FederationConfig(num_clusters=1, workers_per_cluster=W,
                           trust_threshold=0.0)
    assert fed.w_loss > 0 and TrainConfig().local_steps == 1
    out, = _run_round(cfg, fed, gp, batch, rng=jax.random.PRNGKey(3))
    assert float(out.metrics["mean_loss_delta"]) != 0.0
    # one SGD step on the same batch should improve its loss
    assert float(out.metrics["mean_loss_delta"]) > 0.0


def test_loss_delta_gated_off_when_unweighted():
    """w_loss=0 skips the extra forward: the delta metric is exactly 0."""
    W = 4
    cfg, gp, batch = _cnn_round_inputs(W)
    fed = FederationConfig(num_clusters=1, workers_per_cluster=W,
                           trust_threshold=0.0, w_loss=0.0)
    out, = _run_round(cfg, fed, gp, batch, rng=jax.random.PRNGKey(3))
    assert float(out.metrics["mean_loss_delta"]) == 0.0


# ---------------------------------------------------------------------------
# eligibility + state plumbing
# ---------------------------------------------------------------------------

def test_fused_eligibility():
    cnn = get_config("paper-net")
    key = jax.random.PRNGKey(0)
    params, _ = api.init(cnn, key, tp=1)
    fed = FederationConfig()
    assert fed.fused_trust_path == "auto"
    assert fl_step.fused_round_enabled(cnn, fed, params)
    # sharding constraints veto auto (flattening would all-gather)
    assert not fl_step.fused_round_enabled(cnn, fed, params, constrained=True)
    # auto stays off for non-CNN families even when packable
    dense = dataclasses.replace(cnn, family="dense")
    assert not fl_step.fused_round_enabled(dense, fed, params)
    # but "on" forces any packable tree, constrained or not
    fed_on = FederationConfig(fused_trust_path="on")
    assert fl_step.fused_round_enabled(dense, fed_on, params,
                                       constrained=True)
    assert not fl_step.fused_round_enabled(
        cnn, FederationConfig(fused_trust_path="off"), params)
    mixed = {"a": jnp.zeros((2,), jnp.float32),
             "b": jnp.zeros((2,), jnp.bfloat16)}
    with pytest.raises(ValueError, match="packable"):
        fl_step.fused_round_enabled(cnn, fed_on, mixed)
    assert not fl_step.fused_round_enabled(cnn, fed, mixed)  # auto: fallback
    with pytest.raises(ValueError, match="auto|on|off"):
        fl_step.fused_round_enabled(
            cnn, FederationConfig(fused_trust_path="yes"), params)


def test_init_async_state_for_layouts():
    cnn = get_config("paper-net")
    params, _ = api.init(cnn, jax.random.PRNGKey(0), tp=1)
    W = 6
    spec = pack.pack_spec(params)
    fused_state = fl_step.init_async_state_for(
        cnn, FederationConfig(async_mode=True), params, W)
    assert fused_state.pending.shape == \
        fused_round.pending_shape(W, spec.total)
    assert fused_state.staleness.shape == (W,)
    leaf_state = fl_step.init_async_state_for(
        cnn, FederationConfig(async_mode=True, fused_trust_path="off"),
        params, W)
    assert jax.tree.structure(leaf_state.pending) == \
        jax.tree.structure(params)
    for p, x in zip(jax.tree.leaves(leaf_state.pending),
                    jax.tree.leaves(params)):
        assert p.shape == (W,) + x.shape and p.dtype == jnp.float32


# ---------------------------------------------------------------------------
# geometry + HBM accounting
# ---------------------------------------------------------------------------

def test_block_d_for():
    """The sync kernels' planned tiles are lane-aligned, fit the scoped-VMEM
    budget, narrow as W grows, and tile W only where a full-W strip cannot
    fit even at one lane."""
    for mod in (trust_score, trust_agg):
        for itemsize in (2, 4):
            prev = None
            for W in (16, 256, 1024, 4096, 10240, 40000):
                t = mod.tiles(W, 21840, itemsize)
                assert t.bd % tpu.LANE == 0
                assert tpu.LANE <= t.bd <= tpu.MAX_BLOCK_D
                assert mod.vmem_bytes(t.bw, t.bd, itemsize) <= tpu.VMEM_BUDGET
                assert t.w_pad >= W
                if t.nw > 1:
                    full = tpu.round_up(W, tpu.LANE)
                    assert mod.vmem_bytes(full, tpu.LANE, itemsize) \
                        > tpu.VMEM_BUDGET
                elif prev is not None:
                    assert t.bd <= prev
                prev = t.bd if t.nw == 1 else prev
    # the 10k-cohort target keeps one full-W strip (one sweep) for the
    # statistics kernel, at f32 and bf16
    for itemsize in (2, 4):
        assert trust_score.tiles(10240, 21840, itemsize).nw == 1


def test_pending_shape_alignment():
    for W in (1, 7, 8, 255, 256, 10000):
        for D in (1, 511, 512, 21840):
            wp, dp = fused_round.pending_shape(W, D)
            assert wp >= W and dp >= D
            assert wp % fused_round.SUBLANE == 0
            assert dp % fused_round.BLOCK_D_ASYNC == 0


def test_update_passes_gate():
    """The fused chain streams the update volume exactly twice (the
    information floor: weights depend on global stats of the matrix)."""
    for dtype in (jnp.float32, jnp.bfloat16):
        for async_mode in (False, True):
            p = fused_round.update_passes(10240, 21840, dtype,
                                          async_mode=async_mode)
            assert p <= 2.0
