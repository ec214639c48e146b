"""moonlight-16b-a3b's federated LoRA path against the plain reference
(``chipbench/configs/moonlight-16b-a3b.py``) at a CPU size, on seeded
random weights: the loss, the first adapter gradients, one federated
round, the held expert shares, the frozen base and what is published.

The reference is float32. Where the program runs in float32 too (the
base upcast from the same bfloat16 draws), the two differ only by the
order of float32 sums, so those comparisons hold to 1e-4 relative; the
bfloat16 program is held to 3e-2, the rounding of bfloat16 activations
through three layers.
"""
import dataclasses
import importlib.util
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FederationConfig, ModelConfig, TrainConfig
from repro.core import fl_step
from repro.models import api
from repro.models import moe as MOE

ROOT = pathlib.Path(__file__).resolve().parents[1]
CONF = ROOT / "chipbench" / "configs" / "moonlight-16b-a3b.json"

# CPU size: every mechanism of the published configuration, small widths
SMALL = dict(num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
             num_key_value_heads=4, kv_lora_rank=16, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
             moe_intermediate_size=32, router_experts=8, n_routed_experts=4,
             num_experts_per_tok=3, vocab_size=256)
S = 32


def _reference_module():
    spec = importlib.util.spec_from_file_location("moonlight_ref",
                                                  CONF.with_suffix(".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference_module()


def small_config(**over):
    c = json.loads(CONF.read_text())
    c.update(SMALL)
    c["lora"] = dict(c["lora"], r=4, lora_alpha=8)
    c.update(over)
    return c


def model_config(c, dtype=None):
    cfg = ModelConfig(**REF.program(c)["model"])
    return cfg if dtype is None else cfg.replace(dtype=dtype)


def trained_adapters(c, seed=0):
    """Adapters with B made non-zero, so that every target changes the
    forward pass."""
    p = REF.init(c, jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)
    leaves, tree = jax.tree.flatten(p)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(tree, [
        x if x.any() else 0.05 * jax.random.normal(k, x.shape)
        for x, k in zip(leaves, keys)])


def tokens(c, W=1, seed=3):
    t = jax.random.randint(jax.random.PRNGKey(seed), (W, 1, S), 0,
                           c["vocab_size"])
    return {"tokens": t, "labels": t}


def f32_base(c):
    return jax.tree.map(lambda x: x.astype(jnp.float32), REF.base(c))


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-4), ("bfloat16", 3e-2)],
                         ids=["f32", "bf16"])
def test_loss_matches_reference(dtype, tol):
    c = small_config()
    cfg = model_config(c, dtype)
    ad = trained_adapters(c)
    rows = jax.tree.map(lambda x: x[0], tokens(c))
    frozen = f32_base(c) if dtype == "float32" else api.init_frozen(cfg)
    got, _ = api.loss_fn(cfg)(ad, rows, None, frozen)
    with jax.default_matmul_precision("highest"):
        want = REF.loss(c)(ad, rows, None, lambda x: x)
    np.testing.assert_allclose(float(got), float(want), rtol=tol)


def test_first_adapter_gradients_match_reference():
    c = small_config()
    cfg = model_config(c, "float32")
    ad = trained_adapters(c)
    rows = jax.tree.map(lambda x: x[0], tokens(c))
    frozen = f32_base(c)
    g = jax.grad(lambda p: api.loss_fn(cfg)(p, rows, None, frozen)[0])(ad)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda p: REF.loss(c)(p, rows, None,
                                              lambda x: x))(ad)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(want)):
        scale = float(jnp.max(jnp.abs(b))) + 1e-12
        assert float(jnp.max(jnp.abs(a - b))) / scale < 1e-4


def test_federated_round_matches_reference():
    """One round of 4 silos through ``fl_step`` against
    ``chipbench/reference.py``'s round with the reference loss: the
    adapters after aggregation and the trust scores. The step is taken
    from trained adapters at lr 1e-2, so that each silo's post-step loss
    gain, which the score's loss term divides by the largest, stands far
    above float32's rounding of the loss (at this size, at lr 2e-4 from
    B = 0, it is a few units in the last place, and the term is
    noise)."""
    import sys
    sys.path.insert(0, str(ROOT))
    from chipbench import reference as R
    c = small_config()
    c["train"] = dict(c["train"], lr=1e-2)
    cfg = model_config(c, "float32")
    t = c["train"]
    fed = FederationConfig(num_clusters=1, workers_per_cluster=4)
    tc = TrainConfig(**REF.program(c)["train"])
    W = 4
    ad = trained_adapters(c)
    batch = tokens(c, W)
    keys = jax.random.split(jax.random.PRNGKey(5), W)
    opt = fl_step.init_worker_opt(ad, fed, tc)
    out = jax.jit(fl_step.make_fl_round(cfg, fed, tc))(
        ad, opt, jax.tree.map(lambda x: x[:, None], batch), None,
        frozen=f32_base(c))
    ropt = R.Optim("adamw", t["lr"], b1=t["adam_b1"], b2=t["adam_b2"],
                   eps=t["adam_eps"], grad_clip=t["grad_clip"])
    pr = R.Protocol(w_cosine=fed.w_cosine, w_norm=fed.w_norm,
                    w_loss=fed.w_loss, trust_threshold=fed.trust_threshold,
                    soft_trust_weighting=fed.soft_trust_weighting,
                    staleness_alpha=0.0)
    opt_w = jax.tree.map(lambda x: jnp.broadcast_to(x, (W,) + x.shape),
                         R.opt_init(ad, ropt))
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(R.make_round(REF.loss(c), ropt, pr, chunk=2))(
            ad, opt_w, batch, keys)
    np.testing.assert_allclose(np.asarray(out.scores),
                               np.asarray(ref.scores), atol=1e-4)
    # AdamW scales each element's step by its own gradient, so elements
    # whose gradient is at rounding level move differently; the leaf's
    # change as a whole agrees to 1e-3
    for a, b, a0 in zip(jax.tree.leaves(out.global_params),
                        jax.tree.leaves(ref.params), jax.tree.leaves(ad)):
        assert float(jnp.linalg.norm(a - b)) \
            <= 1e-3 * float(jnp.linalg.norm(b - a0))


def _held_layer(moe_cfg, params, lo, n):
    share = dict(params, **{k: params[k][lo:lo + n]
                            for k in ("w_gate", "w_up", "w_down")})
    cfg = dataclasses.replace(moe_cfg, experts_held=(lo, n))
    return MOE.apply_moe_held(share, X, cfg)


X = jax.random.normal(jax.random.PRNGKey(7), (2, 24, 64))


def _uncut(bias_shift=None):
    cfg = model_config(small_config(n_routed_experts=8), "float32")
    params = jax.tree.map(lambda x: x[0], f32_base(
        small_config(n_routed_experts=8))["layers"]["moe"])
    if bias_shift is not None:
        params = dict(params, router_bias=params["router_bias"] + bias_shift)
    return cfg.moe, params


def test_held_shares_sum_to_the_uncut_layer():
    moe_cfg, params = _uncut()
    whole, c_whole = _held_layer(moe_cfg, params, 0, 8)
    shared = _held_layer(moe_cfg, dict(params, w_gate=params["w_gate"] * 0),
                         0, 8)[0]
    parts = [_held_layer(moe_cfg, params, lo, 2) for lo in (0, 2, 4, 6)]
    total = sum(p for p, _ in parts) - 3 * shared      # shared counted once
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-4, atol=1e-4)
    assert sum(int(c["moe.pairs_held"]) for _, c in parts) \
        == int(c_whole["moe.pairs_held"]) == X.shape[0] * X.shape[1] * 3


def test_no_token_dropped_when_all_route_to_one_expert():
    """Every token picks expert 1 (of the held 0-3): each of them gets
    its output, as the dense masked sum over every token gives it."""
    moe_cfg, params = _uncut(bias_shift=jnp.zeros(8).at[1].set(100.0))
    got, counters = _held_layer(moe_cfg, params, 0, 4)
    T = X.shape[0] * X.shape[1]
    assert int(counters["moe.max_expert_load"]) == T
    x = X.reshape(T, -1)
    s = jax.nn.sigmoid(x @ params["router"])
    _, idx = jax.lax.top_k(s + params["router_bias"], 3)
    w = jnp.take_along_axis(s, idx, -1)
    w = w / jnp.sum(w, -1, keepdims=True) * moe_cfg.routed_scaling
    sh = params["shared"]
    want = (jax.nn.silu(x @ sh["w_gate"]) * (x @ sh["w_up"])) @ sh["w_down"]
    for e in range(4):
        we = jnp.sum(jnp.where(idx == e, w, 0.0), -1)
        ye = (jax.nn.silu(x @ params["w_gate"][e])
              * (x @ params["w_up"][e])) @ params["w_down"][e]
        want = want + we[:, None] * ye
    np.testing.assert_allclose(np.asarray(got).reshape(T, -1),
                               np.asarray(want), rtol=1e-4, atol=1e-4)


def test_held_experts_without_lora_rejected():
    """A held share gives its expert weights no gradient, so a full
    fine-tune over one is refused where the config is built."""
    from repro.configs.base import LoRAConfig
    from repro.configs.registry import get_config
    cfg = get_config("moonlight-16b-a3b")
    with pytest.raises(ValueError, match="needs lora"):
        cfg.replace(lora=LoRAConfig())
    uncut = dataclasses.replace(cfg.moe, experts_held=(0, 0))
    assert not cfg.replace(moe=uncut, lora=LoRAConfig()).lora.enabled


@pytest.mark.parametrize("size", ["small", "published"])
def test_frozen_base_equals_reference_bit_for_bit(size):
    c = small_config() if size == "small" else json.loads(CONF.read_text())
    cfg = model_config(c)
    if size == "published":
        # shapes, dtypes and tree at full size; values where they are cheap
        got = jax.eval_shape(lambda: api.init_frozen(cfg))
        want = jax.eval_shape(lambda: REF.base(c))
        assert jax.tree.map(lambda x: (x.shape, x.dtype), got) \
            == jax.tree.map(lambda x: (x.shape, x.dtype), want)
        return
    got = jax.jit(lambda: api.init_frozen(cfg))()
    want = REF.base(c)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and np.array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32))


def test_base_unchanged_and_only_adapters_published():
    from repro.core.protocol import SDFLBProtocol
    c = small_config()
    kw = REF.program(c)
    fed = FederationConfig(num_clusters=1, workers_per_cluster=4,
                           pipeline_depth=2)
    proto = SDFLBProtocol(ModelConfig(**kw["model"]), fed,
                          TrainConfig(**kw["train"]), seed=11)
    task = proto.task
    base0 = jax.device_get(task.frozen)
    rows = jax.tree.map(np.asarray, tokens(c, 4))
    recs = [proto.run_round(rows) for _ in range(2)]
    proto.flush()
    for a, b in zip(jax.tree.leaves(task.frozen), jax.tree.leaves(base0)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    published = proto.ipfs.get_leaves(recs[-1].model_cid)
    adapters = jax.tree.leaves(jax.device_get(task.global_params))
    assert len(published) == len(adapters) == 16
    assert all(np.array_equal(p, np.asarray(a, np.float32))
               for p, a in zip(published, adapters))
    assert sum(a.size for a in adapters) == sum(
        x.size for x in jax.tree.leaves(REF.init(c, jax.random.PRNGKey(0))))
    for r in recs:
        assert r.counters["moe.pairs_held"] > 0
        assert 0 < r.counters["moe.max_expert_load"] \
            <= r.counters["moe.pairs_held"]
    proto.node.close()


def test_paper_net_round_takes_no_frozen_input():
    from repro.configs.registry import get_config
    cfg = get_config("paper-net")
    assert api.init_frozen(cfg) is None
    fed = FederationConfig(num_clusters=1, workers_per_cluster=2)
    tc = TrainConfig()
    params, _ = api.init(cfg, jax.random.PRNGKey(0))
    opt = fl_step.init_worker_opt(params, fed, tc)
    batch = {"images": jnp.zeros((2, 1, 4, 28, 28, 1)),
             "labels": jnp.zeros((2, 1, 4), jnp.int32)}
    fn = jax.jit(fl_step.make_fl_round(cfg, fed, tc))
    args = (params, opt, batch, jax.random.PRNGKey(1))
    plain = fn.lower(*args).as_text()
    assert fn.lower(*args, frozen=None).as_text() == plain
    main = next(line for line in plain.splitlines()
                if "func.func public @main(" in line)
    assert main.count("%arg") == len(jax.tree.leaves(args))


@pytest.mark.parametrize("seq,kernel", [(S, False), (256, True)],
                         ids=["reference", "kernel"])
def test_mla_attention_without_padded_v(monkeypatch, seq, kernel):
    """MLA's training attention, at this file's widths (q and k heads of
    24, v of 16), equals what it gave when v was padded to q's head size
    for the blocked scan and the output sliced back: on the reference path
    at S = 32, and through the causal kernel (interpreted) at S = 256,
    where it engages. float32 throughout, so they agree to rounding."""
    from repro.models import layers as L
    cfg = model_config(small_config(), "float32")
    p, _ = L.init_mla(jax.random.PRNGKey(0), cfg.d_model, cfg.num_heads,
                      cfg.mla, 1, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, seq, cfg.d_model))

    def mla(x):
        return L.apply_mla(p, x, num_heads=cfg.num_heads, mla=cfg.mla,
                           positions=jnp.arange(seq),
                           rope_theta=cfg.rope_theta, kv_chunk=64)
    if kernel:
        monkeypatch.setenv("SDFLB_FUSED_INTERPRET", "1")
    assert ("pallas_call" in str(jax.make_jaxpr(mla)(x))) is kernel
    new = jax.jit(mla)(x)
    monkeypatch.delenv("SDFLB_FUSED_INTERPRET", raising=False)
    blocked = L.blocked_attention

    def padded(q, k, v, **kw):
        pad = q.shape[-1] - v.shape[-1]
        return blocked(q, k, jnp.pad(v, ((0, 0),) * 3 + ((0, pad),)),
                       **kw)[..., :v.shape[-1]]
    monkeypatch.setattr(L, "blocked_attention", padded)
    monkeypatch.setattr(L.attention, "fits", lambda S: False)
    old = jax.jit(mla)(x)
    np.testing.assert_allclose(np.asarray(new), np.asarray(old),
                               rtol=1e-5, atol=1e-5)


def test_mla_attention_partitioned_keeps_blocked_scan(monkeypatch):
    """Where the launch layer partitions the program over several devices
    (a mesh in ``activation_sharding``), MLA's training attention stays
    the blocked scan: the partitioner cannot split a Mosaic kernel. On one
    device the same call takes the kernel."""
    from jax.sharding import AbstractMesh
    from repro.models import layers as L
    from repro.models.sharding import activation_sharding
    cfg = model_config(small_config(), "float32")
    p, _ = L.init_mla(jax.random.PRNGKey(0), cfg.d_model, cfg.num_heads,
                      cfg.mla, 1, jnp.float32)
    x = jax.ShapeDtypeStruct((2, 256, cfg.d_model), jnp.float32)

    def kernel_taken():
        # a new function each time: a cached trace would not see the hook
        return "pallas_call" in str(jax.make_jaxpr(lambda x: L.apply_mla(
            p, x, num_heads=cfg.num_heads, mla=cfg.mla,
            positions=jnp.arange(256), rope_theta=cfg.rope_theta,
            kv_chunk=64))(x))
    monkeypatch.setenv("SDFLB_FUSED_INTERPRET", "1")
    assert kernel_taken()
    for mesh in (AbstractMesh((1, 4), ("data", "model")),
                 AbstractMesh((2, 2), ("data", "model"))):
        with activation_sharding(None, mesh):
            assert not kernel_taken()
    with activation_sharding(None, AbstractMesh((1, 1), ("data", "model"))):
        assert kernel_taken()
