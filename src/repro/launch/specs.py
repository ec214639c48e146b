"""Input ShapeDtypeStructs + shardings for every (arch × shape × mesh) —
what the dry-run lowers. No device allocation anywhere (eval_shape only).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import (FederationConfig, ModelConfig,
                                TrainConfig)
from repro.configs.registry import get_config, get_shape
from repro.core import fl_step
from repro.launch import mesh as meshlib
from repro.models import api

SDS = jax.ShapeDtypeStruct


def federation_for(mesh, fed: FederationConfig) -> FederationConfig:
    """Scale the cluster topology to the mesh: W must equal the data-axis
    extent (each worker = one data slot); each pod hosts ``num_clusters``
    clusters."""
    dp = meshlib.dp_size(mesh)
    per_pod = mesh.shape["data"]
    wpc = per_pod // fed.num_clusters
    clusters_total = dp // wpc
    return dataclasses.replace(fed, num_clusters=clusters_total,
                               workers_per_cluster=wpc)


def train_config_for(cfg: ModelConfig) -> TrainConfig:
    """LLM FL rounds: paper's SGD(momentum) economics, bf16 opt state for
    the biggest archs (HBM fit), remat on."""
    big = cfg.num_layers * cfg.d_model * cfg.d_model > 2e9   # ≳ 20B params
    return TrainConfig(optimizer="sgd", lr=0.01, momentum=0.5,
                       remat=True, opt_dtype="bfloat16" if big else "float32")


def _named(mesh, tree_specs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                        is_leaf=lambda x: isinstance(x, P))


def init_specs(cfg: ModelConfig, tp: int):
    """(param ShapeDtypeStructs, PartitionSpec tree) without allocating:
    init runs abstractly under eval_shape; the spec tree (plain python) is
    captured by side effect."""
    captured = {}

    def f(k):
        p, s = api.init(cfg, k, tp)
        captured["specs"] = s
        return p

    sds = jax.eval_shape(f, jax.random.PRNGKey(0))
    return sds, captured["specs"]


# ---------------------------------------------------------------------------
# per-shape step functions + arg structs + shardings
# ---------------------------------------------------------------------------

def _batch_struct(cfg: ModelConfig, W: int, steps: int, per_worker: int,
                  seq: int):
    b = {"tokens": SDS((W, steps, per_worker, seq), jnp.int32),
         "labels": SDS((W, steps, per_worker, seq), jnp.int32)}
    if cfg.family == "vlm":
        text = seq - cfg.num_patch_tokens
        b["tokens"] = SDS((W, steps, per_worker, text), jnp.int32)
        b["labels"] = SDS((W, steps, per_worker, text), jnp.int32)
        b["patch_embeds"] = SDS(
            (W, steps, per_worker, cfg.num_patch_tokens, cfg.d_model),
            jnp.dtype(cfg.dtype))
    if cfg.family == "audio":
        b["frames"] = SDS(
            (W, steps, per_worker, cfg.encoder_seq, cfg.d_model),
            jnp.dtype(cfg.dtype))
    return b


def _batch_spec(batch, dp):
    return jax.tree.map(lambda s: P(dp, *([None] * (len(s.shape) - 1))), batch)


def train_setup(arch: str, shape_name: str, mesh, fed: FederationConfig,
                *, head_gather: bool = False, local_steps: int = 1):
    """Returns (fn, arg_structs, in_shardings, out_shardings, donate)."""
    cfg = get_config(arch)
    sh = get_shape(shape_name)
    fed = federation_for(mesh, fed)
    if head_gather:
        fed = dataclasses.replace(fed, mode="head_gather")
    tc = dataclasses.replace(train_config_for(cfg), local_steps=local_steps)
    tp = meshlib.tp_size(mesh)
    dp = meshlib.data_axes(mesh)
    W = fl_step.num_workers(fed)
    assert sh.global_batch % W == 0, (sh.global_batch, W)
    per_worker = sh.global_batch // W

    params_sds, param_specs = init_specs(cfg, tp)
    opt_sds = jax.eval_shape(
        lambda p: fl_step.init_worker_opt(p, fed, tc), params_sds)
    wspec = lambda s: P(dp, *s)
    if tc.optimizer == "sgd":
        opt_specs = {"momentum": jax.tree.map(
            lambda s: wspec(s), param_specs, is_leaf=lambda x: isinstance(x, P))}
    else:
        t = jax.tree.map(lambda s: wspec(s), param_specs,
                         is_leaf=lambda x: isinstance(x, P))
        opt_specs = {"m": t, "v": t, "count": P(dp)}

    batch_sds = _batch_struct(cfg, W, tc.local_steps, per_worker, sh.seq_len)
    batch_specs = _batch_spec(batch_sds, dp)

    def worker_constraint(tree):
        """Pin the leading worker dim of params-shaped (W, ...) trees to the
        data axes (leaf-wise: P(dp, *param_spec))."""
        return jax.tree.map(
            lambda x, s: jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(dp, *s))),
            tree, param_specs)

    def param_constraint(tree):
        """Per-worker param constraint (applied under vmap — the W dim is
        batched out): makes grad cotangents inherit the param sharding."""
        return jax.tree.map(
            lambda x, s: jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, s)),
            tree, param_specs)

    from repro.models.sharding import activation_sharding
    fl_round = fl_step.make_fl_round(cfg, fed, tc,
                                     worker_constraint=worker_constraint,
                                     param_constraint=param_constraint)
    # Shard the per-worker residual stream (B, S, d) on d over the TP axis
    # between blocks (sequence-parallel-style): shrinks the remat checkpoint
    # stack 1/TP at the cost of per-layer (re)gathers. Only worth it when
    # the replicated stack would be large — for small d_model the extra
    # collectives dwarf the memory win (measured: smollm 0.03s compute vs
    # 2.9s collective with it always-on).
    n_ckpt = {"hybrid": cfg.num_layers // max(cfg.shared_attn_every, 1),
              "ssm": cfg.num_layers // max(cfg.slstm_every, 1)}.get(
                  cfg.family, cfg.num_layers + cfg.encoder_layers)
    stack_bytes = n_ckpt * per_worker * sh.seq_len * cfg.d_model * 2
    # SEQUENCE sharding (not d): per-position ops (norms, MLP) stay local,
    # attention gathers only the small GQA K/V, and the checkpoint stack
    # still shrinks 1/TP. d-sharding measured 33 collectives/layer (§Perf).
    # Activation sharding policy (per-worker (B, S, d) residual):
    #   batch-sharding over the model axis (FSDP-style) when B divides TP —
    #   every layer is embarrassingly parallel over batch rows (SSM scans
    #   included); collectives become per-layer bf16 weight gathers instead
    #   of per-layer f32 residual psums (measured: zamba2 1.2 TB -> ~50 GB).
    #   Falls back to seq-sharding (dense attention families only — SSD's
    #   (B, nc, Q) reshapes fight seq sharding), else replicated.
    act = None
    if per_worker % tp == 0:
        # always profitable here: per-layer activations (B·S·d) far exceed
        # per-layer params for every assigned arch at train_4k
        act = NamedSharding(mesh, P("model", None, None))
    elif stack_bytes > 4 * 2**30 and sh.seq_len % tp == 0 \
            and cfg.family in ("dense", "moe", "vlm"):
        act = NamedSharding(mesh, P(None, "model", None))

    def fn(*a, **kw):
        with activation_sharding(act, mesh):
            return fl_round(*a, **kw)
    in_shardings = (_named(mesh, param_specs), _named(mesh, opt_specs),
                    _named(mesh, batch_specs))
    rep = NamedSharding(mesh, P())
    dpn = NamedSharding(mesh, P(dp))
    out_shardings = fl_step.RoundOutput(
        global_params=_named(mesh, param_specs),
        opt_state=_named(mesh, opt_specs),
        scores=dpn, weights=dpn, losses=dpn,
        metrics={"mean_loss": rep})
    return (fn, (params_sds, opt_sds, batch_sds), in_shardings, out_shardings,
            (0, 1))


def _prefill_batch_struct(cfg: ModelConfig, B: int, seq: int):
    b = {"tokens": SDS((B, seq), jnp.int32)}
    if cfg.family == "vlm":
        b["tokens"] = SDS((B, seq - cfg.num_patch_tokens), jnp.int32)
        b["patch_embeds"] = SDS((B, cfg.num_patch_tokens, cfg.d_model),
                                jnp.dtype(cfg.dtype))
    if cfg.family == "audio":
        b["frames"] = SDS((B, cfg.encoder_seq, cfg.d_model),
                          jnp.dtype(cfg.dtype))
    return b


def prefill_setup(arch: str, shape_name: str, mesh):
    cfg = get_config(arch)
    sh = get_shape(shape_name)
    tp = meshlib.tp_size(mesh)
    dp = meshlib.data_axes(mesh)
    B = sh.global_batch

    params_sds, param_specs = init_specs(cfg, tp)
    batch_sds = _prefill_batch_struct(cfg, B, sh.seq_len)
    batch_specs = jax.tree.map(
        lambda s: P(dp, *([None] * (len(s.shape) - 1))), batch_sds)
    cache_specs = api.cache_spec(cfg, tp, dp)

    # Prefill activation policy (§Perf H11): sequence-sharding helps ONLY
    # MLA (minicpm3 12.5→6.4 s — its low-rank latent projections gain
    # nothing from head-TP); for GQA/MoE prefill the head-TP layout measured
    # strictly better (yi 1.2→5.0 s, qwen2 8.4→20 s when seq-sharded).
    from repro.models.sharding import activation_sharding
    act = (NamedSharding(mesh, P(None, "model", None))
           if cfg.attn_type == "mla" and sh.seq_len % tp == 0 else None)

    def fn(params, batch):
        with activation_sharding(act, mesh):
            return api.prefill(params, cfg, batch, sh.seq_len)

    logits_spec = P(dp, None, None)   # logits replicated over model
                                      # unless vocab sharded
    in_shardings = (_named(mesh, param_specs), _named(mesh, batch_specs))
    out_shardings = (NamedSharding(mesh, logits_spec),
                     _named(mesh, cache_specs))
    return fn, (params_sds, batch_sds), in_shardings, out_shardings, ()


def decode_setup(arch: str, shape_name: str, mesh, *,
                 long_context: bool = False):
    cfg = get_config(arch)
    sh = get_shape(shape_name)
    tp = meshlib.tp_size(mesh)
    dp = meshlib.data_axes(mesh)
    B = sh.global_batch
    long_context = long_context or shape_name == "long_500k"

    params_sds, param_specs = init_specs(cfg, tp)
    cache_sds = api.cache_struct(cfg, B, sh.seq_len)
    if long_context:
        # batch=1: KV caches shard their *sequence* dim over the data axes;
        # recurrent states shard over model only.
        base = api.cache_spec(cfg, tp, None)

        seq_axes = tuple(dp) + ("model",)   # 524288 % (dp·tp) == 0

        def fix(path, spec):
            name = path[-1].key
            if name in ("k", "v"):
                # (L, B, S, KV, hd) — seq at index 2
                return P(spec[0], None, seq_axes, None, None)
            if name == "latent":
                return P(spec[0], None, seq_axes, None)
            return spec
        cache_specs = jax.tree_util.tree_map_with_path(
            fix, base, is_leaf=lambda x: isinstance(x, P))
    else:
        cache_specs = api.cache_spec(cfg, tp, dp)
    tokens_sds = SDS((B, 1), jnp.int32)
    idx_sds = SDS((), jnp.int32)

    def fn(params, cache, tokens, cur_index):
        return api.decode_step(params, cfg, cache, tokens, cur_index)

    logits_spec = P(None if long_context else dp, None, None)
    in_shardings = (_named(mesh, param_specs), _named(mesh, cache_specs),
                    NamedSharding(mesh, P(None if long_context else dp, None)),
                    NamedSharding(mesh, P()))
    out_shardings = (NamedSharding(mesh, logits_spec),
                     _named(mesh, cache_specs))
    return (fn, (params_sds, cache_sds, tokens_sds, idx_sds), in_shardings,
            out_shardings, (1,))


def setup_for(arch: str, shape_name: str, mesh, fed: FederationConfig,
              **kw):
    kind = get_shape(shape_name).kind
    if kind == "train":
        return train_setup(arch, shape_name, mesh, fed, **kw)
    if kind == "prefill":
        return prefill_setup(arch, shape_name, mesh)
    return decode_setup(arch, shape_name, mesh)
