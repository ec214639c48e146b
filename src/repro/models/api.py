"""Uniform model API over the zoo — what the FL core, launcher, and dry-run
consume. Dispatches on ``cfg.family``.

    init(cfg, key, tp)                  -> (params, param_specs)
    init_frozen(cfg)                    -> frozen base or None
    loss_fn(cfg)(params, batch, rng, frozen) -> (loss, metrics) # train step unit
    forward(params, cfg, batch)         -> (logits, aux)        # prefill/full fwd
    cache_shape(cfg, batch, seq)        -> pytree of shapes
    cache_spec(cfg, tp, data_axes)      -> pytree of PartitionSpec
    decode_step(params, cfg, cache, tokens, cur_index) -> (logits, cache)

Batches are dicts:
    dense/moe/ssm/hybrid : {tokens (B,S), labels (B,S)}
    vlm                  : + {patch_embeds (B,P,d)}      (stub VQ frontend)
    audio                : + {frames (B,enc_seq,d)}      (stub conv frontend)
    cnn                  : {images (B,28,28,1), labels (B,)}

With ``cfg.lora`` the trained params are the adapters only (``models.lora``)
and ``frozen`` is the base they adapt, built once by ``init_frozen``; every
other configuration trains every leaf and has no frozen part.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import cnn as CNN
from repro.models import encdec as ED
from repro.models import hybrid as HY
from repro.models import lora as LORA
from repro.models import moe as MOE
from repro.models import transformer as TF
from repro.models import xlstm as XL


def init(cfg: ModelConfig, key, tp: int = 1):
    if cfg.lora.enabled:
        return LORA.init_adapters(key, cfg)
    if cfg.family in ("dense", "moe", "vlm"):
        return TF.init_decoder(key, cfg, tp)
    if cfg.family == "hybrid":
        return HY.init_hybrid(key, cfg, tp)
    if cfg.family == "ssm":
        return XL.init_xlstm(key, cfg, tp)
    if cfg.family == "audio":
        return ED.init_encdec(key, cfg, tp)
    if cfg.family == "cnn":
        return CNN.init_cnn(key, cfg, tp)
    raise ValueError(cfg.family)


def init_frozen(cfg: ModelConfig):
    """The frozen base the trained params adapt, or None where every leaf
    is trained."""
    return LORA.init_frozen(cfg) if cfg.lora.enabled else None


reduce_counters = MOE.reduce_counters   # counters over a leading axis


def forward(params, cfg: ModelConfig, batch, *, remat: bool = False,
            kv_chunk: int = 1024):
    """Full forward producing logits (the prefill path for LM families)."""
    if cfg.family in ("dense", "moe"):
        return TF.decoder_forward(params, cfg, batch["tokens"], remat=remat,
                                  kv_chunk=kv_chunk)
    if cfg.family == "vlm":
        return TF.decoder_forward(params, cfg, batch["tokens"],
                                  patch_embeds=batch["patch_embeds"],
                                  remat=remat, kv_chunk=kv_chunk)
    if cfg.family == "hybrid":
        return HY.hybrid_forward(params, cfg, batch["tokens"], remat=remat,
                                 kv_chunk=kv_chunk)
    if cfg.family == "ssm":
        return XL.xlstm_forward(params, cfg, batch["tokens"], remat=remat)
    if cfg.family == "audio":
        return ED.encdec_forward(params, cfg, batch["tokens"],
                                 frames=batch["frames"], remat=remat,
                                 kv_chunk=kv_chunk)
    raise ValueError(cfg.family)


def prefill(params, cfg: ModelConfig, batch, cache_len: int, *,
            kv_chunk: int = 1024):
    """Process the prompt, returning (last_logits (B,1,V), decode cache).
    The cache is allocated at ``cache_len`` slots; decode continues at
    cur_index = prompt_len."""
    kw = dict(prefill_cache_len=cache_len, kv_chunk=kv_chunk)
    if cfg.family in ("dense", "moe"):
        return TF.decoder_forward(params, cfg, batch["tokens"], **kw)
    if cfg.family == "vlm":
        return TF.decoder_forward(params, cfg, batch["tokens"],
                                  patch_embeds=batch["patch_embeds"], **kw)
    if cfg.family == "hybrid":
        return HY.hybrid_forward(params, cfg, batch["tokens"], **kw)
    if cfg.family == "ssm":
        return XL.xlstm_forward(params, cfg, batch["tokens"], **kw)
    if cfg.family == "audio":
        return ED.encdec_forward(params, cfg, batch["tokens"],
                                 frames=batch["frames"], **kw)
    raise ValueError(cfg.family)


def _label_logit(logits, safe_labels):
    """logits[..., labels] via a one-hot contraction — unlike
    take_along_axis this keeps a vocab-sharded logits tensor sharded (the
    contraction lowers to a tiny psum instead of an all-gather of the full
    (B, S, V) f32 logits)."""
    one_hot = jax.nn.one_hot(safe_labels, logits.shape[-1],
                             dtype=logits.dtype)
    return jnp.einsum("...v,...v->...", logits, one_hot)


def _xent(logits, labels):
    """Causal LM loss; labels == -100 are masked."""
    logits = logits.astype(jnp.float32)
    mask = labels >= 0
    safe = jnp.where(mask, labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = _label_logit(logits, safe)
    nll = (lse - ll) * mask
    return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1)


def _chunked_xent(x, head, targets, *, seq_chunk: int = 512):
    """Cross-entropy without materializing (B, S, V) logits: scan over
    sequence chunks; the chunk body is rematerialized so backward never
    holds more than one chunk's f32 logits/cotangents.

    x: (B, S, d) final hidden; head: (d, V); targets: (B, S) with -100 pads.
    """
    B, S, d = x.shape
    if S % seq_chunk or S <= seq_chunk:
        return _xent(x @ head, targets)
    nc = S // seq_chunk
    xc = jnp.moveaxis(x.reshape(B, nc, seq_chunk, d), 1, 0)
    tc = jnp.moveaxis(targets.reshape(B, nc, seq_chunk), 1, 0)

    @jax.checkpoint
    def body(carry, inp):
        nll_sum, cnt = carry
        xb, tb = inp
        logits = (xb @ head).astype(jnp.float32)
        mask = tb >= 0
        safe = jnp.where(mask, tb, 0)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = _label_logit(logits, safe)
        nll = jnp.sum((lse - ll) * mask)
        return (nll_sum + nll, cnt + jnp.sum(mask)), None

    (nll_sum, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
        (xc, tc))
    return nll_sum / jnp.maximum(cnt, 1)


def _shifted_targets(labels, total_len: int, offset: int):
    """targets[pos] = next-token label aligned to the fused sequence:
    positions < offset (patch prompt) and the final position get -100."""
    B, S_text = labels.shape
    tgt = jnp.full((B, total_len), -100, jnp.int32)
    tgt = jax.lax.dynamic_update_slice(
        tgt, labels[:, 1:].astype(jnp.int32), (0, offset))
    return tgt


def loss_fn(cfg: ModelConfig, *, remat: bool = False, kv_chunk: int = 1024):
    """Returns f(params, batch, rng, frozen) -> (loss, metrics). ``frozen``
    is ``init_frozen``'s base (None where there is none). Held expert
    layers add ``metrics["counters"]`` (``reduce_counters`` sums them over
    workers)."""
    if cfg.family == "cnn":
        def f_cnn(params, batch, rng=None, frozen=None):
            logits = CNN.cnn_forward(params, cfg, batch["images"], rng=rng,
                                     train=rng is not None)
            labels = batch["labels"]
            loss = _xent(logits[:, None, :], labels[:, None])
            acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
            return loss, {"loss": loss, "accuracy": acc}
        return f_cnn

    def f(params, batch, rng=None, frozen=None):
        kw = dict(remat=remat, kv_chunk=kv_chunk, return_hidden=True)
        counters = {}
        if cfg.family in ("dense", "moe"):
            if frozen is not None:
                params, lora = frozen, params
            else:
                lora = None
            x, aux, counters = TF.decoder_hidden(
                params, cfg, batch["tokens"], remat=remat, kv_chunk=kv_chunk,
                lora=lora)
            head = (params["embed"].T if cfg.tie_embeddings
                    else params["lm_head"])
            offset = 0
        elif cfg.family == "vlm":
            x, aux, _ = TF.decoder_hidden(params, cfg, batch["tokens"],
                                          patch_embeds=batch["patch_embeds"],
                                          remat=remat, kv_chunk=kv_chunk)
            head = params["lm_head"]
            offset = batch["patch_embeds"].shape[1]
        elif cfg.family == "hybrid":
            x, aux = HY.hybrid_forward(params, cfg, batch["tokens"], **kw)
            head, offset = params["lm_head"], 0
        elif cfg.family == "ssm":
            x, aux = XL.xlstm_forward(params, cfg, batch["tokens"], **kw)
            head, offset = params["lm_head"], 0
        elif cfg.family == "audio":
            x, aux = ED.encdec_forward(params, cfg, batch["tokens"],
                                       frames=batch["frames"], **kw)
            head, offset = params["embed"].T, 0
        else:
            raise ValueError(cfg.family)
        targets = _shifted_targets(batch["labels"], x.shape[1], offset)
        loss = _chunked_xent(x, head, targets) + aux
        metrics = {"loss": loss, "aux": jnp.asarray(aux, jnp.float32)}
        if counters:
            metrics["counters"] = counters
        return loss, metrics
    return f


def cache_shape(cfg: ModelConfig, batch: int, seq: int):
    if cfg.family in ("dense", "moe", "vlm"):
        return TF.decoder_cache_shape(cfg, batch, seq)
    if cfg.family == "hybrid":
        return HY.hybrid_cache_shape(cfg, batch, seq)
    if cfg.family == "ssm":
        return XL.xlstm_cache_shape(cfg, batch, seq)
    if cfg.family == "audio":
        return ED.encdec_cache_shape(cfg, batch, seq)
    raise ValueError(cfg.family)


def cache_spec(cfg: ModelConfig, tp: int, data_axes):
    if cfg.family in ("dense", "moe", "vlm"):
        return TF.decoder_cache_spec(cfg, tp, data_axes)
    if cfg.family == "hybrid":
        return HY.hybrid_cache_spec(cfg, tp, data_axes)
    if cfg.family == "ssm":
        return XL.xlstm_cache_spec(cfg, tp, data_axes)
    if cfg.family == "audio":
        return ED.encdec_cache_spec(cfg, tp, data_axes)
    raise ValueError(cfg.family)


# recurrent-state leaves live in f32; KV-style caches in the model dtype
_F32_LEAVES = ("ssm", "c", "n", "h", "m")


def _cache_leaf_dtype(cfg: ModelConfig, name: str):
    return jnp.float32 if name in _F32_LEAVES else jnp.dtype(cfg.dtype)


def cache_struct(cfg: ModelConfig, batch: int, seq: int):
    """Pytree of jax.ShapeDtypeStruct for the decode cache (dry-run input)."""
    shapes = cache_shape(cfg, batch, seq)

    def mk(path, shape):
        name = path[-1].key
        return jax.ShapeDtypeStruct(shape, _cache_leaf_dtype(cfg, name))
    return jax.tree_util.tree_map_with_path(
        mk, shapes, is_leaf=lambda x: isinstance(x, tuple))


def make_cache(cfg: ModelConfig, batch: int, seq: int):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        cache_struct(cfg, batch, seq))


def decode_step(params, cfg: ModelConfig, cache, tokens, cur_index):
    if cfg.family in ("dense", "moe", "vlm"):
        return TF.decoder_decode_step(params, cfg, cache, tokens, cur_index)
    if cfg.family == "hybrid":
        return HY.hybrid_decode_step(params, cfg, cache, tokens, cur_index)
    if cfg.family == "ssm":
        return XL.xlstm_decode_step(params, cfg, cache, tokens, cur_index)
    if cfg.family == "audio":
        return ED.encdec_decode_step(params, cfg, cache, tokens, cur_index)
    raise ValueError(cfg.family)


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# --- flat-param view (the fused trust round's packed layout) -----------------
# Thin delegations to ``kernels.pack`` so protocol/launch code can reason
# about a model's flat (D,) coordinate space (slice offsets per leaf, total
# length, pack dtype) without importing the kernel package directly.

def flat_param_spec(params):
    """Static pack metadata for ``params``: leaf order, (offset, size, shape)
    slices into the flat axis, pack dtype, and total length D. Raises if the
    tree mixes leaf dtypes (see ``flat_packable``)."""
    from repro.kernels import pack
    return pack.pack_spec(params)


def flat_packable(params) -> bool:
    """Whether ``params`` admits the flat view (uniform floating leaf dtype —
    the eligibility signal behind ``FederationConfig.fused_trust_path``)."""
    from repro.kernels import pack
    return pack.packable(params)


def flatten_params(params):
    """params pytree -> ((D,) vector, spec). Inverse: ``unflatten_params``."""
    from repro.kernels import pack
    spec = pack.pack_spec(params)
    flat = jnp.concatenate(
        [x.reshape(-1) for x in jax.tree.leaves(params)])
    return flat, spec


def unflatten_params(flat, spec):
    """(D,) vector + spec -> params pytree (exact inverse of flatten)."""
    from repro.kernels import pack
    return pack.unpack_vector(flat, spec)
