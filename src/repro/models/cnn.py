"""The paper's MNIST 'Net' (§IV): conv1 -> pool -> conv2 -> dropout -> pool
-> fc1 -> fc2. Matches the classic PyTorch MNIST example the paper's
TorchScript dump corresponds to (10/20 channels, 5x5 kernels, fc1 320->50).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import dense_init


def init_cnn(key, cfg: ModelConfig, tp: int = 1):
    c1, c2 = cfg.cnn_channels
    ks = jax.random.split(key, 4)
    # 28x28 -> conv5 -> 24 -> pool -> 12 -> conv5 -> 8 -> pool -> 4 ; 4*4*c2
    flat = (((cfg.image_size - 4) // 2 - 4) // 2) ** 2 * c2
    params = {
        "conv1": {"w": dense_init(ks[0], (5, 5, 1, c1), 25, jnp.float32),
                  "b": jnp.zeros((c1,), jnp.float32)},
        "conv2": {"w": dense_init(ks[1], (5, 5, c1, c2), 25 * c1, jnp.float32),
                  "b": jnp.zeros((c2,), jnp.float32)},
        "fc1": {"w": dense_init(ks[2], (flat, cfg.d_model), flat, jnp.float32),
                "b": jnp.zeros((cfg.d_model,), jnp.float32)},
        "fc2": {"w": dense_init(ks[3], (cfg.d_model, cfg.num_classes), cfg.d_model,
                                jnp.float32),
                "b": jnp.zeros((cfg.num_classes,), jnp.float32)},
    }
    specs = jax.tree.map(lambda _: P(), params)
    return params, specs


def _conv_plain(x, w):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


@jax.custom_vjp
def _conv_valid(x, w):
    return _conv_plain(x, w)


def _conv_valid_fwd(x, w):
    return _conv_plain(x, w), (x, w)


def _conv_valid_bwd(res, g):
    """Input cotangent from the convolution's own transpose; weight
    cotangent as one contraction of the input's kh*kw shifted windows
    against ``g`` over every (image, output position). Under a vmap over
    per-worker kernels that contraction is a dot_general batched over the
    workers, where autodiff's transpose would be a convolution grouped by
    worker (one group per worker, each with a handful of channels)."""
    x, w = res
    (dx,) = jax.linear_transpose(lambda x_: _conv_plain(x_, w), x)(g)
    kh, kw, cin, cout = w.shape
    n, ho, wo, _ = g.shape
    with jax.named_scope("conv_wgrad"):
        # channels first, so the contraction over n*ho*wo is the minor
        # dimension of both operands rather than the few channels
        xt = jnp.transpose(x, (3, 0, 1, 2))
        patches = jnp.stack([xt[:, :, i:i + ho, j:j + wo]
                             for i in range(kh) for j in range(kw)])
        patches = patches.reshape(kh * kw * cin, n * ho * wo)
        gt = jnp.transpose(g, (3, 0, 1, 2)).reshape(cout, n * ho * wo)
        dw = jax.lax.dot_general(patches, gt, (((1,), (1,)), ((), ())))
    return dx, dw.reshape(w.shape)


_conv_valid.defvjp(_conv_valid_fwd, _conv_valid_bwd)


def _conv(x, w, b):
    return _conv_valid(x, w) + b


def _maxpool2(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def cnn_forward(params, cfg: ModelConfig, images, *, rng=None, train=False):
    """images: (B, 28, 28, 1) -> logits (B, 10). Dropout (p=0.5 feature-map
    dropout, like the paper's conv2_drop) only when ``train`` and rng given."""
    x = jax.nn.relu(_maxpool2(_conv(images, params["conv1"]["w"], params["conv1"]["b"])))
    x = _conv(x, params["conv2"]["w"], params["conv2"]["b"])
    if train and rng is not None:
        keep = jax.random.bernoulli(rng, 0.5, x.shape[:1] + (1, 1, x.shape[-1]))
        x = jnp.where(keep, x / 0.5, 0.0)
    x = jax.nn.relu(_maxpool2(x))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ params["fc1"]["w"] + params["fc1"]["b"])
    return x @ params["fc2"]["w"] + params["fc2"]["b"]
