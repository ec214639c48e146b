"""paper-net: plain float32 reference, weights and FLOP count.

The network of the paper's section IV (the classic MNIST Net): conv 5x5
1->10, maxpool 2, relu; conv 5x5 10->20, feature-map dropout 0.5 (one
draw per image and channel, kept values scaled by 2), maxpool 2, relu;
flatten in (H, W, C) order; fc 320->50, relu; fc 50->10; cross-entropy
averaged over the batch. Images are NHWC, kernels HWIO.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def program(c: dict) -> dict:
    """Keyword arguments of the program's model and training configs."""
    t = c["train"]
    return {
        "model": dict(name=c["name"], family="cnn", num_layers=2,
                      d_model=c["fc_hidden"], num_heads=1, num_kv_heads=1,
                      d_ff=0, vocab_size=0, image_size=c["image_size"],
                      num_classes=c["num_classes"],
                      cnn_channels=tuple(c["conv_channels"]),
                      dtype=c["dtype"]),
        "train": dict(optimizer=t["optimizer"], lr=t["lr"],
                      momentum=t["momentum"], dampening=t["dampening"],
                      weight_decay=t["weight_decay"], nesterov=t["nesterov"],
                      grad_clip=t["grad_clip"], local_steps=t["local_steps"]),
    }


def _sizes(c):
    k, p = c["kernel_size"], c["pool"]
    c0, (c1, c2) = c["in_channels"], c["conv_channels"]
    s1 = c["image_size"] - k + 1            # conv1 output side
    s2 = s1 // p - k + 1                    # conv2 output side
    flat = (s2 // p) ** 2 * c2
    return k, c0, c1, c2, s1, s2, flat


def init(c: dict, key):
    """Random weights from ``key``, in the configuration's dtype."""
    k, c0, c1, c2, _, _, flat = _sizes(c)
    h, n = c["fc_hidden"], c["num_classes"]
    ks = jax.random.split(key, 4)
    dt = jnp.dtype(c["dtype"])

    def w(key, shape, fan_in):
        return (jax.random.normal(key, shape) / fan_in ** 0.5).astype(dt)
    return {
        "conv1": {"w": w(ks[0], (k, k, c0, c1), k * k * c0),
                  "b": jnp.zeros((c1,), dt)},
        "conv2": {"w": w(ks[1], (k, k, c1, c2), k * k * c1),
                  "b": jnp.zeros((c2,), dt)},
        "fc1": {"w": w(ks[2], (flat, h), flat), "b": jnp.zeros((h,), dt)},
        "fc2": {"w": w(ks[3], (h, n), h), "b": jnp.zeros((n,), dt)},
    }


def loss(c: dict):
    """``f(params, rows, key, qm) -> scalar``; ``qm`` rounds each stored
    activation (the identity for the reference itself)."""
    p = c["pool"]

    def conv(x, w, b):
        return jax.lax.conv_general_dilated(
            x, w, (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + b

    def pool(x):
        return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                     (1, p, p, 1), (1, p, p, 1), "VALID")

    def f(params, rows, key, qm):
        x = rows["images"].astype(jnp.float32)
        x = jax.nn.relu(pool(qm(conv(x, params["conv1"]["w"],
                                     params["conv1"]["b"]))))
        x = qm(conv(x, params["conv2"]["w"], params["conv2"]["b"]))
        keep = jax.random.bernoulli(key, 1.0 - c["dropout"],
                                    x.shape[:1] + (1, 1, x.shape[-1]))
        x = jnp.where(keep, x / (1.0 - c["dropout"]), 0.0)
        x = jax.nn.relu(pool(x)).reshape(x.shape[0], -1)
        x = qm(jax.nn.relu(jnp.dot(x, params["fc1"]["w"])
                           + params["fc1"]["b"]))
        logits = qm(jnp.dot(x, params["fc2"]["w"]) + params["fc2"]["b"])
        lab = rows["labels"]
        ll = jnp.take_along_axis(logits, lab[:, None], axis=1)[:, 0]
        return jnp.mean(jax.nn.logsumexp(logits, axis=1) - ll)
    return f


def forward_flops(c: dict) -> int:
    """Multiply-adds of one image's forward pass, times two."""
    k, c0, c1, c2, s1, s2, flat = _sizes(c)
    return 2 * (s1 * s1 * c1 * k * k * c0 + s2 * s2 * c2 * k * k * c1
                + flat * c["fc_hidden"] + c["fc_hidden"] * c["num_classes"])


def flops_per_sample(c: dict, traffic: dict) -> int:
    """Forward and backward FLOPs one image needs: the forward pass, the
    weight gradients of every layer (as many again), and the input
    gradients of every layer but the first, whose input is data. Bias,
    pooling and activation work is not counted; the loss re-evaluation
    and the optimizer are not part of the model's FLOPs."""
    k, c0, c1, _, s1, _, _ = _sizes(c)
    first = 2 * s1 * s1 * c1 * k * k * c0
    return 3 * forward_flops(c) - first


def samples_per_worker(traffic: dict) -> int:
    """Images one worker trains on in a round."""
    return traffic["samples_per_worker"]
