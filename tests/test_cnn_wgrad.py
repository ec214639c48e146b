"""The paper CNN's convolution weight gradient: a contraction of the input's
shifted windows against the output cotangent, batched over workers under
the round's vmap, in place of autodiff's worker-grouped convolution."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FederationConfig, TrainConfig
from repro.configs.registry import get_config
from repro.core import fl_step
from repro.models import api, cnn


def _plain_conv(x, w):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


@pytest.mark.parametrize("W,n,h,wd,k,cin,cout", [
    (4, 3, 28, 28, 5, 1, 10),     # paper-net conv1
    (4, 3, 12, 12, 5, 10, 20),    # paper-net conv2
    (3, 2, 9, 7, 3, 3, 4),        # odd kernel, non-square input
], ids=["conv1", "conv2", "odd"])
def test_patch_wgrad_matches_autodiff_under_vmap(W, n, h, wd, k, cin, cout):
    kx, kw_, kg = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (W, n, h, wd, cin), jnp.float32)
    w = jax.random.normal(kw_, (W, k, k, cin, cout), jnp.float32)
    g = jax.random.normal(kg, (W, n, h - k + 1, wd - k + 1, cout),
                          jnp.float32)

    def vjp_of(conv):
        return jax.jit(jax.vmap(lambda x_, w_, g_: jax.vjp(conv, x_, w_)[1](g_)))

    with jax.default_matmul_precision("highest"):
        y = jax.jit(jax.vmap(cnn._conv_valid))(x, w)
        y_ref = jax.jit(jax.vmap(_plain_conv))(x, w)
        dx, dw = vjp_of(cnn._conv_valid)(x, w, g)
        dx_ref, dw_ref = vjp_of(_plain_conv)(x, w, g)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))
    np.testing.assert_array_equal(np.asarray(dx), np.asarray(dx_ref))
    rel = float(jnp.linalg.norm(dw - dw_ref) / jnp.linalg.norm(dw_ref))
    assert rel <= 1e-5, rel


def test_paper_net_round_takes_batched_wgrad_dots():
    """Lowered at W = 8, the paper-net round computes both conv weight
    gradients as dot_generals batched over the 8 workers, and no grouped
    convolution produces a (kh, kw, Cin, W*Cout) gradient."""
    W = 8
    cfg = get_config("paper-net")
    fed = FederationConfig(num_clusters=2, workers_per_cluster=4)
    tc = TrainConfig()
    gp = jax.eval_shape(lambda key: api.init(cfg, key, tp=1)[0],
                        jax.random.PRNGKey(0))
    opt = jax.eval_shape(lambda p: fl_step.init_worker_opt(p, fed, tc), gp)
    batch = {"images": jax.ShapeDtypeStruct((W, 1, 4, 28, 28, 1),
                                            jnp.float32),
             "labels": jax.ShapeDtypeStruct((W, 1, 4), jnp.int32)}
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    text = jax.jit(fl_step.make_fl_round(cfg, fed, tc)).lower(
        gp, opt, batch, rng).as_text()
    lines = text.splitlines()

    def result_shape(line):
        return tuple(int(d) for d in
                     re.search(r"-> tensor<([\dx]+)x\w+>", line)
                     .group(1).split("x"))

    dots = {result_shape(ln) for ln in lines if "stablehlo.dot_general" in ln
            and "batching_dims = [0] x [0]" in ln}
    convs = [result_shape(ln) for ln in lines
             if "stablehlo.convolution" in ln]
    c1, c2 = cfg.cnn_channels
    for cin, cout in ((1, c1), (c1, c2)):
        assert (W, 5 * 5 * cin, cout) in dots
        assert (5, 5, cin, W * cout) not in convs
