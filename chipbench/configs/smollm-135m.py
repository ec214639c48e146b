"""smollm-135m: plain float32 reference, weights and FLOP count.

A Llama-architecture decoder as its Hugging Face config states: token
embedding tied to the output head; per layer RMSNorm (eps 1e-5, weight
after normalising), grouped-query attention (9 query heads, 3 key/value
heads, head size 64, no biases) with rotary embedding on the two halves
of each head (theta 10000), causal softmax over the full sequence, then
RMSNorm and a SiLU-gated MLP (gate, up, down); final RMSNorm. The loss
is next-token cross-entropy averaged over every position but the last.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def program(c: dict) -> dict:
    """Keyword arguments of the program's model and training configs."""
    t = c["train"]
    return {
        "model": dict(name=c["name"], family="dense",
                      num_layers=c["num_hidden_layers"],
                      d_model=c["hidden_size"],
                      num_heads=c["num_attention_heads"],
                      num_kv_heads=c["num_key_value_heads"],
                      d_ff=c["intermediate_size"],
                      vocab_size=c["vocab_size"],
                      rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"],
                      tie_embeddings=c["tie_word_embeddings"],
                      dtype=c["torch_dtype"]),
        "train": dict(optimizer=t["optimizer"], lr=t["lr"],
                      adam_b1=t["adam_b1"], adam_b2=t["adam_b2"],
                      adam_eps=t["adam_eps"], weight_decay=t["weight_decay"],
                      grad_clip=t["grad_clip"], remat=t["remat"],
                      kv_chunk=t["kv_chunk"], local_steps=t["local_steps"]),
    }


def _dims(c):
    d, h, kv = (c["hidden_size"], c["num_attention_heads"],
                c["num_key_value_heads"])
    return d, h, kv, d // h, c["intermediate_size"], c["num_hidden_layers"]


def init(c: dict, key):
    """Random weights from ``key``, in the served dtype."""
    d, h, kv, hd, ff, n = _dims(c)
    dt = jnp.dtype(c["torch_dtype"])
    ks = jax.random.split(key, 8)

    def w(key, shape, fan_in):
        return (jax.random.normal(key, shape) / fan_in ** 0.5).astype(dt)
    return {
        "embed": (jax.random.normal(ks[0], (c["vocab_size"], d))
                  * c["initializer_range"]).astype(dt),
        "final_norm": jnp.ones((d,), dt),
        "layers": {
            "attn": {"wq": w(ks[1], (n, d, h * hd), d),
                     "wk": w(ks[2], (n, d, kv * hd), d),
                     "wv": w(ks[3], (n, d, kv * hd), d),
                     "wo": w(ks[4], (n, h * hd, d), h * hd)},
            "mlp": {"w_gate": w(ks[5], (n, d, ff), d),
                    "w_up": w(ks[6], (n, d, ff), d),
                    "w_down": w(ks[7], (n, ff, d), ff)},
            "norm1": jnp.ones((n, d), dt),
            "norm2": jnp.ones((n, d), dt),
        },
    }


def loss(c: dict):
    """``f(params, rows, key, qm) -> scalar``; ``qm`` rounds each matmul's
    activation operand (the identity for the reference itself)."""
    d, h, kv, hd, _, _ = _dims(c)
    eps, theta = c["rms_norm_eps"], c["rope_theta"]

    def norm(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w

    def rope(x, pos):
        freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
        ang = pos[:, None] * freqs
        cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
        x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)

    def f(params, rows, key, qm):
        del key                                  # no dropout
        tok = rows["tokens"]
        B, S = tok.shape
        pos = jnp.arange(S, dtype=jnp.float32)
        causal = jnp.tril(jnp.ones((S, S), bool))

        def mm(a, w):
            return jnp.matmul(qm(a), w)

        @jax.checkpoint
        def layer(x, lp):
            a = norm(x, lp["norm1"])
            q = rope(mm(a, lp["attn"]["wq"]).reshape(B, S, h, hd), pos)
            k = rope(mm(a, lp["attn"]["wk"]).reshape(B, S, kv, hd), pos)
            v = mm(a, lp["attn"]["wv"]).reshape(B, S, kv, hd)
            k = jnp.repeat(k, h // kv, axis=2)   # query head j reads kv j//g
            v = jnp.repeat(v, h // kv, axis=2)
            s = jnp.einsum("bqhd,bkhd->bhqk", qm(q), qm(k)) / hd ** 0.5
            p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", qm(p), qm(v))
            x = x + mm(o.reshape(B, S, h * hd), lp["attn"]["wo"])
            a = norm(x, lp["norm2"])
            m = jax.nn.silu(mm(a, lp["mlp"]["w_gate"])) * mm(a, lp["mlp"]["w_up"])
            return x + mm(m, lp["mlp"]["w_down"]), None

        x = params["embed"][tok]
        x, _ = jax.lax.scan(layer, x, params["layers"])
        x = norm(x, params["final_norm"])
        logits = mm(x[:, :-1], params["embed"].T)
        lab = rows["labels"][:, 1:]
        ll = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - ll)
    return f


def param_count(c: dict) -> int:
    d, h, kv, hd, ff, n = _dims(c)
    layer = d * h * hd * 2 + d * kv * hd * 2 + 3 * d * ff + 2 * d
    return n * layer + c["vocab_size"] * d + d


def flops_per_sample(c: dict, traffic: dict) -> int:
    """Forward and backward FLOPs one trained token needs: six per weight
    of every matmul (the tied head included, the embedding lookup not)
    plus causal attention's score and value products over the full
    sequence, 12 * layers * heads * head size * sequence length (the
    convention of PaLM, appendix B). The loss re-evaluation, remat
    recompute and the optimizer are not counted."""
    d, h, kv, hd, ff, n = _dims(c)
    matmul = n * (d * h * hd * 2 + d * kv * hd * 2 + 3 * d * ff) \
        + c["vocab_size"] * d
    return 6 * matmul + 12 * n * h * hd * traffic["seq_len"]


def samples_per_worker(traffic: dict) -> int:
    """Tokens one silo trains on in a round."""
    return traffic["rows_per_worker"] * traffic["seq_len"]
