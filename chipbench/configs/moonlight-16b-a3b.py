"""moonlight-16b-a3b: plain float32 reference, weights and FLOP counts.

A DeepSeek-V3 decoder (Hugging Face ``modeling_deepseek``) as the
configuration states, one chip's share of it, fine-tuned with LoRA:

- token embedding, untied output head, RMSNorm (eps 1e-5, weight after
  normalising) before attention, before the MLP and at the end;
- latent attention with no query bottleneck: q = x q_proj, split into 128
  nope and 64 rope dims per head; kv_a_proj_with_mqa gives a 512-dim
  latent (RMSNorm) and one 64-dim rope key shared by the heads; kv_b_proj
  maps the latent to 128 key and 128 value dims per head; RoPE (theta
  50000) on the two halves of the rope dims; causal softmax scaled by
  1/sqrt(192); o_proj;
- layer 0 a SiLU-gated MLP of width 11,264; the others a MoE layer: scores
  sigmoid(x router), the 6 experts of the highest score plus selection
  bias, weights the chosen scores over their sum times 2.446; of the
  router's 64 experts this share holds 8, and each adds its SiLU-gated
  output (width 1,408) for the tokens routed to it, as a dense masked sum
  over every token; the 2 shared experts (one MLP of width 2,816, no gate)
  run on every token;
- LoRA on the four attention projections: y = x W + (x A) B * alpha / r.

The trained params are the adapters only (``init``). The frozen base is
drawn inside ``loss`` from ``base_seed`` by the scheme the configuration's
``assumed.weights`` states, in bfloat16 as the program holds it, and
computed in float32. The loss is next-token cross-entropy averaged over
every position but the last. Attention runs a block of queries at a time,
and each layer is recomputed in the backward pass, so that one worker at
8,192 tokens fits on a chip beside nothing else.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

TARGETS = {"q_proj": "wq", "kv_a_proj_with_mqa": "wkv_a",
           "kv_b_proj": "wkv_b", "o_proj": "wo"}
QUERY_BLOCK = 1024


def program(c: dict) -> dict:
    """Keyword arguments of the program's model and training configs."""
    from repro.configs.base import LoRAConfig, MLAConfig, MoEConfig
    t, lo = c["train"], c["lora"]
    return {
        "model": dict(
            name=c["name"], family="moe", num_layers=c["num_hidden_layers"],
            first_dense_layers=c["first_k_dense_replace"],
            d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
            num_kv_heads=c["num_key_value_heads"],
            d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
            attn_type="mla",
            mla=MLAConfig(q_lora_rank=c["q_lora_rank"] or 0,
                          kv_lora_rank=c["kv_lora_rank"],
                          qk_nope_head_dim=c["qk_nope_head_dim"],
                          qk_rope_head_dim=c["qk_rope_head_dim"],
                          v_head_dim=c["v_head_dim"]),
            moe=MoEConfig(num_experts=c["router_experts"],
                          top_k=c["num_experts_per_tok"],
                          d_ff_expert=c["moe_intermediate_size"],
                          num_shared_experts=c["n_shared_experts"],
                          d_ff_shared=c["moe_intermediate_size"],
                          scoring=c["scoring_func"],
                          routed_scaling=c["routed_scaling_factor"],
                          shared_gate=False,
                          experts_held=(c["experts_held_from"],
                                        c["n_routed_experts"])),
            lora=LoRAConfig(rank=lo["r"], alpha=float(lo["lora_alpha"]),
                            targets=tuple(lo["target_modules"]),
                            base_seed=c["base_seed"]),
            rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"],
            tie_embeddings=c["tie_word_embeddings"], dtype=c["torch_dtype"]),
        "train": dict(optimizer=t["optimizer"], lr=t["lr"],
                      adam_b1=t["adam_b1"], adam_b2=t["adam_b2"],
                      adam_eps=t["adam_eps"], weight_decay=t["weight_decay"],
                      grad_clip=t["grad_clip"], remat=t["remat"],
                      kv_chunk=t["kv_chunk"], local_steps=t["local_steps"]),
    }


def _dims(c):
    return dict(d=c["hidden_size"], h=c["num_attention_heads"],
                nope=c["qk_nope_head_dim"], rd=c["qk_rope_head_dim"],
                vd=c["v_head_dim"], rank=c["kv_lora_rank"],
                ff=c["intermediate_size"], f=c["moe_intermediate_size"],
                fs=c["moe_intermediate_size"] * c["n_shared_experts"],
                E=c["router_experts"], n=c["n_routed_experts"],
                k=c["num_experts_per_tok"], V=c["vocab_size"],
                nd=c["first_k_dense_replace"],
                nm=c["num_hidden_layers"] - c["first_k_dense_replace"])


def _attn_shapes(c, L):
    m = _dims(c)
    d, h = m["d"], m["h"]
    return {"wq": (L, d, h * (m["nope"] + m["rd"])),
            "wkv_a": (L, d, m["rank"] + m["rd"]),
            "kv_a_norm": (L, m["rank"]),
            "wkv_b": (L, m["rank"], h * (m["nope"] + m["vd"])),
            "wo": (L, h * m["vd"], d)}


def base_shapes(c: dict) -> dict:
    """{path: (shape, dtype)} of the frozen base, by the paths the
    configuration's weight scheme draws them under."""
    m = _dims(c)
    d, nd, nm = m["d"], m["nd"], m["nm"]
    bf, f32 = jnp.bfloat16, jnp.float32
    out = {"embed": ((m["V"], d), bf), "lm_head": ((d, m["V"]), bf),
           "final_norm": ((d,), bf)}
    for stack, L in (("dense_layers", nd), ("layers", nm)):
        for k, s in _attn_shapes(c, L).items():
            out[f"{stack}/attn/{k}"] = (s, bf)
        out[f"{stack}/norm1"] = ((L, d), bf)
        out[f"{stack}/norm2"] = ((L, d), bf)
    out.update({
        "dense_layers/mlp/w_gate": ((nd, d, m["ff"]), bf),
        "dense_layers/mlp/w_up": ((nd, d, m["ff"]), bf),
        "dense_layers/mlp/w_down": ((nd, m["ff"], d), bf),
        "layers/moe/router": ((nm, d, m["E"]), bf),
        "layers/moe/router_bias": ((nm, m["E"]), f32),
        "layers/moe/w_gate": ((nm, m["n"], d, m["f"]), bf),
        "layers/moe/w_up": ((nm, m["n"], d, m["f"]), bf),
        "layers/moe/w_down": ((nm, m["n"], m["f"], d), bf),
        "layers/moe/shared/w_gate": ((nm, d, m["fs"]), bf),
        "layers/moe/shared/w_up": ((nm, d, m["fs"]), bf),
        "layers/moe/shared/w_down": ((nm, m["fs"], d), bf),
    })
    return out


def base(c: dict) -> dict:
    """The frozen base, drawn from ``base_seed``: a nested dict whose
    leaf at path "a/b/c" is ``base(c)["a"]["b"]["c"]``."""
    root = jax.random.PRNGKey(c["base_seed"])
    out = {}
    for path, (shape, dt) in base_shapes(c).items():
        name = path.rsplit("/", 1)[-1]
        if "norm" in name:
            leaf = jnp.ones(shape, dt)
        else:
            std = {"embed": 0.02, "router_bias": 0.1}.get(name)
            std = shape[-2] ** -0.5 if std is None else std
            key = jax.random.fold_in(root,
                                     zlib.crc32(path.encode()) & 0x7FFFFFFF)
            # the draw fenced off from its scaling: no compiler may fold
            # the two multiplies into one and round differently
            z = jax.lax.optimization_barrier(
                jax.random.normal(key, shape, jnp.float32))
            leaf = (z * std).astype(dt)
        *parents, name = path.split("/")
        node = out
        for p in parents:
            node = node.setdefault(p, {})
        node[name] = leaf
    return out


def init(c: dict, key):
    """The trained params: f32 LoRA adapters of every target projection,
    A kaiming-uniform (bound 1/sqrt(in)), B zeros."""
    m = _dims(c)
    r = c["lora"]["r"]
    out = {}
    for stack, L in (("dense_layers", m["nd"]), ("layers", m["nm"])):
        shapes = _attn_shapes(c, L)
        out[stack] = {}
        for t in c["lora"]["target_modules"]:
            _, fan_in, fan_out = shapes[TARGETS[t]]
            key, sub = jax.random.split(key)
            b = fan_in ** -0.5
            out[stack][t] = {
                "a": jax.random.uniform(sub, (L, fan_in, r), jnp.float32,
                                        -b, b),
                "b": jnp.zeros((L, r, fan_out), jnp.float32)}
    return out


def loss(c: dict):
    """``f(params, rows, key, qm) -> scalar``; ``qm`` rounds each matmul's
    operands (the identity for the reference itself)."""
    m = _dims(c)
    h, nope, rd, vd, rank = m["h"], m["nope"], m["rd"], m["vd"], m["rank"]
    eps, theta = c["rms_norm_eps"], float(c["rope_theta"])
    scale = c["lora"]["lora_alpha"] / c["lora"]["r"]
    lo, n, k = c["experts_held_from"], m["n"], m["k"]

    def norm(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * w.astype(jnp.float32)

    def rope(x, pos):
        freqs = 1.0 / theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd)
        ang = pos[:, None] * freqs
        cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
        x1, x2 = x[..., :rd // 2], x[..., rd // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)

    def f(params, rows, key, qm):
        del key                                  # no dropout

        def mm(a, w):
            return jnp.matmul(qm(a), qm(w.astype(jnp.float32)))

        def proj(x, w, ad):
            return mm(x, w) + scale * mm(mm(x, ad["a"]), ad["b"])

        def swiglu(x, wg, wu, wd):
            return mm(jax.nn.silu(mm(x, wg)) * mm(x, wu), wd)

        def attention(x, p, ad, pos):
            B, S, _ = x.shape
            q = proj(x, p["wq"], ad["q_proj"]).reshape(B, S, h, nope + rd)
            kv_a = proj(x, p["wkv_a"], ad["kv_a_proj_with_mqa"])
            latent = norm(kv_a[..., :rank], p["kv_a_norm"])
            k_rope = rope(kv_a[..., None, rank:], pos)           # (B,S,1,rd)
            kv = proj(latent, p["wkv_b"], ad["kv_b_proj"]).reshape(
                B, S, h, nope + vd)
            q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos)], -1)
            kk = jnp.concatenate(
                [kv[..., :nope], jnp.broadcast_to(k_rope, (B, S, h, rd))], -1)
            v = kv[..., nope:]
            qb = QUERY_BLOCK if S % QUERY_BLOCK == 0 else S

            @jax.checkpoint
            def block(i):
                qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
                s = jnp.einsum("bqhd,bkhd->bhqk", qm(qi), qm(kk)) \
                    / (nope + rd) ** 0.5
                causal = (i * qb + jnp.arange(qb))[:, None] \
                    >= jnp.arange(S)[None]
                pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
                return jnp.einsum("bhqk,bkhd->bqhd", qm(pr), qm(v))
            o = jax.lax.map(block, jnp.arange(S // qb))    # (nb,B,qb,h,vd)
            o = jnp.moveaxis(o, 0, 1).reshape(B, S, h * vd)
            return proj(o, p["wo"], ad["o_proj"])

        def moe(x, p):
            s = jax.nn.sigmoid(mm(x, p["router"]))
            _, idx = jax.lax.top_k(s + p["router_bias"], k)
            w = jnp.take_along_axis(s, idx, axis=-1)
            w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) \
                * c["routed_scaling_factor"]
            sh = p["shared"]
            out = swiglu(x, sh["w_gate"], sh["w_up"], sh["w_down"])
            for e in range(n):              # each held expert, every token
                we = jnp.sum(jnp.where(idx == lo + e, w, 0.0), axis=-1)
                out = out + we[..., None] * swiglu(
                    x, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
            return out

        P = base(c)
        tok = rows["tokens"]
        pos = jnp.arange(tok.shape[1], dtype=jnp.float32)

        @jax.checkpoint
        def layer(x, inp):
            lp, ad = inp
            x = x + attention(norm(x, lp["norm1"]), lp["attn"], ad, pos)
            a = norm(x, lp["norm2"])
            if "mlp" in lp:
                mp = lp["mlp"]
                return x + swiglu(a, mp["w_gate"], mp["w_up"],
                                  mp["w_down"]), None
            return x + moe(a, lp["moe"]), None

        x = P["embed"][tok].astype(jnp.float32)
        for stack in ("dense_layers", "layers"):
            x, _ = jax.lax.scan(layer, x, (P[stack], params[stack]))
        x = norm(x, P["final_norm"])
        logits = mm(x[:, :-1], P["lm_head"])
        lab = rows["labels"][:, 1:]
        ll = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - ll)
    return f


def param_count(c: dict) -> int:
    """The frozen base's parameters and the adapters'."""
    import math
    frozen = sum(math.prod(s) for s, _ in base_shapes(c).values())
    adapters = sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(lambda k: init(c, k), jax.random.PRNGKey(0))))
    return frozen + adapters


def held_expert_flops(c: dict, traffic: dict) -> int:
    """FLOPs one token-expert pair routed to a held expert needs in a
    round: its SwiGLU expert's forward (gate, up and down products), the
    input gradient through it (as many again), and the forward of the
    post-step loss where the job scores it (``w_loss`` > 0). The
    recomputation of remat is not counted."""
    m = _dims(c)
    passes = 2 + (traffic["protocol"]["w_loss"] > 0)
    return passes * 2 * 3 * m["d"] * m["f"]


def flops_per_sample(c: dict, traffic: dict) -> int:
    """Model FLOPs one trained token needs: the frozen base's matmuls
    forward and back to their inputs (4 per weight a token meets: the
    attention projections, the dense MLP, the router, the shared experts,
    the expected share of held experts, top_k * held / router_experts
    pairs a token, and the head; the embedding lookup not), the adapters
    forward, back to their inputs and to their weights (6 per weight), and
    causal attention's score and value products forward and back (3 times
    the forward's 2 * (seq_len / 2) * heads * (qk + v head dims) a layer).
    The post-step loss, remat recompute and the optimizer are not
    counted."""
    m = _dims(c)
    d, L = m["d"], m["nd"] + m["nm"]
    attn = sum(s[1] * s[2] for t, s in _attn_shapes(c, 1).items()
               if t != "kv_a_norm")
    held = m["k"] * m["n"] / m["E"]
    moe = d * m["E"] + 3 * d * m["fs"] + held * 3 * d * m["f"]
    base_w = L * attn + m["nd"] * 3 * d * m["ff"] + m["nm"] * moe \
        + d * m["V"]
    adapters = sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(lambda k: init(c, k), jax.random.PRNGKey(0))))
    S = traffic["seq_len"]
    causal = 3 * L * 2 * (S // 2) * m["h"] * (m["nope"] + m["rd"] + m["vd"])
    return int(4 * base_w + 6 * adapters + causal)


def samples_per_worker(traffic: dict) -> int:
    """Tokens one silo trains on in a round."""
    return traffic["rows_per_worker"] * traffic["seq_len"]
