"""moonlight-16b-a3b — DeepSeek-V3 block: MLA without a query bottleneck,
one leading dense layer, then MoE layers of 64 routed experts (top-6,
sigmoid routing with a selection bias, normalised weights scaled by
2.446) and 2 ungated shared experts. Federated as LoRA fine-tuning: rank-16
adapters on the four MLA projections over a frozen base.
[hf:moonshotai/Moonlight-16B-A3B]"""
from repro.configs.base import LoRAConfig, MLAConfig, ModelConfig, MoEConfig

TARGETS = ("q_proj", "kv_a_proj_with_mqa", "kv_b_proj", "o_proj")

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    num_layers=27,
    first_dense_layers=1,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=11264,
    vocab_size=163840,
    attn_type="mla",
    mla=MLAConfig(q_lora_rank=0, kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(num_experts=64, top_k=6, d_ff_expert=1408,
                  num_shared_experts=2, d_ff_shared=1408,
                  scoring="sigmoid", routed_scaling=2.446,
                  shared_gate=False, experts_held=(0, 64)),
    lora=LoRAConfig(rank=16, alpha=32.0, targets=TARGETS),
    rope_theta=50_000.0,
    norm_eps=1e-5,
    source="hf:moonshotai/Moonlight-16B-A3B",
)


def smoke_config() -> ModelConfig:
    """3 layers (1 dense + 2 MoE), 8 routed experts of which 4 are held."""
    return CONFIG.replace(
        num_layers=3, d_model=64, num_heads=4, num_kv_heads=4, d_ff=128,
        vocab_size=256,
        mla=MLAConfig(q_lora_rank=0, kv_lora_rank=16, qk_nope_head_dim=16,
                      qk_rope_head_dim=8, v_head_dim=16),
        moe=MoEConfig(num_experts=8, top_k=3, d_ff_expert=32,
                      num_shared_experts=2, d_ff_shared=32,
                      scoring="sigmoid", routed_scaling=2.446,
                      shared_gate=False, experts_held=(0, 4)),
        lora=LoRAConfig(rank=4, alpha=8.0, targets=TARGETS),
    )
