"""DeepSeek-V2-Lite [arXiv:2405.04434; huggingface.co/deepseek-ai/
DeepSeek-V2-Lite config.json]: 27 layers, hidden 2048, MLA without query
compression (q_lora_rank null; kv_lora 512, nope/rope/v head dims
128/64/128, 16 heads), untied vocab 102,400; layer 0 dense (d_ff 10,944),
layers 1-26 MoE with 64 routed experts of width 1,408, 6 per token,
softmax scores not renormalized (norm_topk_prob false), plus 2 shared
experts.

`share_config` is one rank's share of an aggregator that spreads each
layer over `ranks` chips: routed experts expert-parallel (64 / ranks a
chip), attention heads tensor-parallel (16 / ranks a chip; the latent
down-projections, router and norms held whole), the dense MLP and the
shared experts held whole, and 1 / ranks of the vocabulary.  Depth keeps
the leading dense layer and `moe_layers` MoE layers; the rest would sit
on further pipeline stages.  No width changes."""
from repro.models.config import MLAConfig, ModelConfig, MoEConfig


def get_config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite",
        arch_type="moe",
        num_layers=27,
        d_model=2048,
        num_heads=16,
        num_kv_heads=16,
        d_ff=10944,                 # dense prefix layer MLP
        vocab_size=102400,
        prefix_kinds=("dense",),
        scan_pattern=("moe",),
        act="swiglu",
        norm="rmsnorm",
        rope_theta=10000.0,
        mla=MLAConfig(
            kv_lora_rank=512,
            q_lora_rank=None,
            nope_head_dim=128,
            rope_head_dim=64,
            v_head_dim=128,
        ),
        moe=MoEConfig(
            num_experts=64,
            top_k=6,
            d_ff_expert=1408,
            num_shared_experts=2,
            d_ff_residual=1408,
            norm_topk_prob=False,
        ),
    )


def share_config(ranks: int = 8, rank: int = 0,
                 moe_layers: int = 4) -> ModelConfig:
    """Rank `rank`'s share of a `ranks`-chip layer (module docstring)."""
    full = get_config()
    mc = full.moe
    per = mc.num_experts // ranks
    return full.with_overrides(
        name=f"deepseek-v2-lite-ep{ranks}-rank{rank}",
        num_layers=len(full.prefix_kinds) + moe_layers,
        num_heads=full.num_heads // ranks,
        num_kv_heads=full.num_kv_heads // ranks,
        vocab_size=full.vocab_size // ranks,
        moe=MoEConfig(
            num_experts=mc.num_experts,
            top_k=mc.top_k,
            d_ff_expert=mc.d_ff_expert,
            num_shared_experts=mc.num_shared_experts,
            d_ff_residual=mc.d_ff_residual,
            norm_topk_prob=mc.norm_topk_prob,
            held_experts=tuple(range(rank * per, (rank + 1) * per)),
        ),
    )


def reduced_config() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite-smoke",
        arch_type="moe",
        num_layers=3,
        d_model=256,
        num_heads=4,
        num_kv_heads=4,
        d_ff=512,
        vocab_size=512,
        prefix_kinds=("dense",),
        scan_pattern=("moe",),
        act="swiglu",
        norm="rmsnorm",
        mla=MLAConfig(
            kv_lora_rank=64,
            q_lora_rank=None,
            nope_head_dim=32,
            rope_head_dim=16,
            v_head_dim=32,
        ),
        moe=MoEConfig(
            num_experts=4,
            top_k=2,
            d_ff_expert=128,
            num_shared_experts=2,
            d_ff_residual=128,
            norm_topk_prob=False,
        ),
        vocab_pad_multiple=16,
    )
