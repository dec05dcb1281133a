"""DeepSeek-V2-Lite [arXiv:2405.04434; HF deepseek-ai/DeepSeek-V2-Lite] —
MLA + 64-expert MoE with YaRN rope.

27 layers, d_model 2048, 16 heads of MLA in every layer with no query
down-projection (q_lora_rank null), kv_lora_rank 512, nope 128, rope 64,
v 128. Layer 0 is dense (d_ff 10944); layers 1-26 route each token to 6 of
64 experts of d_ff 1408 (softmax over all 64, greedy top-6, gates not
renormalised, routed_scaling_factor 1) beside 2 shared experts. YaRN:
factor 40 over 4096 original positions, beta_fast 32, beta_slow 1,
mscale = mscale_all_dim = 0.707, rope theta 1e4. Vocabulary 102400,
untied; rms_norm_eps 1e-6.

``full`` is one chip's share of 8-way expert parallelism over a v5e-8
host (attention data-parallel): every layer, the embedding and the head,
and experts 0-7 of each MoE layer, dropless; the router still scores all
64. ``n_held_experts`` 0 holds all 64.
"""
from repro.models import MLAConfig, MoEConfig, ModelConfig, YarnScaling


def make(variant: str = "full", arch: str = "deepseek-v2-lite"
         ) -> ModelConfig:
    if variant == "smoke":
        return ModelConfig(
            name=arch + "-smoke", family="moe", n_layers=3, d_model=64,
            n_heads=4, n_kv_heads=4, d_ff=128, vocab=512, dtype="float32",
            block_pattern=("mla",) + ("mla_moe",) * 2,
            mla=MLAConfig(q_lora_rank=None, kv_lora_rank=32,
                          qk_nope_head_dim=16, qk_rope_head_dim=8,
                          v_head_dim=16),
            moe=MoEConfig(n_experts=4, top_k=2, d_ff_expert=32,
                          n_shared_experts=2, dropless=True,
                          n_held_experts=2),
            rope_scaling=YarnScaling(original_max_position_embeddings=8),
            norm_eps=1e-6, vocab_pad_multiple=8)
    return ModelConfig(
        name=arch, family="moe", n_layers=27, d_model=2048,
        n_heads=16, n_kv_heads=16, d_ff=10944, vocab=102400,
        block_pattern=("mla",) + ("mla_moe",) * 26,
        mla=MLAConfig(q_lora_rank=None, kv_lora_rank=512,
                      qk_nope_head_dim=128, qk_rope_head_dim=64,
                      v_head_dim=128),
        moe=MoEConfig(n_experts=64, top_k=6, d_ff_expert=1408,
                      n_shared_experts=2, dropless=True, n_held_experts=8),
        rope_theta=10000.0, rope_scaling=YarnScaling(), norm_eps=1e-6,
        sliding_window=8192 if variant == "long" else None)
