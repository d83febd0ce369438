"""deepseek-v2-lite [moe] — 27L d_model=2048 16H vocab=102400; MLA without
q LoRA (kv_lora 512, nope 128 + rope 64, v 128), YaRN rope (factor 40 over
4096 positions), layer 0 a dense SwiGLU of 10944, then 64 routed experts of
1408 (softmax, greedy top-6, weights not renormalised) plus 2 shared experts.
[hf:deepseek-ai/DeepSeek-V2-Lite config.json]

Rotary layout.  The published ``apply_rotary_pos_emb`` de-interleaves the
64 rope dims (evens first, then odds) and rotates the halves with
``rotate_half``: it turns each adjacent pair ``(2i, 2i+1)`` by frequency
``i``, then leaves the pairs split in half.  The program turns the same
adjacent pairs by the same frequencies and keeps them interleaved.  The two
differ by one permutation applied to queries and keys alike, so every
``q . k`` is the same and the published weights load without reordering.

The two shared experts are one SwiGLU of width 2 x 1408, as published
(``DeepseekV2MLP(intermediate_size=moe_intermediate_size * n_shared_experts)``).
"""
from repro.configs.base import ArchConfig, MLAConfig, MoEConfig, YarnScaling

CONFIG = ArchConfig(
    name="deepseek-v2-lite",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=192,               # nope 128 + rope 64 (MLA)
    d_ff=1408,                  # routed-expert FFN width
    vocab_size=102400,
    activation="silu",
    rope_theta=1e4,
    rope_scaling=YarnScaling(factor=40.0, original_max_position=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                             mscale_all_dim=0.707),
    tie_embeddings=False,
    moe=MoEConfig(
        num_experts=64,
        top_k=6,
        expert_d_ff=1408,
        shared_experts=2,
        shared_d_ff=1408,
        first_k_dense=1,
        first_dense_d_ff=10944,
        norm_topk_prob=False,
        routed_scaling_factor=1.0,
    ),
    mla=MLAConfig(
        q_lora_rank=None,
        kv_lora_rank=512,
        rope_head_dim=64,
        nope_head_dim=128,
        v_head_dim=128,
    ),
    use_stem=True,
)
