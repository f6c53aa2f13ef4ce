"""deepseek-v2-lite [moe] — 27L d_model=2048 16H, multi-head latent
attention (no q LoRA; kv_lora_rank=512, qk_nope 128 + qk_rope 64, v 128,
YaRN factor 40 over 4096), layer 0 dense (d_ff=10944), then 64 routed
experts top-6 (softmax, greedy, no renormalisation) + 2 shared, expert
d_ff=1408, vocab 102400. 15.71B parameters.
[arXiv:2405.04434; huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json]

One chip holds every expert here. A deployment that splits each layer's
experts over chips states ``expert_parallel`` (the benchmark's: 8 chips,
each holding 8 of 64, ``bench/configs/deepseek_v2_lite.json``)."""

import dataclasses

from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10944,           # the single dense layer's FFN
    vocab=102400,
    head_dim=128,
    rope_theta=10_000.0,
    norm_eps=1e-6,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_scaling_factor=40.0,
    rope_original_max_position=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    n_experts=64,
    n_shared_experts=2,
    top_k=6,
    expert_d_ff=1408,
    first_k_dense=1,
    norm_topk_prob=False,
)


def smoke() -> ArchConfig:
    """Tiny widths; a share of 4 of the router's 16 experts."""
    return dataclasses.replace(
        CONFIG,
        name="deepseek-v2-lite-smoke",
        n_layers=3,
        d_model=64,
        n_heads=4,
        n_kv_heads=4,
        d_ff=160,
        vocab=256,
        head_dim=16,
        kv_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        n_experts=16,
        n_shared_experts=2,
        top_k=4,
        expert_d_ff=32,
        expert_parallel=4,
        attn_chunk=32,
        compute_dtype="float32",
    )
