"""sdar-30b-a3b [moe] — 48L d_model=2048 32H (GQA kv=4) head_dim=128,
vocab=151936, 128 routed experts of width 768, top-8 renormalised, no
shared expert, every layer sparse; q/k RMSNorm per head (Qwen3-MoE
attention). SDAR-30B-A3B-Chat
[https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json]

``d_ff`` is the published dense width (``intermediate_size``); no layer
uses it (``mlp_only_layers`` is empty). Every expert is held here: a
deployment that spreads the experts over chips sets ``moe.experts_held``
(and ``moe.expert_offset``) to one chip's share."""
from .base import ModelConfig, MoEConfig


def get_config() -> ModelConfig:
    return ModelConfig(
        name="sdar-30b-a3b",
        source="https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/"
               "config.json",
        arch_type="moe", n_layers=48, d_model=2048, n_heads=32, n_kv_heads=4,
        head_dim=128, d_ff=6144, vocab_size=151936, act="silu", glu=True,
        rope_theta=1_000_000.0, norm_eps=1e-6, tie_embeddings=False,
        qk_norm=True,
        moe=MoEConfig(num_experts=128, top_k=8, expert_d_ff=768,
                      dropless=True),
    )
