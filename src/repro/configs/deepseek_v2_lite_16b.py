"""DeepSeek-V2-Lite-16B: MLA + MoE [arXiv:2405.04434].

27L d_model=2048 16H MLA (kv_lora=512, qk_nope=128, qk_rope=64, v=128,
no query compression) vocab=102400; layer 0 uses a dense 10944-wide FFN,
layers 1-26 are MoE with 64 routed experts of width 1408 (softmax
scores, greedy top-6, gates NOT renormalised: ``norm_topk_prob: false``,
``routed_scaling_factor`` 1) + 2 shared experts run as one 2816-wide
SwiGLU.  YaRN RoPE: factor 40 over an original 4096 positions, beta_fast
32, beta_slow 1, mscale = mscale_all_dim = 0.707; rms_norm_eps 1e-6.
Source: huggingface.co/deepseek-ai/DeepSeek-V2-Lite, config.json.

Fidelity note (also in DESIGN.md): the assignment line says "MoE 64e
top-6" and "2 shared+160 routed"; 160 routed is full DeepSeek-V2 — the
Lite model is 64 routed + 2 shared, which matches the 64e spec we build.
"""

from repro.models.config import MLASpec, ModelConfig, MoESpec, YarnSpec

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16, d_ff=10944,
    vocab_size=102400, rope_theta=10_000.0, norm_eps=1e-6,
    rope_scaling=YarnSpec(factor=40.0, original_max_position=4096,
                          beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                          mscale_all_dim=0.707),
    mla=MLASpec(kv_lora_rank=512, q_lora_rank=0, qk_nope_dim=128,
                qk_rope_dim=64, v_head_dim=128),
    moe=MoESpec(n_experts=64, top_k=6, d_ff_expert=1408, n_shared=2,
                d_ff_shared=2816, norm_topk=False),
    first_k_dense=1,
)
