"""DeepSeek-V2-Lite (15.7B total, 2.4B active): MLA without a query
low-rank, YaRN RoPE, one dense SwiGLU layer, then 26 layers of a 64-expert
top-6 MoE with 2 shared experts [hf:deepseek-ai/DeepSeek-V2-Lite].

`moe.held` is None here: the whole model holds every expert. A deployment
that divides the experts over chips by expert parallelism sets it to the
(first, count) slice one chip holds."""

from repro.configs.base import MLAConfig, MoEConfig, ModelConfig, YaRNConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite", arch_type="moe", n_layers=27, d_model=2048,
    vocab=102400, block_pattern=("mla_moe",), lead_layers=("mla",),
    d_ff=10944, mlp_act="silu", norm_eps=1e-6,
    mla=MLAConfig(n_heads=16, q_lora_rank=0, kv_lora_rank=512,
                  qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
                  rope_theta=1e4,
                  yarn=YaRNConfig(factor=40.0, original_max_position=4096,
                                  beta_fast=32.0, beta_slow=1.0,
                                  mscale=0.707, mscale_all_dim=0.707)),
    moe=MoEConfig(n_experts=64, top_k=6, d_ff=1408, shared_ff=2 * 1408,
                  norm_topk_prob=False, routed_scaling=1.0, aux="seq",
                  router_aux_weight=0.001, router_fp32=False, dropless=True),
    source="hf:deepseek-ai/DeepSeek-V2-Lite",
)
