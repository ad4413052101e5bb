"""Config system: architecture and run configuration dataclasses.

Every assigned architecture is a `ModelConfig` in repro/configs/<id>.py; the
registry (repro.configs.registry) resolves `--arch <id>` strings.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    n_heads: int
    n_kv: int
    head_dim: int
    rope_theta: float = 1e4
    rotary_frac: float = 1.0       # fraction of head_dim rotated (ChatGLM: 0.5)
    window: Optional[int] = None   # native sliding window (Mistral: 4096)
    qkv_bias: bool = False
    causal: bool = True


@dataclasses.dataclass(frozen=True)
class YaRNConfig:
    """YaRN RoPE scaling as DeepSeek-V2's modelling code has it: the rope
    frequencies ramp from the plain ones (fast dims) to the plain ones over
    `factor` (slow dims), and attention scales by mscale(factor, mscale_all_dim)
    squared."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head Latent Attention (DeepSeek-V2 / MiniCPM3). q_lora_rank 0:
    no query low-rank, q = x W_q with no query norm (DeepSeek-V2-Lite)."""

    n_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_head_dim: int
    rope_theta: float = 1e4
    yarn: Optional[YaRNConfig] = None


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff: int
    capacity_factor: float = 1.25
    dense_residual_ff: int = 0     # Arctic: parallel dense MLP of this width
    router_aux_weight: float = 0.01
    # DeepSeek-style routing: renormalise the top-k weights only if asked,
    # then scale them; "switch" aux counts the top-1 expert over the batch,
    # "seq" is DeepSeek's sequence-wise balance loss over all top-k slots
    norm_topk_prob: bool = True
    routed_scaling: float = 1.0
    aux: str = "switch"            # switch | seq
    router_fp32: bool = True       # False: router weight in the model dtype
    shared_ff: int = 0             # shared experts, as one SwiGLU this wide
    # dropless: every routed (token, k) row is computed, by grouped matmuls
    # over the held experts; held = (first, count) of the n_experts that
    # this chip holds (None: all of them)
    dropless: bool = False
    held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        if self.held is not None:      # a JSON list becomes a (hashable) pair
            object.__setattr__(self, "held", tuple(self.held))

    @property
    def held_range(self) -> Tuple[int, int]:
        return self.held if self.held is not None else (0, self.n_experts)


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """Mamba-2 SSD mixer."""

    d_state: int = 128
    head_dim: int = 64
    expand: int = 2
    conv_width: int = 4
    chunk: int = 256
    n_groups: int = 1


@dataclasses.dataclass(frozen=True)
class RGLRUConfig:
    """RecurrentGemma / Griffin recurrent block."""

    lru_width: int
    conv_width: int = 4
    c_constant: float = 8.0


@dataclasses.dataclass(frozen=True)
class EncoderConfig:
    """Whisper-style encoder consuming precomputed frame embeddings (the conv
    + mel frontend is a stub per the assignment)."""

    n_layers: int
    n_frames: int = 1500
    d_input: int = 768             # frontend output dim (== d_model for whisper)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str                 # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    vocab: int
    block_pattern: Tuple[str, ...]  # cycled over layers: attn|mla|moe|mla_moe|ssm|rglru|local
    d_ff: int = 0
    mlp_act: str = "silu"
    mlp_gated: bool = True
    attn: Optional[AttnConfig] = None
    mla: Optional[MLAConfig] = None
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    rglru: Optional[RGLRUConfig] = None
    encoder: Optional[EncoderConfig] = None
    vlm_img_tokens: int = 0        # >0: prepend this many projected patch embeds
    vlm_d_vision: int = 1024
    norm: str = "rmsnorm"          # rmsnorm | layernorm
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    learned_positions: int = 0     # >0 (whisper): learned abs positions
    embed_scale: bool = False      # multiply embeddings by sqrt(d) (gemma-style)
    logit_softcap: float = 0.0
    dtype: object = jnp.bfloat16
    remat: bool = True
    # long-context variant: dense/full-attention archs get a sliding-window
    # attention cache of this size for the long_500k decode shape only.
    long_context_window: int = 4096
    source: str = ""               # citation
    # layers run once ahead of the scanned pattern (DeepSeek's
    # first_k_dense_replace dense layers); counted in n_layers
    lead_layers: Tuple[str, ...] = ()

    # -- derived -------------------------------------------------------------

    @property
    def pattern_repeats(self) -> int:
        return (self.n_layers - len(self.lead_layers)) // len(self.block_pattern)

    @property
    def tail_layers(self) -> Tuple[str, ...]:
        r = (self.n_layers - len(self.lead_layers)) % len(self.block_pattern)
        return self.block_pattern[:r]

    def layer_kind(self, i: int) -> str:
        if i < len(self.lead_layers):
            return self.lead_layers[i]
        i -= len(self.lead_layers)
        return self.block_pattern[i % len(self.block_pattern)]

    @property
    def supports_long_decode(self) -> bool:
        """True if a 524k-token decode has bounded per-step state."""
        if self.encoder is not None:
            return False           # enc-dec full attention (whisper): skipped
        return True                # SSM/hybrid native; dense via SWA variant

    @property
    def is_native_long(self) -> bool:
        kinds = set(self.block_pattern)
        if kinds <= {"ssm", "rglru", "local"}:
            return True
        return (self.attn is not None and self.attn.window is not None
                and "attn" not in self.block_pattern)


def reduce_for_smoke(cfg: ModelConfig, *, d_model: int = 256,
                     n_layers: int | None = None, vocab: int = 512,
                     d_ff: int = 512, n_experts: int = 4) -> ModelConfig:
    """Reduced same-family variant for CPU smoke tests (<=2 layers, d<=512)."""
    n_layers = n_layers if n_layers is not None else min(
        2 * len(cfg.block_pattern), max(2, len(cfg.block_pattern)))
    kw = {}
    if cfg.attn is not None:
        hd = 32
        n_heads = max(2, min(4, cfg.attn.n_heads))
        n_kv = max(1, min(cfg.attn.n_kv, n_heads))
        window = None if cfg.attn.window is None else 64
        kw["attn"] = dataclasses.replace(cfg.attn, n_heads=n_heads, n_kv=n_kv,
                                         head_dim=hd, window=window)
    if cfg.mla is not None:
        kw["mla"] = dataclasses.replace(
            cfg.mla, n_heads=4, q_lora_rank=64 if cfg.mla.q_lora_rank else 0,
            kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16)
    if cfg.moe is not None:
        kw["moe"] = dataclasses.replace(
            cfg.moe, n_experts=n_experts, top_k=min(cfg.moe.top_k, 2),
            d_ff=d_ff // 2,
            dense_residual_ff=(d_ff // 2 if cfg.moe.dense_residual_ff else 0),
            shared_ff=(d_ff // 2 if cfg.moe.shared_ff else 0),
            held=(0, n_experts // 2) if cfg.moe.held else None)
    if cfg.ssm is not None:
        kw["ssm"] = dataclasses.replace(cfg.ssm, d_state=16, head_dim=16,
                                        chunk=16)
    if cfg.rglru is not None:
        kw["rglru"] = dataclasses.replace(cfg.rglru, lru_width=d_model)
    if cfg.encoder is not None:
        kw["encoder"] = dataclasses.replace(cfg.encoder, n_layers=2,
                                            n_frames=16, d_input=d_model)
    if cfg.vlm_img_tokens:
        kw["vlm_img_tokens"] = 8
        kw["vlm_d_vision"] = 64
    if cfg.learned_positions:
        kw["learned_positions"] = 4096
    return dataclasses.replace(
        cfg, name=cfg.name + "-smoke", d_model=d_model, n_layers=n_layers,
        vocab=vocab, d_ff=d_ff, dtype=jnp.float32, remat=False,
        long_context_window=64, **kw)


def _moe_params(d: int, m: MoEConfig) -> int:
    held = m.held_range[1]
    return (held * 3 * d * m.d_ff + d * m.n_experts
            + 3 * d * (m.dense_residual_ff + m.shared_ff))


def param_count_estimate(cfg: ModelConfig) -> float:
    """Rough N for FSDP decisions and 6ND math (exact count comes from defs)."""
    d = cfg.d_model
    n = 2.0 * cfg.vocab * d
    for i in range(cfg.n_layers):
        k = cfg.layer_kind(i)
        if k in ("attn", "local"):
            a = cfg.attn
            n += d * (a.n_heads + 2 * a.n_kv + a.n_heads) * a.head_dim
            n += (3 if cfg.mlp_gated else 2) * d * cfg.d_ff
        elif k in ("mla", "mla_moe"):
            m = cfg.mla
            qk = m.n_heads * (m.qk_nope_dim + m.qk_rope_dim)
            n += (d * m.q_lora_rank + m.q_lora_rank * qk if m.q_lora_rank
                  else d * qk)
            n += d * (m.kv_lora_rank + m.qk_rope_dim)
            n += m.kv_lora_rank * m.n_heads * (m.qk_nope_dim + m.v_head_dim)
            n += m.n_heads * m.v_head_dim * d
            if k == "mla":
                n += (3 if cfg.mlp_gated else 2) * d * cfg.d_ff
            else:
                n += _moe_params(d, cfg.moe)
        elif k == "moe":
            a = cfg.attn
            n += d * (a.n_heads + 2 * a.n_kv + a.n_heads) * a.head_dim
            n += _moe_params(d, cfg.moe)
        elif k == "ssm":
            s = cfg.ssm
            d_in = s.expand * d
            n += d * (2 * d_in + 2 * s.n_groups * s.d_state + d_in // s.head_dim)
            n += d_in * d
        elif k == "rglru":
            r = cfg.rglru
            n += 2 * d * r.lru_width + r.lru_width * d + 3 * r.lru_width
    if cfg.encoder is not None:
        a = cfg.attn
        per = d * 4 * a.n_heads * a.head_dim + 2 * d * cfg.d_ff
        n += cfg.encoder.n_layers * per
        # decoder cross-attention
        n += cfg.n_layers * d * 4 * a.n_heads * a.head_dim
    return float(n)


def active_param_count_estimate(cfg: ModelConfig) -> float:
    """Active params per token (MoE: top_k of n_experts)."""
    if cfg.moe is None:
        return param_count_estimate(cfg)
    full = param_count_estimate(cfg)
    moe_layers = sum(1 for i in range(cfg.n_layers)
                     if cfg.layer_kind(i) in ("moe", "mla_moe"))
    all_experts = (moe_layers * cfg.moe.held_range[1] * 3 * cfg.d_model
                   * cfg.moe.d_ff)
    active = moe_layers * cfg.moe.top_k * 3 * cfg.d_model * cfg.moe.d_ff
    return float(full - all_experts + active)
