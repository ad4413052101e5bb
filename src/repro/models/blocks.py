"""Per-layer block assembly: norm + mixer + MLP/MoE with residuals.

Block kinds (cycled through ModelConfig.block_pattern):
  attn   -- (windowed) causal self-attention + dense MLP
  local  -- sliding-window self-attention + dense MLP (hybrid models)
  mla    -- multi-head latent attention + dense MLP
  moe    -- self-attention + mixture-of-experts MLP (+ optional dense residual)
  mla_moe -- multi-head latent attention + mixture-of-experts MLP (DeepSeek-V2)
  ssm    -- Mamba-2 SSD mixer (no separate MLP, as in the source arch)
  rglru  -- RG-LRU recurrent mixer + dense MLP
  enc    -- bidirectional self-attention + MLP (encoder towers)
  cross  -- causal self-attention + cross-attention + MLP (enc-dec decoders)
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import planner as pl
from repro.models import attention as attn_mod
from repro.models import common, mlp, moe, rglru, ssm


# --- norms -------------------------------------------------------------------

def norm_defs(d: int, cfg: ModelConfig) -> dict:
    out = {"scale": pl.ParamDef((d,), pl.K_NORM, cfg.dtype, init="ones")}
    if cfg.norm == "layernorm":
        out["bias"] = pl.ParamDef((d,), pl.K_NORM, cfg.dtype, init="zeros")
    return out


def norm_apply(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.norm == "layernorm":
        return common.layernorm(x, p["scale"], p["bias"], cfg.norm_eps)
    return common.rmsnorm(x, p["scale"], cfg.norm_eps)


# --- defs --------------------------------------------------------------------

def block_defs(kind: str, cfg: ModelConfig) -> dict:
    d = cfg.d_model
    dt = cfg.dtype
    if kind in ("attn", "local", "enc"):
        return {"ln1": norm_defs(d, cfg),
                "attn": attn_mod.gqa_defs(d, cfg.attn, dt),
                "ln2": norm_defs(d, cfg),
                "mlp": mlp.mlp_defs(d, cfg.d_ff, dt, gated=cfg.mlp_gated)}
    if kind == "mla":
        return {"ln1": norm_defs(d, cfg),
                "mla": attn_mod.mla_defs(d, cfg.mla, dt),
                "ln2": norm_defs(d, cfg),
                "mlp": mlp.mlp_defs(d, cfg.d_ff, dt, gated=cfg.mlp_gated)}
    if kind == "moe":
        return {"ln1": norm_defs(d, cfg),
                "attn": attn_mod.gqa_defs(d, cfg.attn, dt),
                "ln2": norm_defs(d, cfg),
                "moe": moe.moe_defs(d, cfg.moe, dt)}
    if kind == "mla_moe":
        return {"ln1": norm_defs(d, cfg),
                "mla": attn_mod.mla_defs(d, cfg.mla, dt),
                "ln2": norm_defs(d, cfg),
                "moe": moe.moe_defs(d, cfg.moe, dt)}
    if kind == "ssm":
        return {"ln1": norm_defs(d, cfg),
                "ssm": ssm.ssm_defs(d, cfg.ssm, dt)}
    if kind == "rglru":
        return {"ln1": norm_defs(d, cfg),
                "rec": rglru.rglru_defs(d, cfg.rglru, dt),
                "ln2": norm_defs(d, cfg),
                "mlp": mlp.mlp_defs(d, cfg.d_ff, dt, gated=cfg.mlp_gated)}
    if kind == "cross":
        return {"ln1": norm_defs(d, cfg),
                "attn": attn_mod.gqa_defs(d, cfg.attn, dt),
                "ln_x": norm_defs(d, cfg),
                "xattn": attn_mod.gqa_defs(d, cfg.attn, dt),
                "ln2": norm_defs(d, cfg),
                "mlp": mlp.mlp_defs(d, cfg.d_ff, dt, gated=cfg.mlp_gated)}
    raise ValueError(f"unknown block kind {kind!r}")


# --- runtime options passed down from the model ------------------------------

@dataclasses.dataclass(frozen=True)
class BlockCtx:
    cfg: ModelConfig
    window_override: Any = None     # int: force SWA on full-attn blocks
    enc_out: Any = None             # encoder output for cross blocks
    moe_impl: str = "gather"        # gather | ep
    kv_chunk: Any = None            # int: online-softmax attention chunk
    kv_dtype: str = "native"        # int8: quantized GQA KV cache (serving)
    mesh: Any = None                # for moe ep
    batch_axes: tuple = ("data",)
    fsdp_axes: tuple = ()
    wgather_wire: str = "bf16"      # int8: quantized ZeRO weight gathers
    gmm_backend: str = "ragged"     # dropless MoE: kernels/gmm.py backend
    # hybrid execution: activation-exchange axis for tensor-parallel blocks.
    # Whether a given block actually runs sharded is detected from its shard
    # shapes (attn_tp / mlp_tp) — the per-layer hybrid plan leaves fallback
    # layers replicated, and putting f/g psums around full-size weights
    # would multiply their output by the group size.
    tp_axis: Any = None

    def attn_tp(self, p_attn: dict, a) -> Any:
        if self.tp_axis is None:
            return None
        sharded = p_attn["wo"].shape[-2] != a.n_heads * a.head_dim
        return self.tp_axis if sharded else None

    def mlp_tp(self, p_mlp: dict) -> Any:
        if self.tp_axis is None:
            return None
        return self.tp_axis if p_mlp["w2"].shape[-2] != self.cfg.d_ff else None

    def window_for(self, kind: str):
        a = self.cfg.attn
        native = a.window if a is not None else None
        if kind == "local":
            native = native or 2048
        if self.window_override is not None:
            return (min(native, self.window_override) if native
                    else self.window_override)
        return native


# --- train / full-sequence apply ----------------------------------------------

def _moe_ffn(p: dict, x: jax.Array, ctx: BlockCtx):
    """The MoE feed-forward of a block: (y, stats)."""
    cfg = ctx.cfg
    if cfg.moe.dropless:
        return moe.moe_dropless(p, x, cfg.moe, act=cfg.mlp_act,
                                gmm_backend=ctx.gmm_backend)
    if ctx.moe_impl == "ep":
        y, aux = moe.moe_apply_ep(p, x, cfg.moe, act=cfg.mlp_act,
                                  mesh=ctx.mesh, batch_axes=ctx.batch_axes,
                                  fsdp_axes=ctx.fsdp_axes,
                                  wgather_wire=ctx.wgather_wire)
    else:
        y, aux = moe.moe_apply(p, x, cfg.moe, act=cfg.mlp_act)
    return y, {"aux": aux}


def block_apply(kind: str, p: dict, h: jax.Array, ctx: BlockCtx):
    """Returns (h, stats): stats {"aux": aux_loss} (moe.zero_stats for the
    keys of a dropless MoE block)."""
    cfg = ctx.cfg
    stats = {"aux": jnp.zeros((), jnp.float32)}
    if kind in ("attn", "local", "moe", "enc"):
        w = ctx.window_for(kind)
        x = norm_apply(p["ln1"], h, cfg)
        causal = kind != "enc"
        a = cfg.attn if causal else dataclasses.replace(cfg.attn, causal=False)
        h = h + attn_mod.gqa_apply(p["attn"], x, a, window=w,
                                   kv_chunk=ctx.kv_chunk,
                                   tp_axis=ctx.attn_tp(p["attn"], a))
        x = norm_apply(p["ln2"], h, cfg)
        if kind == "moe":
            y, stats = _moe_ffn(p["moe"], x, ctx)
        else:
            y = mlp.mlp_apply(p["mlp"], x, act=cfg.mlp_act, gated=cfg.mlp_gated,
                              tp_axis=ctx.mlp_tp(p["mlp"]))
        return h + y, stats
    if kind in ("mla", "mla_moe"):
        x = norm_apply(p["ln1"], h, cfg)
        with jax.named_scope("mla"):
            h = h + attn_mod.mla_apply(p["mla"], x, cfg.mla,
                                       window=ctx.window_override,
                                       kv_chunk=ctx.kv_chunk)
        x = norm_apply(p["ln2"], h, cfg)
        if kind == "mla_moe":
            y, stats = _moe_ffn(p["moe"], x, ctx)
            return h + y, stats
        with jax.named_scope("mlp"):
            y = mlp.mlp_apply(p["mlp"], x, act=cfg.mlp_act,
                              gated=cfg.mlp_gated,
                              tp_axis=ctx.mlp_tp(p["mlp"]))
        return h + y, stats
    if kind == "ssm":
        x = norm_apply(p["ln1"], h, cfg)
        return h + ssm.ssm_apply(p["ssm"], x, cfg.ssm), stats
    if kind == "rglru":
        x = norm_apply(p["ln1"], h, cfg)
        h = h + rglru.rglru_apply(p["rec"], x, cfg.rglru)
        x = norm_apply(p["ln2"], h, cfg)
        return h + mlp.mlp_apply(p["mlp"], x, act=cfg.mlp_act,
                                 gated=cfg.mlp_gated,
                                 tp_axis=ctx.mlp_tp(p["mlp"])), stats
    if kind == "cross":
        x = norm_apply(p["ln1"], h, cfg)
        h = h + attn_mod.gqa_apply(p["attn"], x, cfg.attn,
                                   kv_chunk=ctx.kv_chunk)
        x = norm_apply(p["ln_x"], h, cfg)
        kv = attn_mod.gqa_cross_kv(p["xattn"], ctx.enc_out, cfg.attn)
        h = h + attn_mod.gqa_apply(p["xattn"], x, cfg.attn, kv_override=kv,
                                   mask=None)
        x = norm_apply(p["ln2"], h, cfg)
        return h + mlp.mlp_apply(p["mlp"], x, act=cfg.mlp_act,
                                 gated=cfg.mlp_gated), stats
    raise ValueError(kind)


# --- caches --------------------------------------------------------------------

def block_init_cache(kind: str, cfg: ModelConfig, batch: int, max_seq: int,
                     ctx: BlockCtx):
    dt = cfg.dtype
    if kind in ("attn", "local", "moe"):
        return attn_mod.gqa_init_cache(batch, max_seq, cfg.attn, dt,
                                       window=ctx.window_for(kind),
                                       kv_dtype=ctx.kv_dtype)
    if kind in ("mla", "mla_moe"):
        return attn_mod.mla_init_cache(batch, max_seq, cfg.mla, dt,
                                       window=ctx.window_override)
    if kind == "ssm":
        return ssm.ssm_init_cache(batch, cfg.d_model, cfg.ssm, dt)
    if kind == "rglru":
        return rglru.rglru_init_cache(batch, cfg.rglru, dt)
    if kind == "cross":
        nf = cfg.encoder.n_frames
        kv, hd = cfg.attn.n_kv, cfg.attn.head_dim
        return {"self": attn_mod.gqa_init_cache(batch, max_seq, cfg.attn, dt),
                "cross": {"k": jnp.zeros((batch, nf, kv, hd), dt),
                          "v": jnp.zeros((batch, nf, kv, hd), dt)}}
    raise ValueError(kind)


def block_prefill_cache(kind: str, p: dict, h_in: jax.Array, cfg: ModelConfig,
                        ctx: BlockCtx):
    """Cache after consuming the full prompt. h_in is the block INPUT (the
    same normalized projections the forward pass used)."""
    if kind in ("attn", "local", "moe"):
        x = norm_apply(p["ln1"], h_in, cfg)
        return attn_mod.gqa_prefill_cache(p["attn"], x, cfg.attn,
                                          window=ctx.window_for(kind),
                                          kv_dtype=ctx.kv_dtype)
    if kind in ("mla", "mla_moe"):
        x = norm_apply(p["ln1"], h_in, cfg)
        return attn_mod.mla_prefill_cache(p["mla"], x, cfg.mla,
                                          window=ctx.window_override)
    if kind == "ssm":
        x = norm_apply(p["ln1"], h_in, cfg)
        return ssm.ssm_prefill_cache(p["ssm"], x, cfg.ssm)
    if kind == "rglru":
        x = norm_apply(p["ln1"], h_in, cfg)
        return rglru.rglru_prefill_cache(p["rec"], x, cfg.rglru)
    if kind == "cross":
        x = norm_apply(p["ln1"], h_in, cfg)
        self_c = attn_mod.gqa_prefill_cache(p["attn"], x, cfg.attn)
        k, v = attn_mod.gqa_cross_kv(p["xattn"], ctx.enc_out, cfg.attn)
        return {"self": self_c, "cross": {"k": k, "v": v}}
    raise ValueError(kind)


# --- decode --------------------------------------------------------------------

def block_decode(kind: str, p: dict, h1: jax.Array, cache, pos, ctx: BlockCtx):
    cfg = ctx.cfg
    if kind in ("attn", "local", "moe"):
        w = ctx.window_for(kind)
        x = norm_apply(p["ln1"], h1, cfg)
        y, cache2 = attn_mod.gqa_decode(p["attn"], x, cache, pos, cfg.attn,
                                        window=w)
        h1 = h1 + y
        x = norm_apply(p["ln2"], h1, cfg)
        if kind == "moe":
            y, _ = _moe_ffn(p["moe"], x, dataclasses.replace(ctx,
                                                             moe_impl="gather"))
        else:
            y = mlp.mlp_apply(p["mlp"], x, act=cfg.mlp_act, gated=cfg.mlp_gated)
        return h1 + y, cache2
    if kind in ("mla", "mla_moe"):
        x = norm_apply(p["ln1"], h1, cfg)
        y, cache2 = attn_mod.mla_decode(p["mla"], x, cache, pos, cfg.mla,
                                        window=ctx.window_override)
        h1 = h1 + y
        x = norm_apply(p["ln2"], h1, cfg)
        if kind == "mla_moe":
            y, _ = _moe_ffn(p["moe"], x, dataclasses.replace(
                ctx, moe_impl="gather"))
            return h1 + y, cache2
        return h1 + mlp.mlp_apply(p["mlp"], x, act=cfg.mlp_act,
                                  gated=cfg.mlp_gated), cache2
    if kind == "ssm":
        x = norm_apply(p["ln1"], h1, cfg)
        y, cache2 = ssm.ssm_decode(p["ssm"], x, cache, cfg.ssm)
        return h1 + y, cache2
    if kind == "rglru":
        x = norm_apply(p["ln1"], h1, cfg)
        y, cache2 = rglru.rglru_decode(p["rec"], x, cache, cfg.rglru)
        h1 = h1 + y
        x = norm_apply(p["ln2"], h1, cfg)
        return h1 + mlp.mlp_apply(p["mlp"], x, act=cfg.mlp_act,
                                  gated=cfg.mlp_gated), cache2
    if kind == "cross":
        x = norm_apply(p["ln1"], h1, cfg)
        y, self2 = attn_mod.gqa_decode(p["attn"], x, cache["self"], pos,
                                       cfg.attn)
        h1 = h1 + y
        x = norm_apply(p["ln_x"], h1, cfg)
        h1 = h1 + attn_mod.gqa_decode_cross(p["xattn"], x, cache["cross"],
                                            cfg.attn)
        x = norm_apply(p["ln2"], h1, cfg)
        h1 = h1 + mlp.mlp_apply(p["mlp"], x, act=cfg.mlp_act,
                                gated=cfg.mlp_gated)
        return h1, {"self": self2, "cross": cache["cross"]}
    raise ValueError(kind)
