"""Attention mixers: GQA/MHA (full + sliding-window) and MLA.

Each mixer exposes:
  <name>_defs(cfg)                          -> ParamDef dict
  <name>_apply(p, x, cfg, *, pos0, window)  -> y            (train / prefill)
  <name>_prefill_cache(p, x, cfg, ...)      -> cache pieces
  <name>_decode(p, x1, cache, pos, cfg)     -> (y1, cache)  (one new token)

Caches are plain dicts of arrays so they stack cleanly under lax.scan and
shard via the planner's cache specs. Sliding-window caches are ring buffers
of exactly `window` slots; keys are roped at write time so no positional
reconstruction is needed at read time.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import AttnConfig, MLAConfig
from repro.core import collectives as cl
from repro.core import planner as pl
from repro.models import common


# =============================== GQA =========================================

def gqa_defs(d_model: int, a: AttnConfig, dtype) -> dict:
    H, KV, hd = a.n_heads, a.n_kv, a.head_dim
    return {
        "wq": pl.ParamDef((d_model, H * hd), pl.K_PROJ_IN, dtype),
        "wk": pl.ParamDef((d_model, KV * hd), pl.K_PROJ_IN, dtype),
        "wv": pl.ParamDef((d_model, KV * hd), pl.K_PROJ_IN, dtype),
        "wo": pl.ParamDef((H * hd, d_model), pl.K_PROJ_OUT, dtype),
    }


def _split_heads(x, n, hd):
    return x.reshape(*x.shape[:-1], n, hd)


def _sdpa(q, k, v, mask) -> jax.Array:
    """q (B,Q,H,hd), k/v (B,K,H,hd), mask (Q,K) or (B,Q,K) bool."""
    hd = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    if mask is not None:
        if mask.ndim == 2:
            mask = mask[None, None]
        else:
            mask = mask[:, None]
        scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


def _repeat_kv(k, n_heads):
    return jnp.repeat(k, n_heads // k.shape[-2], axis=-2) \
        if k.shape[-2] != n_heads else k


def chunked_sdpa(q, k, v, *, causal: bool = True, window: int | None = None,
                 q_offset: int = 0, kv_chunk: int = 1024,
                 scale: float | None = None) -> jax.Array:
    """Online-softmax (flash-style) attention: scans KV in chunks so the
    (Sq, Sk) score matrix never materializes -- O(Sq * kv_chunk) live memory
    instead of O(Sq * Sk). Numerically identical to _sdpa (tests assert).

    q (B,Sq,H,D); k/v (B,Sk,H,D) with heads already repeated. This is the
    beyond-paper memory optimization for the 32k prefill shapes
    (EXPERIMENTS.md §Perf): a TPU-native reformulation (VMEM-sized KV tiles,
    running max/denominator in f32) of the attention the paper-era stack
    materialized.

    The backward pass recomputes each chunk's scores and probabilities
    instead of keeping them (the chunk body is checkpointed), so training
    too holds O(Sq * kv_chunk) of them at a time rather than all Sq * Sk.
    """
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    Dv = v.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    c = min(kv_chunk, Sk)
    pad = (-Sk) % c
    if pad:
        zk = jnp.zeros((B, pad) + k.shape[2:], k.dtype)
        k = jnp.concatenate([k, zk], axis=1)
        v = jnp.concatenate([v, jnp.zeros((B, pad) + v.shape[2:], v.dtype)],
                            axis=1)
    nk = k.shape[1] // c
    kc = k.reshape(B, nk, c, H, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nk, c, H, Dv).transpose(1, 0, 2, 3, 4)
    q_pos = jnp.arange(Sq) + q_offset

    def body(carry, inp):
        m, l, acc = carry
        j, kj, vj = inp
        k_pos = j * c + jnp.arange(c)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kj).astype(jnp.float32) * scale
        valid = k_pos[None, :] <= Sk - 1
        if causal:
            valid = valid & (k_pos[None, :] <= q_pos[:, None])
        if window is not None:
            valid = valid & (k_pos[None, :] > q_pos[:, None] - window)
        s = jnp.where(valid[None, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), vj)
        acc_new = acc * corr.transpose(0, 2, 1)[..., None].astype(acc.dtype) \
            + pv
        return (m_new, l_new, acc_new), None

    body = jax.checkpoint(body)
    m0 = jnp.full((B, H, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    a0 = jnp.zeros((B, Sq, H, Dv), q.dtype)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                  (jnp.arange(nk), kc, vc))
    denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return (acc.astype(jnp.float32) / denom).astype(q.dtype)


def gqa_apply(p: dict, x: jax.Array, a: AttnConfig, *, pos0: int = 0,
              window: int | None = None, mask: jax.Array | None = None,
              kv_override=None, kv_chunk: int | None = None,
              tp_axis: str | None = None) -> jax.Array:
    """Full forward over a sequence (training / prefill / encoder).

    kv_override: (k, v) for cross-attention (whisper decoder).

    tp_axis: head-sharded tensor parallelism — the projections in `p` are
    this rank's head shard (local head counts derived from the shard
    shapes), x enters through the f operator (identity fwd / psum bwd) and
    the out-projection's partial sum leaves through g (psum fwd / identity
    bwd): repro.core.collectives.tp_replicate / tp_psum. Rope and softmax
    are per-head, so the sharded math is exact."""
    B, S, _ = x.shape
    H, KV, hd = a.n_heads, a.n_kv, a.head_dim
    if tp_axis is not None:
        H = p["wq"].shape[-1] // hd
        KV = p["wk"].shape[-1] // hd
        x = cl.tp_replicate(x, tp_axis)
    q = _split_heads(x @ p["wq"], H, hd)
    if kv_override is None:
        k = _split_heads(x @ p["wk"], KV, hd)
        v = _split_heads(x @ p["wv"], KV, hd)
        positions = jnp.arange(S) + pos0
        q = common.apply_rope(q, positions, rotary_frac=a.rotary_frac,
                              theta=a.rope_theta)
        k = common.apply_rope(k, positions, rotary_frac=a.rotary_frac,
                              theta=a.rope_theta)
        if mask is None and a.causal and kv_chunk is None:
            w = window if window is not None else a.window
            mask = common.causal_mask(S, S, q_offset=0, window=w)
    else:
        k, v = kv_override
    k = _repeat_kv(k, H)
    v = _repeat_kv(v, H)
    if kv_chunk is not None and kv_override is None and mask is None:
        w = window if window is not None else a.window
        o = chunked_sdpa(q, k, v, causal=a.causal, window=w, q_offset=pos0,
                         kv_chunk=kv_chunk)
    else:
        o = _sdpa(q, k, v, mask)
    y = o.reshape(B, S, H * hd) @ p["wo"]
    if tp_axis is not None:
        y = cl.tp_psum(y, tp_axis)
    return y


def gqa_cross_kv(p: dict, enc: jax.Array, a: AttnConfig):
    """Precompute cross-attention K/V from encoder output (whisper)."""
    KV, hd = a.n_kv, a.head_dim
    return (_split_heads(enc @ p["wk"], KV, hd),
            _split_heads(enc @ p["wv"], KV, hd))


def _kv_quant(x: jax.Array):
    """Per-(position, head) vector int8 quantization of K/V rows.

    x (..., hd) -> (int8 (..., hd), f16 scale (..., 1)). The C6 idea applied
    to the decode-shape bottleneck: the KV-cache stream is halved (paper's
    low-precision principle on the memory system instead of the wire)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.float16)


def _kv_dequant(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)).astype(dtype)


def gqa_init_cache(batch: int, max_seq: int, a: AttnConfig, dtype,
                   *, window: int | None = None,
                   kv_dtype: str = "native") -> dict:
    slots = min(max_seq, window) if window else max_seq
    KV, hd = a.n_kv, a.head_dim
    if kv_dtype == "int8":
        return {"k": jnp.zeros((batch, slots, KV, hd), jnp.int8),
                "v": jnp.zeros((batch, slots, KV, hd), jnp.int8),
                "k_s": jnp.zeros((batch, slots, KV, 1), jnp.float16),
                "v_s": jnp.zeros((batch, slots, KV, 1), jnp.float16)}
    return {"k": jnp.zeros((batch, slots, KV, hd), dtype),
            "v": jnp.zeros((batch, slots, KV, hd), dtype)}


def gqa_prefill_cache(p: dict, x: jax.Array, a: AttnConfig, *,
                      window: int | None = None,
                      kv_dtype: str = "native") -> dict:
    """K/V for the whole prompt (ring-compacted if windowed)."""
    KV, hd = a.n_kv, a.head_dim
    S = x.shape[1]
    k = _split_heads(x @ p["wk"], KV, hd)
    v = _split_heads(x @ p["wv"], KV, hd)
    k = common.apply_rope(k, jnp.arange(S), rotary_frac=a.rotary_frac,
                          theta=a.rope_theta)
    if window and S > window:
        # keep the last `window` positions, laid out at their ring slots
        keep_k, keep_v = k[:, -window:], v[:, -window:]
        slot = (jnp.arange(S - window, S)) % window
        k = jnp.zeros_like(keep_k).at[:, slot].set(keep_k)
        v = jnp.zeros_like(keep_v).at[:, slot].set(keep_v)
    if kv_dtype == "int8":
        kq, ks = _kv_quant(k)
        vq, vs = _kv_quant(v)
        return {"k": kq, "v": vq, "k_s": ks, "v_s": vs}
    return {"k": k, "v": v}


def gqa_decode(p: dict, x1: jax.Array, cache: dict, pos: jax.Array,
               a: AttnConfig, *, window: int | None = None):
    """One-token decode. x1 (B,1,d); pos scalar int32 (current length)."""
    B = x1.shape[0]
    H, KV, hd = a.n_heads, a.n_kv, a.head_dim
    slots = cache["k"].shape[1]
    quantized = "k_s" in cache
    q = _split_heads(x1 @ p["wq"], H, hd)
    k1 = _split_heads(x1 @ p["wk"], KV, hd)
    v1 = _split_heads(x1 @ p["wv"], KV, hd)
    posv = jnp.full((1,), pos)
    q = common.apply_rope(q, posv, rotary_frac=a.rotary_frac, theta=a.rope_theta)
    k1 = common.apply_rope(k1, posv, rotary_frac=a.rotary_frac, theta=a.rope_theta)
    write = pos % slots if window else pos
    if quantized:
        k1q, k1s = _kv_quant(k1)
        v1q, v1s = _kv_quant(v1)
        cache2 = {
            "k": jax.lax.dynamic_update_slice_in_dim(cache["k"], k1q, write,
                                                     axis=1),
            "v": jax.lax.dynamic_update_slice_in_dim(cache["v"], v1q, write,
                                                     axis=1),
            "k_s": jax.lax.dynamic_update_slice_in_dim(cache["k_s"], k1s,
                                                       write, axis=1),
            "v_s": jax.lax.dynamic_update_slice_in_dim(cache["v_s"], v1s,
                                                       write, axis=1),
        }
        k = _kv_dequant(cache2["k"], cache2["k_s"], x1.dtype)
        v = _kv_dequant(cache2["v"], cache2["v_s"], x1.dtype)
    else:
        k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k1, write, axis=1)
        v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v1, write, axis=1)
        cache2 = {"k": k, "v": v}
    idx = jnp.arange(slots)
    if window:
        # ring buffer: once full, every slot holds one of the last `slots`
        # positions; before that only slots <= pos are written.
        valid = jnp.where(pos >= slots, jnp.ones_like(idx, dtype=bool),
                          idx <= pos)
    else:
        valid = idx <= pos
    o = _sdpa(q, _repeat_kv(k, H), _repeat_kv(v, H), valid[None, None, :])
    y = o.reshape(B, 1, H * hd) @ p["wo"]
    return y, cache2


def gqa_decode_cross(p: dict, x1: jax.Array, cross_kv: dict,
                     a: AttnConfig) -> jax.Array:
    """Cross-attention for one decoder token against fixed encoder K/V."""
    B = x1.shape[0]
    H, hd = a.n_heads, a.head_dim
    q = _split_heads(x1 @ p["wq"], H, hd)
    k, v = _repeat_kv(cross_kv["k"], H), _repeat_kv(cross_kv["v"], H)
    o = _sdpa(q, k, v, None)
    return o.reshape(B, 1, H * hd) @ p["wo"]


# =============================== MLA =========================================

def mla_defs(d_model: int, m: MLAConfig, dtype) -> dict:
    H = m.n_heads
    qk = m.qk_nope_dim + m.qk_rope_dim
    if m.q_lora_rank:
        q = {"w_dq": pl.ParamDef((d_model, m.q_lora_rank), pl.K_REPLICATED,
                                 dtype),
             "q_norm": pl.ParamDef((m.q_lora_rank,), pl.K_NORM, dtype,
                                   init="ones"),
             "w_uq": pl.ParamDef((m.q_lora_rank, H * qk), pl.K_PROJ_IN, dtype)}
    else:
        q = {"w_q": pl.ParamDef((d_model, H * qk), pl.K_PROJ_IN, dtype)}
    return {
        **q,
        "w_dkv": pl.ParamDef((d_model, m.kv_lora_rank + m.qk_rope_dim),
                             pl.K_REPLICATED, dtype),
        "kv_norm": pl.ParamDef((m.kv_lora_rank,), pl.K_NORM, dtype, init="ones"),
        "w_uk": pl.ParamDef((m.kv_lora_rank, H * m.qk_nope_dim), pl.K_PROJ_IN,
                            dtype),
        "w_uv": pl.ParamDef((m.kv_lora_rank, H * m.v_head_dim), pl.K_PROJ_IN,
                            dtype),
        "wo": pl.ParamDef((H * m.v_head_dim, d_model), pl.K_PROJ_OUT, dtype),
    }


def _mla_q(p, x, m: MLAConfig):
    """Queries (..., H * (nope + rope)): through the low-rank and its norm,
    or x W_q where the config has no query low-rank."""
    if m.q_lora_rank:
        return common.rmsnorm(x @ p["w_dq"], p["q_norm"]) @ p["w_uq"]
    return x @ p["w_q"]


def _mla_scale(m: MLAConfig) -> float:
    """Attention's softmax scale: qk**-0.5, times YaRN's mscale squared."""
    if m.yarn is None:
        return 1.0 / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    return common.yarn_softmax_scale(m.qk_nope_dim + m.qk_rope_dim, m.yarn)


def _mla_scores(scores, m: MLAConfig):
    if m.yarn is None:
        return scores / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    return scores * _mla_scale(m)


def _mla_qkv(p, x, m: MLAConfig, pos0: int):
    """Shared q / latent computation for a full sequence."""
    B, S, _ = x.shape
    H = m.n_heads
    q = _mla_q(p, x, m).reshape(B, S, H, m.qk_nope_dim + m.qk_rope_dim)
    q_nope, q_pe = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim:]
    ckv_full = x @ p["w_dkv"]
    ckv, kpe = (ckv_full[..., : m.kv_lora_rank],
                ckv_full[..., m.kv_lora_rank:])
    ckv = common.rmsnorm(ckv, p["kv_norm"])
    positions = jnp.arange(S) + pos0
    q_pe = common.apply_rope(q_pe, positions, theta=m.rope_theta, yarn=m.yarn)
    kpe = common.apply_rope(kpe[..., None, :], positions,
                            theta=m.rope_theta, yarn=m.yarn)[..., 0, :]
    return q_nope, q_pe, ckv, kpe


def mla_apply(p: dict, x: jax.Array, m: MLAConfig, *, pos0: int = 0,
              window: int | None = None,
              kv_chunk: int | None = None) -> jax.Array:
    B, S, _ = x.shape
    H = m.n_heads
    q_nope, q_pe, ckv, kpe = _mla_qkv(p, x, m, pos0)
    k_nope = (ckv @ p["w_uk"]).reshape(B, S, H, m.qk_nope_dim)
    v = (ckv @ p["w_uv"]).reshape(B, S, H, m.v_head_dim)
    if kv_chunk is not None:
        # fold the decoupled-RoPE component into the head dim and reuse the
        # online-softmax kernel path
        q_cat = jnp.concatenate([q_nope, q_pe], axis=-1)
        k_cat = jnp.concatenate(
            [k_nope, jnp.broadcast_to(kpe[:, :, None, :],
                                      (B, S, H, m.qk_rope_dim))], axis=-1)
        o = chunked_sdpa(q_cat, k_cat, v, causal=True, window=window,
                         q_offset=pos0, kv_chunk=kv_chunk,
                         scale=_mla_scale(m))
        return o.reshape(B, S, H * m.v_head_dim) @ p["wo"]
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
              + jnp.einsum("bqhd,bkd->bhqk", q_pe, kpe)).astype(jnp.float32)
    scores = _mla_scores(scores, m)
    mask = common.causal_mask(S, S, window=window)
    scores = jnp.where(mask[None, None], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, v)
    return o.reshape(B, S, H * m.v_head_dim) @ p["wo"]


def mla_init_cache(batch: int, max_seq: int, m: MLAConfig, dtype,
                   *, window: int | None = None) -> dict:
    slots = min(max_seq, window) if window else max_seq
    return {"ckv": jnp.zeros((batch, slots, m.kv_lora_rank), dtype),
            "kpe": jnp.zeros((batch, slots, m.qk_rope_dim), dtype)}


def mla_prefill_cache(p: dict, x: jax.Array, m: MLAConfig, *,
                      window: int | None = None) -> dict:
    _, _, ckv, kpe = _mla_qkv(p, x, m, 0)
    if window and x.shape[1] > window:
        S = x.shape[1]
        slot = jnp.arange(S - window, S) % window
        ckv = jnp.zeros_like(ckv[:, :window]).at[:, slot].set(ckv[:, -window:])
        kpe = jnp.zeros_like(kpe[:, :window]).at[:, slot].set(kpe[:, -window:])
    return {"ckv": ckv, "kpe": kpe}


def mla_decode(p: dict, x1: jax.Array, cache: dict, pos: jax.Array,
               m: MLAConfig, *, window: int | None = None):
    """Absorbed-projection MLA decode: attention acts on the latent cache."""
    B = x1.shape[0]
    H = m.n_heads
    slots = cache["ckv"].shape[1]
    q = _mla_q(p, x1, m).reshape(B, 1, H, m.qk_nope_dim + m.qk_rope_dim)
    q_nope, q_pe = q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim:]
    posv = jnp.full((1,), pos)
    q_pe = common.apply_rope(q_pe, posv, theta=m.rope_theta, yarn=m.yarn)
    ckv1_full = x1 @ p["w_dkv"]
    ckv1 = common.rmsnorm(ckv1_full[..., : m.kv_lora_rank], p["kv_norm"])
    kpe1 = common.apply_rope(ckv1_full[..., None, m.kv_lora_rank:], posv,
                             theta=m.rope_theta, yarn=m.yarn)[..., 0, :]
    write = pos % slots if window else pos
    ckv = jax.lax.dynamic_update_slice_in_dim(cache["ckv"], ckv1, write, axis=1)
    kpe = jax.lax.dynamic_update_slice_in_dim(cache["kpe"], kpe1, write, axis=1)
    # absorb W_uk into the query: q_abs (B,1,H,r)
    w_uk = p["w_uk"].reshape(m.kv_lora_rank, H, m.qk_nope_dim)
    q_abs = jnp.einsum("bqhn,rhn->bqhr", q_nope, w_uk)
    scores = (jnp.einsum("bqhr,bkr->bhqk", q_abs, ckv)
              + jnp.einsum("bqhd,bkd->bhqk", q_pe, kpe)).astype(jnp.float32)
    scores = _mla_scores(scores, m)
    idx = jnp.arange(slots)
    if window:
        valid = jnp.where(pos >= slots, jnp.ones_like(idx, dtype=bool),
                          idx <= pos)
    else:
        valid = idx <= pos
    scores = jnp.where(valid[None, None, None, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(x1.dtype)
    ctx = jnp.einsum("bhqk,bkr->bqhr", w, ckv)
    w_uv = p["w_uv"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    o = jnp.einsum("bqhr,rhv->bqhv", ctx, w_uv)
    y = o.reshape(B, 1, H * m.v_head_dim) @ p["wo"]
    return y, {"ckv": ckv, "kpe": kpe}
