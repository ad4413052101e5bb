"""Plain float32 reference of DeepSeek-V2-Lite's decoder, one chip's
expert share (configs/dsv2lite-l7e8.json).

Layer 0: multi-head latent attention (MLA) and a dense SwiGLU. Every
later layer: MLA and a mixture of experts. MLA has no query low-rank
(q = x W_q), one normed latent for keys and values and one roped key part
shared by all heads, YaRN RoPE on the 64 rope dims and YaRN's softmax
scale. The MoE layer routes each token over all of the router's experts
(softmax, greedy top-k, no renormalisation, times routed_scaling_factor)
and adds the part of the held experts: each held expert is computed over
every token and weighted by that token's gate, which is 0 where the
expert was not chosen, so nothing is sorted or dropped. The shared
experts are one SwiGLU over every token. The loss is next-token
cross-entropy plus aux_loss_alpha times the sum over the MoE layers of
DeepSeek's sequence-wise balance loss. The departures from the published
model are listed in the JSON file. Imports nothing of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

import plain

LEAD = "lead/l0_mla/"
BLOCK = "blocks/p0_mla_moe/"


def _mla_specs(c: dict, prefix: str, lead: tuple) -> dict:
    d, H = c["hidden_size"], c["num_attention_heads"]
    rkv = c["kv_lora_rank"]
    nope, rope, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    return {
        prefix + "ln1/scale": (lead + (d,), "ones"),
        prefix + "ln2/scale": (lead + (d,), "ones"),
        prefix + "mla/w_q": (lead + (d, H * (nope + rope)), d ** -0.5),
        prefix + "mla/w_dkv": (lead + (d, rkv + rope), d ** -0.5),
        prefix + "mla/kv_norm": (lead + (rkv,), "ones"),
        prefix + "mla/w_uk": (lead + (rkv, H * nope), rkv ** -0.5),
        prefix + "mla/w_uv": (lead + (rkv, H * vd), rkv ** -0.5),
        prefix + "mla/wo": (lead + (H * vd, d), (H * vd) ** -0.5),
    }


def _swiglu_specs(prefix: str, lead: tuple, d: int, ff: int) -> dict:
    return {prefix + "w1": (lead + (d, ff), d ** -0.5),
            prefix + "w3": (lead + (d, ff), d ** -0.5),
            prefix + "w2": (lead + (ff, d), ff ** -0.5)}


def moe_layers(c: dict) -> int:
    return c["num_hidden_layers"] - c["first_k_dense_replace"]


def param_specs(c: dict) -> dict:
    d, V = c["hidden_size"], c["vocab_size"]
    L = (moe_layers(c),)
    E, ff = c["n_routed_experts"], c["moe_intermediate_size"]
    shared = c["n_shared_experts"] * ff
    assert c["first_k_dense_replace"] == 1
    return {
        "embed": ((V, d), 0.02),
        "head": ((d, V), d ** -0.5),
        "ln_f/scale": ((d,), "ones"),
        **_mla_specs(c, LEAD, ()),
        **_swiglu_specs(LEAD + "mlp/", (), d, c["intermediate_size"]),
        **_mla_specs(c, BLOCK, L),
        BLOCK + "moe/router": (L + (d, c["published"]["n_routed_experts"]),
                               d ** -0.5),
        BLOCK + "moe/w1": (L + (E, d, ff), d ** -0.5),
        BLOCK + "moe/w3": (L + (E, d, ff), d ** -0.5),
        BLOCK + "moe/w2": (L + (E, ff, d), ff ** -0.5),
        **_swiglu_specs(BLOCK + "moe/shared/", L, d, shared),
    }


# --- YaRN -----------------------------------------------------------------------

def _yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(c: dict) -> np.ndarray:
    """DeepSeek-V2's YaRN frequencies over the rope dims (dim/2,)."""
    dim, base = c["qk_rope_head_dim"], float(c["rope_theta"])
    y = c["rope_scaling"]
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / y["factor"]

    def corr(rot):
        return (dim * math.log(y["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))) / (2 * math.log(base))
    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def softmax_mscale(c: dict) -> float:
    """YaRN's factor on the softmax scale: mscale(factor, mscale_all_dim)^2."""
    y = c["rope_scaling"]
    m = _yarn_mscale(y["factor"], y["mscale_all_dim"])
    return m * m


def yarn_rope(x, c: dict):
    """RoPE with YaRN frequencies over the whole last dim of x (B, S, H, D),
    halves convention, cos and sin scaled by mscale / mscale_all_dim."""
    y = c["rope_scaling"]
    S = x.shape[1]
    ang = np.arange(S, dtype=np.float64)[:, None] * yarn_inv_freq(c)
    m = (_yarn_mscale(y["factor"], y["mscale"])
         / _yarn_mscale(y["factor"], y["mscale_all_dim"]))
    cos = jnp.asarray((np.cos(ang) * m).astype(np.float32))[:, None, :]
    sin = jnp.asarray((np.sin(ang) * m).astype(np.float32))[:, None, :]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1)


# --- layers ---------------------------------------------------------------------

def _mla(p: dict, h, c: dict, mm: plain.Matmul):
    """h + MLA(rmsnorm(h))."""
    B, S, _ = h.shape
    H, rkv = c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    eps = c["rms_norm_eps"]
    x = plain.rmsnorm(h, p["ln1/scale"], eps)
    q = mm.einsum("bsd,de->bse", x, p["mla/w_q"]).reshape(
        B, S, H, nope + rope)
    q_nope, q_pe = q[..., :nope], yarn_rope(q[..., nope:], c)
    ckv = mm.einsum("bsd,dr->bsr", x, p["mla/w_dkv"])
    latent = plain.rmsnorm(ckv[..., :rkv], p["mla/kv_norm"], eps)
    k_pe = yarn_rope(ckv[..., None, rkv:], c)              # (B, S, 1, rope)
    k_nope = mm.einsum("bsr,re->bse", latent, p["mla/w_uk"]).reshape(
        B, S, H, nope)
    v = mm.einsum("bsr,re->bse", latent, p["mla/w_uv"]).reshape(B, S, H, vd)
    # scores scale = (nope + rope)**-0.5 * mscale^2, the mscale^2 on q
    q = jnp.concatenate([q_nope, q_pe], axis=-1) * softmax_mscale(c)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (B, S, H, rope))],
                        axis=-1)
    o = plain.causal_attention(mm, q, k, v).reshape(B, S, H * vd)
    return h + mm.einsum("bse,ed->bsd", o, p["mla/wo"])


def seq_aux(probs, ids, n_experts: int, top_k: int):
    """DeepSeek's sequence-wise balance loss: per sequence, each expert's
    share of the top-k slots over its fair share, times its mean
    probability, summed over experts; mean over sequences."""
    S = probs.shape[1]
    slots = jnp.sum(jax.nn.one_hot(ids, n_experts, dtype=jnp.float32),
                    axis=(1, 2))                           # (B, E)
    ce = slots / (S * top_k / n_experts)
    return jnp.mean(jnp.sum(ce * jnp.mean(probs, axis=1), axis=-1))


def moe(p: dict, x, c: dict, mm: plain.Matmul):
    """(the held experts' part of the MoE output plus the shared experts,
    the layer's aux loss). x (B, S, d) is the normed input."""
    n_all, k = c["published"]["n_routed_experts"], c["num_experts_per_tok"]
    first, held = c["held_experts"]
    probs = jax.nn.softmax(mm.einsum("bsd,de->bse", x, p["moe/router"]),
                           axis=-1)
    weights, ids = jax.lax.top_k(probs, k)                 # (B, S, k)
    if c["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    weights = weights * c["routed_scaling_factor"]
    experts = jnp.arange(first, first + held)
    gates = jnp.sum(jnp.where(ids[..., None] == experts, weights[..., None],
                              0.0), axis=2)                # (B, S, held)

    @jax.checkpoint
    def one(y, e):
        w1, w3, w2, gate = e
        return y + gate[..., None] * plain.swiglu(mm, x, w1, w3, w2), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (p["moe/w1"], p["moe/w3"], p["moe/w2"],
                         jnp.moveaxis(gates, -1, 0)))
    y = y + plain.swiglu(mm, x, p["moe/shared/w1"], p["moe/shared/w3"],
                         p["moe/shared/w2"])
    return y, seq_aux(probs, ids, n_all, k)


def loss(p: dict, tokens, c: dict, mm: plain.Matmul):
    """Mean next-token cross-entropy of float32 parameters `p` on tokens
    (B, S), plus aux_loss_alpha times the MoE layers' balance losses."""
    eps = c["rms_norm_eps"]
    h = jnp.take(p["embed"], tokens, axis=0).astype(jnp.float32)
    lead = {k[len(LEAD):]: v for k, v in p.items() if k.startswith(LEAD)}
    h = _mla(lead, h, c, mm)
    h = h + plain.swiglu(mm, plain.rmsnorm(h, lead["ln2/scale"], eps),
                         lead["mlp/w1"], lead["mlp/w3"], lead["mlp/w2"])
    stacked = {k[len(BLOCK):]: v for k, v in p.items() if k.startswith(BLOCK)}

    @jax.checkpoint
    def layer(h, lp):
        h = _mla(lp, h, c, mm)
        y, aux = moe(lp, plain.rmsnorm(h, lp["ln2/scale"], eps), c, mm)
        return h + y, aux

    h, aux = jax.lax.scan(layer, h, stacked)
    h = plain.rmsnorm(h, p["ln_f/scale"], eps)
    return (plain.next_token_xent(mm, h, p["head"], tokens)
            + c["aux_loss_alpha"] * jnp.sum(aux))


def flops_per_token(c: dict, seq: int) -> float:
    """Forward + backward operations per token: every matmul (the routed
    experts at their expected top_k * held / n_experts applications per
    token, the router 64 wide), the head, and attention's score (192 dims)
    and value (128 dims) products over all `seq` keys, nothing
    recomputed."""
    d, V = c["hidden_size"], c["vocab_size"]
    H, rkv = c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    n_all = c["published"]["n_routed_experts"]
    ff, k = c["moe_intermediate_size"], c["num_experts_per_tok"]
    held = c["n_routed_experts"]
    attn = (d * H * (nope + rope) + d * (rkv + rope) + rkv * H * nope
            + rkv * H * vd + H * vd * d)
    expert = 3 * d * ff
    ffn = (d * n_all + k * held / n_all * expert
           + 3 * d * c["n_shared_experts"] * ff)
    L = c["num_hidden_layers"]
    matmul_params = (attn + 3 * d * c["intermediate_size"]
                     + moe_layers(c) * (attn + ffn) + d * V)
    attention = L * 2 * seq * H * ((nope + rope) + vd)
    return 3.0 * (2 * matmul_params + attention)


def expert_flops_per_step(c: dict, tokens: int) -> float:
    """Least operations of the routed experts' matmuls in one step: the
    expected rows (tokens * top_k * held / n_experts per MoE layer), three
    d x ff products each, forward and backward (3x), nothing recomputed."""
    n_all = c["published"]["n_routed_experts"]
    rows = tokens * c["num_experts_per_tok"] * c["n_routed_experts"] / n_all
    return (moe_layers(c) * rows * 2 * 3 * c["hidden_size"]
            * c["moe_intermediate_size"] * 3)
