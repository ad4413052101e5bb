"""Plain float32 reference of MiniCPM3-4B's decoder (configs/minicpm3-l8.json).

Multi-head latent attention (MLA) with a low-rank query, one shared latent
for keys and values, a decoupled RoPE key part shared by all heads, then a
SwiGLU MLP; untied head, next-token cross-entropy. The departures from the
published model are listed in the JSON file. Imports nothing of the
program.
"""

from __future__ import annotations

import jax.numpy as jnp

import plain

BLOCK = "blocks/p0_mla/"


def param_specs(c: dict) -> dict:
    d, L, V = c["hidden_size"], c["num_hidden_layers"], c["vocab_size"]
    H, ff = c["num_attention_heads"], c["intermediate_size"]
    rq, rkv = c["q_lora_rank"], c["kv_lora_rank"]
    nope, rope, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    return {
        "embed": ((V, d), 0.02),
        "head": ((d, V), d ** -0.5),
        "ln_f/scale": ((d,), "ones"),
        BLOCK + "ln1/scale": ((L, d), "ones"),
        BLOCK + "ln2/scale": ((L, d), "ones"),
        BLOCK + "mla/w_dq": ((L, d, rq), d ** -0.5),
        BLOCK + "mla/q_norm": ((L, rq), "ones"),
        BLOCK + "mla/w_uq": ((L, rq, H * (nope + rope)), rq ** -0.5),
        BLOCK + "mla/w_dkv": ((L, d, rkv + rope), d ** -0.5),
        BLOCK + "mla/kv_norm": ((L, rkv), "ones"),
        BLOCK + "mla/w_uk": ((L, rkv, H * nope), rkv ** -0.5),
        BLOCK + "mla/w_uv": ((L, rkv, H * vd), rkv ** -0.5),
        BLOCK + "mla/wo": ((L, H * vd, d), (H * vd) ** -0.5),
        BLOCK + "mlp/w1": ((L, d, ff), d ** -0.5),
        BLOCK + "mlp/w3": ((L, d, ff), d ** -0.5),
        BLOCK + "mlp/w2": ((L, ff, d), ff ** -0.5),
    }


def _layer(p: dict, h, c: dict, mm: plain.Matmul):
    B, S, _ = h.shape
    H, rkv = c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    x = plain.rmsnorm(h, p["ln1/scale"], eps)
    cq = plain.rmsnorm(mm.einsum("bsd,dr->bsr", x, p["mla/w_dq"]),
                       p["mla/q_norm"], eps)
    q = mm.einsum("bsr,re->bse", cq, p["mla/w_uq"]).reshape(
        B, S, H, nope + rope)
    q_nope, q_pe = q[..., :nope], plain.rope(q[..., nope:], theta)
    ckv = mm.einsum("bsd,dr->bsr", x, p["mla/w_dkv"])
    latent = plain.rmsnorm(ckv[..., :rkv], p["mla/kv_norm"], eps)
    k_pe = plain.rope(ckv[..., None, rkv:], theta)          # (B, S, 1, rope)
    k_nope = mm.einsum("bsr,re->bse", latent, p["mla/w_uk"]).reshape(
        B, S, H, nope)
    v = mm.einsum("bsr,re->bse", latent, p["mla/w_uv"]).reshape(B, S, H, vd)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (B, S, H, rope))],
                        axis=-1)
    o = plain.causal_attention(mm, q, k, v).reshape(B, S, H * vd)
    h = h + mm.einsum("bse,ed->bsd", o, p["mla/wo"])
    x = plain.rmsnorm(h, p["ln2/scale"], eps)
    return h + plain.swiglu(mm, x, p["mlp/w1"], p["mlp/w3"], p["mlp/w2"])


def loss(p: dict, tokens, c: dict, mm: plain.Matmul):
    """Mean next-token cross-entropy of float32 parameters `p` on tokens
    (B, S)."""
    h = jnp.take(p["embed"], tokens, axis=0).astype(jnp.float32)
    h = plain.layers(lambda lp, h: _layer(lp, h, c, mm), h, p, BLOCK)
    h = plain.rmsnorm(h, p["ln_f/scale"], c["rms_norm_eps"])
    return plain.next_token_xent(mm, h, p["head"], tokens)


def flops_per_token(c: dict, seq: int) -> float:
    """Forward + backward operations per token: every matmul, the head and
    attention's score (96 dims) and value (64 dims) products over all `seq`
    keys, nothing recomputed."""
    d, L, V = c["hidden_size"], c["num_hidden_layers"], c["vocab_size"]
    H, ff = c["num_attention_heads"], c["intermediate_size"]
    rq, rkv = c["q_lora_rank"], c["kv_lora_rank"]
    nope, rope, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    per_layer = (d * rq + rq * H * (nope + rope) + d * (rkv + rope)
                 + rkv * H * nope + rkv * H * vd + H * vd * d + 3 * d * ff)
    matmul_params = L * per_layer + d * V
    attention = L * 2 * seq * H * ((nope + rope) + vd)
    return 3.0 * (2 * matmul_params + attention)
