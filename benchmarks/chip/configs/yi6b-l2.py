"""Plain float32 reference of Yi-6B's decoder (configs/yi6b-l2.json).

LLaMA architecture: RMSNorm, grouped-query attention with RoPE, SwiGLU MLP,
untied head, next-token cross-entropy. The departures from the published
model are listed in the JSON file. Imports nothing of the program.
"""

from __future__ import annotations

import jax.numpy as jnp

import plain

BLOCK = "blocks/p0_attn/"


def param_specs(c: dict) -> dict:
    d, L, V = c["hidden_size"], c["num_hidden_layers"], c["vocab_size"]
    H, KV, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    ff = c["intermediate_size"]
    return {
        "embed": ((V, d), 0.02),
        "head": ((d, V), d ** -0.5),
        "ln_f/scale": ((d,), "ones"),
        BLOCK + "ln1/scale": ((L, d), "ones"),
        BLOCK + "ln2/scale": ((L, d), "ones"),
        BLOCK + "attn/wq": ((L, d, H * hd), d ** -0.5),
        BLOCK + "attn/wk": ((L, d, KV * hd), d ** -0.5),
        BLOCK + "attn/wv": ((L, d, KV * hd), d ** -0.5),
        BLOCK + "attn/wo": ((L, H * hd, d), (H * hd) ** -0.5),
        BLOCK + "mlp/w1": ((L, d, ff), d ** -0.5),
        BLOCK + "mlp/w3": ((L, d, ff), d ** -0.5),
        BLOCK + "mlp/w2": ((L, ff, d), ff ** -0.5),
    }


def _layer(p: dict, h, c: dict, mm: plain.Matmul):
    B, S, _ = h.shape
    H, KV, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    x = plain.rmsnorm(h, p["ln1/scale"], eps)
    q = mm.einsum("bsd,de->bse", x, p["attn/wq"]).reshape(B, S, H, hd)
    k = mm.einsum("bsd,de->bse", x, p["attn/wk"]).reshape(B, S, KV, hd)
    v = mm.einsum("bsd,de->bse", x, p["attn/wv"]).reshape(B, S, KV, hd)
    q, k = plain.rope(q, theta), plain.rope(k, theta)
    # query head i reads key/value head i // (H / KV)
    k, v = jnp.repeat(k, H // KV, axis=2), jnp.repeat(v, H // KV, axis=2)
    o = plain.causal_attention(mm, q, k, v).reshape(B, S, H * hd)
    h = h + mm.einsum("bse,ed->bsd", o, p["attn/wo"])
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
    attention's score and value products over all `seq` keys (the step
    computes the masked half too), nothing recomputed."""
    d, L, V = c["hidden_size"], c["num_hidden_layers"], c["vocab_size"]
    H, KV, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    ff = c["intermediate_size"]
    per_layer = d * H * hd * 2 + d * KV * hd * 2 + 3 * d * ff
    matmul_params = L * per_layer + d * V
    attention = L * 2 * seq * H * hd * 2          # q.k and p.v
    return 3.0 * (2 * matmul_params + attention)
