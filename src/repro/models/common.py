"""Shared model building blocks: init, norms, rotary embeddings, losses."""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.planner import ParamDef


# --- parameter initialization -----------------------------------------------

def init_param(key: jax.Array, pd: ParamDef) -> jax.Array:
    if pd.init == "zeros":
        return jnp.zeros(pd.shape, pd.dtype)
    if pd.init == "ones":
        return jnp.ones(pd.shape, pd.dtype)
    fan_in = pd.shape[-2] if len(pd.shape) >= 2 else pd.shape[-1]
    scale = pd.init_scale if pd.init_scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, pd.shape, jnp.float32) * scale).astype(pd.dtype)


def init_tree(key: jax.Array, defs_tree) -> Any:
    leaves, treedef = jax.tree_util.tree_flatten(
        defs_tree, is_leaf=lambda x: isinstance(x, ParamDef))
    keys = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(
        treedef, [init_param(k, pd) for k, pd in zip(keys, leaves)])


def abstract_tree(defs_tree, shardings=None) -> Any:
    """ParamDef tree -> ShapeDtypeStruct tree (optionally sharded) for dry-runs."""
    def one(pd, sh=None):
        return jax.ShapeDtypeStruct(pd.shape, pd.dtype, sharding=sh)
    if shardings is None:
        return jax.tree_util.tree_map(one, defs_tree,
                                      is_leaf=lambda x: isinstance(x, ParamDef))
    return jax.tree_util.tree_map(one, defs_tree, shardings,
                                  is_leaf=lambda x: isinstance(x, ParamDef))


def count_params(defs_tree) -> int:
    leaves = jax.tree_util.tree_leaves(
        defs_tree, is_leaf=lambda x: isinstance(x, ParamDef))
    return int(sum(l.size for l in leaves))


def stack_defs(defs_tree, n: int):
    """Add a leading scan dimension of size n to every ParamDef."""
    def one(pd: ParamDef) -> ParamDef:
        return ParamDef(shape=(n,) + pd.shape, kind=pd.kind, dtype=pd.dtype,
                        init=pd.init, init_scale=pd.init_scale)
    return jax.tree_util.tree_map(one, defs_tree,
                                  is_leaf=lambda x: isinstance(x, ParamDef))


# --- norms --------------------------------------------------------------------

def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * scale.astype(jnp.float32)).astype(dt)


def layernorm(x: jax.Array, scale: jax.Array, bias: jax.Array,
              eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


# --- rotary position embeddings ------------------------------------------------

def rope_freqs(head_dim: int, rotary_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies for the rotated prefix of the head dim."""
    assert rotary_dim % 2 == 0
    exponents = jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim
    del head_dim
    return 1.0 / (theta ** exponents)          # (rotary_dim/2,)


def yarn_mscale(scale: float, mscale: float) -> float:
    """YaRN's attention temperature: 0.1 mscale ln(scale) + 1 (1 if the
    context is not stretched)."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_correction_range(dim: int, base: float, yarn) -> tuple:
    """(low, high): the rotary pairs between which the frequencies ramp
    from interpolated to plain, from the rotations each pair makes over
    the original context (beta_fast and beta_slow of them)."""
    def corr(rotations):
        return (dim * math.log(yarn.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))
    low = math.floor(corr(yarn.beta_fast))
    high = math.ceil(corr(yarn.beta_slow))
    return max(low, 0), min(high, dim - 1)


def yarn_freqs(dim: int, base: float, yarn) -> jax.Array:
    """YaRN inverse frequencies (dim/2,) as DeepSeek-V2's modelling code
    computes them: plain below `low`, divided by `factor` above `high`, a
    linear ramp between."""
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    inter = extra / np.float32(yarn.factor)
    low, high = yarn_correction_range(dim, base, yarn)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    mask = (1.0 - ramp).astype(np.float32)
    return jnp.asarray(inter * (1 - mask) + extra * mask)


def yarn_softmax_scale(qk_dim: int, yarn) -> float:
    """Attention's softmax scale under YaRN: qk_dim**-0.5 times
    mscale(factor, mscale_all_dim) squared."""
    scale = qk_dim ** -0.5
    if yarn is not None and yarn.mscale_all_dim:
        m = yarn_mscale(yarn.factor, yarn.mscale_all_dim)
        scale = scale * m * m
    return scale


def apply_rope(x: jax.Array, positions: jax.Array, *, rotary_frac: float = 1.0,
               theta: float = 1e4, yarn=None) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq).

    rotary_frac < 1 rotates only the leading fraction of head_dim (partial
    rotary, e.g. ChatGLM's 2D-RoPE halves and GLM/NeoX-style models).
    yarn (configs.base.YaRNConfig): YaRN frequencies, and cos / sin scaled
    by mscale(factor, mscale) / mscale(factor, mscale_all_dim).
    """
    head_dim = x.shape[-1]
    rot = int(head_dim * rotary_frac)
    rot -= rot % 2
    if rot == 0:
        return x
    inv = (rope_freqs(head_dim, rot, theta) if yarn is None
           else yarn_freqs(rot, theta, yarn))              # (rot/2,)
    ang = positions[..., None].astype(jnp.float32) * inv   # (..., seq, rot/2)
    cos = jnp.cos(ang)[..., None, :]                        # (..., seq, 1, rot/2)
    sin = jnp.sin(ang)[..., None, :]
    if yarn is not None:
        m = (yarn_mscale(yarn.factor, yarn.mscale)
             / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    xr = x[..., :rot].astype(jnp.float32)
    x1, x2 = xr[..., : rot // 2], xr[..., rot // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), x[..., rot:]], axis=-1)


def sinusoidal_positions(n: int, d: int) -> jax.Array:
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, d, 2, dtype=jnp.float32) * (-math.log(10000.0) / d))
    pe = jnp.zeros((n, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


# --- activations / loss ---------------------------------------------------------

def act_fn(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
            "gelu_tanh": lambda x: jax.nn.gelu(x, approximate=True),
            "relu": jax.nn.relu}[name]


def softmax_xent(logits: jax.Array, labels: jax.Array,
                 mask: jax.Array | None = None) -> jax.Array:
    """Mean cross-entropy; logits (..., V) promoted to f32."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def causal_mask(q_len: int, kv_len: int, *, q_offset: int = 0,
                window: int | None = None) -> jax.Array:
    """Boolean (q_len, kv_len) mask; True == attend. Supports sliding window."""
    q_pos = jnp.arange(q_len) + q_offset
    k_pos = jnp.arange(kv_len)
    m = k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        m &= k_pos[None, :] > (q_pos[:, None] - window)
    return m
