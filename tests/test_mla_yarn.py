"""MLA without the query low-rank (DeepSeek-V2-Lite) and YaRN RoPE:
the YaRN constants against DeepSeek-V2's published values, the no-LoRA
query path against its formula, and MiniCPM3's MLA (query low-rank, no
YaRN) unchanged."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import MLAConfig
from repro.models import attention as attn
from repro.models import common

DSV2 = registry.get_config("deepseek-v2-lite").mla


def test_yarn_correction_range_and_frequencies():
    """dim 64, base 1e4, factor 40, original 4096: low = floor(corr(32))
    = 10, high = ceil(corr(1)) = 23; plain frequencies below, divided by
    40 above, a linear ramp between."""
    y = DSV2.yarn
    assert common.yarn_correction_range(64, 1e4, y) == (10, 23)
    inv = np.asarray(common.yarn_freqs(64, 1e4, y))
    extra = 1.0 / 1e4 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], extra[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], extra[23:] / 40, rtol=1e-6)
    i = np.arange(32)
    mask = 1 - np.clip((i - 10) / 13, 0, 1)
    np.testing.assert_allclose(inv, extra / 40 * (1 - mask) + extra * mask,
                               rtol=1e-6)


def test_yarn_scales():
    """cos / sin scale m(40, .707) / m(40, .707) = 1; the softmax scale is
    192**-0.5 * m(40, .707)**2 = 192**-0.5 * 1.58963."""
    y = DSV2.yarn
    m = common.yarn_mscale(40, 0.707)
    assert m == pytest.approx(0.1 * 0.707 * math.log(40) + 1)
    assert m * m == pytest.approx(1.58963, abs=1e-5)
    assert common.yarn_softmax_scale(192, y) == pytest.approx(
        192 ** -0.5 * 1.58963, rel=1e-5)
    assert attn._mla_scale(DSV2) == common.yarn_softmax_scale(192, y)
    # the rope itself is a rotation at YaRN's frequencies, unscaled
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 64))
    out = common.apply_rope(x, jnp.arange(5), theta=1e4, yarn=y)
    np.testing.assert_allclose(np.linalg.norm(out, axis=-1),
                               np.linalg.norm(x, axis=-1), rtol=1e-5)
    ang = 3 * np.asarray(common.yarn_freqs(64, 1e4, y))
    x1, x2 = np.asarray(x[0, 3, 0, :32]), np.asarray(x[0, 3, 0, 32:])
    np.testing.assert_allclose(
        out[0, 3, 0], np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                                      x1 * np.sin(ang) + x2 * np.cos(ang)]),
        rtol=1e-5, atol=1e-5)


def _small(m: MLAConfig, q_lora: int) -> MLAConfig:
    return dataclasses.replace(m, n_heads=4, q_lora_rank=q_lora,
                               kv_lora_rank=32, qk_nope_dim=16,
                               qk_rope_dim=8, v_head_dim=16)


def _params(m: MLAConfig, d: int, key):
    defs = attn.mla_defs(d, m, jnp.float32)
    return common.init_tree(key, defs)


def _reference(p, x, m: MLAConfig, scale: float, yarn):
    """MLA written out: q from x (through the low-rank and its norm if
    any), the latent and the shared roped key part, causal softmax."""
    B, S, _ = x.shape
    H = m.n_heads
    if m.q_lora_rank:
        q = common.rmsnorm(x @ p["w_dq"], p["q_norm"]) @ p["w_uq"]
    else:
        q = x @ p["w_q"]
    q = q.reshape(B, S, H, -1)
    qn, qp = q[..., :m.qk_nope_dim], q[..., m.qk_nope_dim:]
    kv = x @ p["w_dkv"]
    c = common.rmsnorm(kv[..., :m.kv_lora_rank], p["kv_norm"])
    pos = jnp.arange(S)
    qp = common.apply_rope(qp, pos, theta=m.rope_theta, yarn=yarn)
    kp = common.apply_rope(kv[..., None, m.kv_lora_rank:], pos,
                           theta=m.rope_theta, yarn=yarn)[..., 0, :]
    kn = (c @ p["w_uk"]).reshape(B, S, H, -1)
    v = (c @ p["w_uv"]).reshape(B, S, H, -1)
    s = (jnp.einsum("bqhd,bkhd->bhqk", qn, kn)
         + jnp.einsum("bqhd,bkd->bhqk", qp, kp)) * scale
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -1e30)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    return o.reshape(B, S, -1) @ p["wo"]


@pytest.mark.parametrize("kv_chunk", [None, 8])
def test_mla_without_query_lora_with_yarn(kv_chunk):
    m = _small(DSV2, 0)
    p = _params(m, 64, jax.random.PRNGKey(1))
    assert "w_q" in p and "w_dq" not in p and "q_norm" not in p
    assert p["w_q"].shape == (64, 4 * 24)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, 64))
    got = attn.mla_apply(p, x, m, kv_chunk=kv_chunk)
    want = _reference(p, x, m, common.yarn_softmax_scale(24, m.yarn),
                      m.yarn)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kv_chunk", [None, 8])
def test_minicpm3_mla_unchanged(kv_chunk):
    """MiniCPM3's MLA: query low-rank and its norm, plain RoPE, scale
    qk**-0.5; its parameters are those it always had."""
    m = _small(registry.get_config("minicpm3-4b").mla, 64)
    assert m.yarn is None
    p = _params(m, 64, jax.random.PRNGKey(3))
    assert set(p) == {"w_dq", "q_norm", "w_uq", "w_dkv", "kv_norm", "w_uk",
                      "w_uv", "wo"}
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, 64))
    got = attn.mla_apply(p, x, m, kv_chunk=kv_chunk)
    want = _reference(p, x, m, 1 / math.sqrt(24), None)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_chunk_remat_keeps_values_and_gradients():
    """Chunked attention recomputes each chunk in the backward pass: the
    same output and the same gradients as attention over the whole
    (Sq, Sk) score matrix."""
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, 32, 2, 8))
               for i in range(3))
    mask = common.causal_mask(32, 32)

    def chunked(q, k, v):
        return jnp.sum(jnp.sin(attn.chunked_sdpa(q, k, v, kv_chunk=8)))

    def whole(q, k, v):
        return jnp.sum(jnp.sin(attn._sdpa(q, k, v, mask)))
    np.testing.assert_allclose(chunked(q, k, v), whole(q, k, v), rtol=1e-5)
    for a, b in zip(jax.grad(chunked, (0, 1, 2))(q, k, v),
                    jax.grad(whole, (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_decode_matches_full_forward_without_query_lora():
    """The absorbed-projection decode of the no-LoRA YaRN MLA gives the
    full forward's last position."""
    m = _small(DSV2, 0)
    p = _params(m, 64, jax.random.PRNGKey(5))
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 9, 64))
    full = attn.mla_apply(p, x, m)
    cache = attn.mla_prefill_cache(p, x[:, :8], m)
    cache = jax.tree_util.tree_map(
        lambda c: jnp.pad(c, ((0, 0), (0, 4), (0, 0))), cache)
    y, _ = attn.mla_decode(p, x[:, 8:], cache, jnp.int32(8), m)
    np.testing.assert_allclose(y[:, 0], full[:, 8], rtol=1e-4, atol=1e-5)
