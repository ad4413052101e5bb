"""The dropless held-expert MoE (DeepSeek-V2-Lite's layer) and its
grouped-matmul kernel, at small widths on seeded random weights: against
a masked-dense reference in the forward pass and in gradients, under a
routing forced onto one expert, summed over the shares of an
expert-parallel deployment, the sequence-wise aux loss against its
formula, and the buffers bounded at R rows against those of all T * k
assignments, bit for bit, with the chunked fallback where the held
assignments outrun R."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import MoEConfig
from repro.kernels import gmm
from repro.models import common, mlp, moe
from repro.models.transformer import Batch, Model

D, FF = 32, 16
DSV2 = MoEConfig(n_experts=64, top_k=6, d_ff=FF, shared_ff=2 * FF,
                 norm_topk_prob=False, routed_scaling=1.0, aux="seq",
                 router_aux_weight=0.001, router_fp32=False, dropless=True)


def _params(m: MoEConfig, key, full: bool = False):
    """Parameters of the layer; `full`: every expert's, whatever is held."""
    held = m if not full else dataclasses.replace(m, held=None)
    return common.init_tree(key, moe.moe_defs(D, held, jnp.float32))


def _share(p: dict, first: int, count: int) -> dict:
    return dict(p, **{k: p[k][first:first + count]
                      for k in ("w1", "w2", "w3")})


def masked_dense(p: dict, x, m: MoEConfig):
    """Each held expert over every token, weighted by the token's gate
    (0 where the expert was not chosen), plus the shared experts."""
    B, S, d = x.shape
    xf = x.reshape(-1, d)
    probs = jax.nn.softmax(xf @ p["router"].astype(jnp.float32), -1)
    w, ids = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * m.routed_scaling
    first, count = m.held_range
    y = jnp.zeros_like(xf)
    for j in range(count):
        gate = jnp.sum(jnp.where(ids == first + j, w, 0.0), -1)
        h = jax.nn.silu(xf @ p["w1"][j]) * (xf @ p["w3"][j])
        y = y + gate[:, None] * (h @ p["w2"][j])
    y = y.reshape(B, S, d)
    if "shared" in p:
        y = y + mlp.mlp_apply(p["shared"], x)
    return y


def _x(key, B=2, S=24):
    return jax.random.normal(jax.random.PRNGKey(key), (B, S, D))


@pytest.mark.parametrize("backend", ["ragged", "interpret"])
@pytest.mark.parametrize("held", [None, (8, 8)])
def test_dropless_matches_masked_dense(backend, held):
    m = dataclasses.replace(DSV2, held=held)
    p = _params(m, jax.random.PRNGKey(0))
    x = _x(1)

    def ours(p, x):
        return moe.moe_dropless(p, x, m, gmm_backend=backend)[0]

    np.testing.assert_allclose(ours(p, x), masked_dense(p, x, m),
                               rtol=1e-5, atol=1e-5)
    loss = lambda f: lambda p, x: jnp.sum(jnp.sin(f(p, x)))  # noqa: E731
    g_ours = jax.grad(loss(ours), (0, 1))(p, x)
    g_ref = jax.grad(loss(lambda p, x: masked_dense(p, x, m)), (0, 1))(p, x)
    for a, b in zip(jax.tree_util.tree_leaves(g_ours),
                    jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def _all_rows(monkeypatch, m: MoEConfig):
    """Buffers of all T * k rows from here on: R = T * k for any T."""
    monkeypatch.setattr(moe, "HELD_HEADROOM", m.n_experts)


@pytest.mark.parametrize("backend", ["ragged", "interpret"])
@pytest.mark.parametrize("held", [(0, 8), (8, 8)])
def test_bounded_buffers_bit_identical_to_all_rows(backend, held,
                                                   monkeypatch):
    """On R = T * k / 4 rows (twice the uniform share of 8 of 64 experts)
    the layer's output and the gradients of x and of every parameter are
    those of the buffers of all T * k rows, bit for bit: the held
    assignments are the sorted order's first rows, and every row past them
    is zero on both paths."""
    m = dataclasses.replace(DSV2, held=held)
    p = _params(m, jax.random.PRNGKey(12))
    x = _x(13, S=512)
    T = x.shape[0] * x.shape[1]
    assert moe.held_row_bound(T, m) == T * m.top_k // 4

    def run():
        def loss(p, x):
            y, stats = moe.moe_dropless(p, x, m, gmm_backend=backend)
            return jnp.sum(jnp.sin(y)), (y, stats)
        (_, (y, stats)), g = jax.jit(jax.value_and_grad(
            loss, (0, 1), has_aux=True))(p, x)
        return y, stats, g

    y, stats, g = run()
    assert float(stats["bounded_layers"]) == 1.0
    _all_rows(monkeypatch, m)
    assert moe.held_row_bound(T, m) == T * m.top_k
    y_all, stats_all, g_all = run()
    assert float(stats_all["bounded_layers"]) == 0.0
    assert float(stats["held_rows"]) == float(stats_all["held_rows"])
    np.testing.assert_array_equal(y, y_all)
    paths = jax.tree_util.tree_leaves_with_path(g)
    assert len(paths) == 1 + len(jax.tree_util.tree_leaves(p))
    for (path, a), b in zip(paths, jax.tree_util.tree_leaves(g_all)):
        np.testing.assert_array_equal(a, b, err_msg=str(path))


@pytest.mark.parametrize("backend", ["ragged", "interpret"])
def test_fallback_when_held_assignments_outrun_the_bound(backend):
    """Every token's six slots forced onto the 8 held experts: 6 T held
    assignments, four times R. The layer runs them in chunks of R rows,
    drops none, and matches the masked-dense reference in the output and
    in gradients; bounded_layers reads 0. T * k = 6,000 is no whole
    number of chunks of R = 1,536, so the last chunk is padded."""
    m = dataclasses.replace(DSV2, held=(0, 8))
    p = _params(m, jax.random.PRNGKey(14))
    x = _x(15, S=500).at[..., 0].set(4.0)
    p["router"] = p["router"].at[0, :8].add(8.0)
    T = x.shape[0] * x.shape[1]
    rows = moe.held_row_bound(T, m)
    assert rows == 1536 and (T * m.top_k) % rows

    def ours(p, x):
        return moe.moe_dropless(p, x, m, gmm_backend=backend)

    y, stats = ours(p, x)
    assert float(stats["held_rows"]) == T * m.top_k > rows
    assert float(stats["bounded_layers"]) == 0.0
    np.testing.assert_allclose(y, masked_dense(p, x, m), rtol=1e-5,
                               atol=1e-5)
    loss = lambda f: lambda p, x: jnp.sum(jnp.sin(f(p, x)))  # noqa: E731
    g_ours = jax.grad(loss(lambda p, x: ours(p, x)[0]), (0, 1))(p, x)
    g_ref = jax.grad(loss(lambda p, x: masked_dense(p, x, m)), (0, 1))(p, x)
    for a, b in zip(jax.tree_util.tree_leaves(g_ours),
                    jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("held,want", [((0, 1), 2.0), (None, 0.0)])
def test_bounded_layers_counts_the_model_layers(held, want):
    """The step's counter over a model's MoE layers: both of them on R
    rows under balanced routing where the chip holds 1 of 4 experts, none
    where it holds all 4 (R = T * k), and the loss's gradient through the
    rematerialised scan stays finite."""
    base = registry.get_smoke_config("deepseek-v2-lite")
    cfg = dataclasses.replace(base, n_layers=3, remat=True,
                              moe=dataclasses.replace(base.moe, held=held))
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(16))
    tok = jax.random.randint(jax.random.PRNGKey(17), (2, 256), 0, cfg.vocab)
    batch = Batch(tokens=tok, labels=tok)
    T = tok.size
    rows = moe.held_row_bound(T, cfg.moe)
    assert (rows < T * cfg.moe.top_k) == (held is not None)

    def loss(params):
        return model.loss(params, batch, with_stats=True)

    (value, stats), g = jax.value_and_grad(loss, has_aux=True)(params)
    assert cfg.pattern_repeats == 2
    assert float(stats["moe/bounded_layers"]) == want
    assert float(stats["moe/held_rows"]) <= 2 * rows
    assert all(bool(jnp.all(jnp.isfinite(x)))
               for x in jax.tree_util.tree_leaves(g))


def test_held_rows_count_the_held_assignments():
    m = dataclasses.replace(DSV2, held=(0, 8))
    p = _params(m, jax.random.PRNGKey(2))
    x = _x(3)
    _, stats = moe.moe_dropless(p, x, m)
    _, ids, _ = moe.route(x.reshape(-1, D), p["router"], m, batch=2)
    ids = np.asarray(ids)
    held = (ids >= 0) & (ids < 8)
    assert float(stats["held_rows"]) == held.sum()
    assert float(stats["max_expert_rows"]) == max(
        (ids == e).sum() for e in range(8))


@pytest.mark.parametrize("backend", ["ragged", "interpret"])
def test_routing_forced_onto_one_expert_drops_nothing(backend):
    """Every token's first choice is expert 0: its group holds all T rows,
    far past a capacity of T k 1.25 / E. The dropless layer computes them
    all; the capacity path drops most of them."""
    m = dataclasses.replace(DSV2, held=(0, 8))
    p = _params(m, jax.random.PRNGKey(4))
    x = _x(5).at[..., 0].set(4.0)
    p["router"] = p["router"].at[0, 0].set(8.0)
    _, ids, _ = moe.route(x.reshape(-1, D), p["router"], m, batch=2)
    assert bool(jnp.all(ids[:, 0] == 0))
    y, stats = moe.moe_dropless(p, x, m, gmm_backend=backend)
    np.testing.assert_allclose(y, masked_dense(p, x, m), rtol=1e-5,
                               atol=1e-5)
    assert float(stats["max_expert_rows"]) == 2 * 24
    # the capacity path over all 64 experts keeps ceil(48 * 6 * 1.25 / 64)
    # rounded up to 8 = 8 of expert 0's 48 rows
    cap = dataclasses.replace(m, held=None, dropless=False)
    p_all = _params(m, jax.random.PRNGKey(4), full=True)
    p_all["router"] = p["router"]
    y_cap, _ = moe.moe_apply(p_all, x, cap)
    y_full = masked_dense(p_all, x, dataclasses.replace(m, held=None))
    assert float(jnp.max(jnp.abs(y_cap - y_full))) > 1e-2
    y_dropless = moe.moe_dropless(p_all, x, dataclasses.replace(
        m, held=None), gmm_backend=backend)[0]
    np.testing.assert_allclose(y_dropless, y_full, rtol=1e-5, atol=1e-5)


def test_shares_add_up_to_the_whole_layer():
    """An EP-8 deployment of 64 experts: the routed parts of the shares
    [0:8), [8:16), ... [56:64), plus the shared experts once, are the
    uncut layer."""
    full = dataclasses.replace(DSV2, held=None)
    p = _params(full, jax.random.PRNGKey(6))
    x = _x(7)
    shared = mlp.mlp_apply(p["shared"], x)
    total = shared
    for first in range(0, 64, 8):
        m = dataclasses.replace(DSV2, held=(first, 8))
        y, _ = moe.moe_dropless(_share(p, first, 8), x, m)
        total = total + (y - shared)
    np.testing.assert_allclose(total, masked_dense(p, x, full), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(total, moe.moe_dropless(p, x, full)[0],
                               rtol=1e-4, atol=1e-5)


def test_sequence_aux_loss_against_its_formula():
    """aux = mean_b sum_e ce[b, e] * mean_t p[b, t, e], ce[b, e] = the
    sequence's top-k slots on e / (T k / E)."""
    m = DSV2
    p = _params(m, jax.random.PRNGKey(8))
    B, S = 3, 10
    x = _x(9, B, S)
    _, ids, aux = moe.route(x.reshape(-1, D), p["router"], m, batch=B)
    probs = np.asarray(jax.nn.softmax(
        x.reshape(-1, D) @ p["router"], -1)).reshape(B, S, 64)
    ids = np.asarray(ids).reshape(B, S, 6)
    want = 0.0
    for b in range(B):
        ce = np.bincount(ids[b].ravel(), minlength=64) / (S * 6 / 64)
        want += np.sum(ce * probs[b].mean(0))
    assert float(aux) == pytest.approx(want / B, rel=1e-5)
    # the switch formula stays the default for other configurations
    sw = dataclasses.replace(m, aux="switch")
    _, ids1, aux1 = moe.route(x.reshape(-1, D), p["router"], sw)
    f = np.bincount(np.asarray(ids1)[:, 0], minlength=64) / (B * S)
    assert float(aux1) == pytest.approx(
        64 * np.sum(f * probs.reshape(-1, 64).mean(0)), rel=1e-5)


def test_router_in_model_dtype_and_scoring_options():
    m = dataclasses.replace(DSV2, held=(0, 8))
    defs = moe.moe_defs(D, m, jnp.bfloat16)
    assert defs["router"].dtype == jnp.bfloat16
    assert defs["w1"].shape == (8, D, FF)
    assert moe.moe_defs(D, dataclasses.replace(m, held=None, dropless=False,
                                               router_fp32=True),
                        jnp.bfloat16)["router"].dtype == jnp.float32
    p = _params(m, jax.random.PRNGKey(10))
    xf = _x(11).reshape(-1, D)
    w, _, _ = moe.route(xf, p["router"], m)
    assert float(jnp.max(jnp.sum(w, -1))) < 1.0          # no renormalising
    w2, _, _ = moe.route(xf, p["router"], dataclasses.replace(
        m, norm_topk_prob=True, routed_scaling=2.5))
    np.testing.assert_allclose(jnp.sum(w2, -1), 2.5, rtol=1e-5)
    with pytest.raises(ValueError, match="dropless"):
        moe.moe_defs(D, dataclasses.replace(m, dropless=False), jnp.float32)


# --- the grouped-matmul kernel ------------------------------------------------

def _gmm_ref(lhs, rhs, sizes):
    """Each group's rows times its matrix, rows past the groups zero."""
    gid = np.repeat(np.arange(len(sizes)), sizes)
    n = int(np.sum(sizes))
    out = jnp.einsum("mk,mkn->mn", lhs[:n], rhs[gid])
    return jnp.zeros((lhs.shape[0], rhs.shape[2]), out.dtype).at[:n].set(out)


@pytest.mark.parametrize("sizes,tile_m", [((10, 0, 17, 9), 8),
                                          ((0, 0, 40, 0), 16),
                                          ((7, 7, 7, 7), 64),
                                          ((0, 0, 0, 0), 8)])
def test_grouped_matmul_kernel_against_einsum(sizes, tile_m):
    """The Pallas kernels (interpreted): forward moe_gmm, and through the
    custom_vjp the rows' gradient (moe_gmm against the transposed weights)
    and the weights' gradient (moe_tgmm); rows past the groups, whatever
    they hold, come out zero in both directions."""
    m, k, n = 56, 32, 24
    rng = np.random.default_rng(0)
    lhs = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(len(sizes), k, n)), jnp.float32)
    s = jnp.asarray(sizes, jnp.int32)
    spec = gmm.Spec(backend="interpret", tile_m=tile_m)
    np.testing.assert_allclose(gmm.grouped_matmul(lhs, rhs, s, spec),
                               _gmm_ref(lhs, rhs, sizes), rtol=1e-5,
                               atol=1e-5)
    junk = lhs.at[sum(sizes):].set(jnp.nan)
    assert bool(jnp.all(jnp.isfinite(gmm.grouped_matmul(junk, rhs, s,
                                                        spec))))

    def loss(f):
        return lambda a, b: jnp.sum(jnp.cos(f(a, b)))
    got = jax.grad(loss(lambda a, b: gmm.grouped_matmul(a, b, s, spec)),
                   (0, 1))(lhs, rhs)
    want = jax.grad(loss(lambda a, b: _gmm_ref(a, b, sizes)), (0, 1))(lhs,
                                                                      rhs)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_grouped_matmul_in_bfloat16_accumulates_in_float32():
    rng = np.random.default_rng(1)
    lhs = jnp.asarray(rng.normal(size=(64, 256)), jnp.bfloat16)
    rhs = jnp.asarray(rng.normal(size=(2, 256, 16)), jnp.bfloat16)
    s = jnp.asarray([30, 20], jnp.int32)
    out = gmm.grouped_matmul(lhs, rhs, s, gmm.Spec("interpret", 16))
    assert out.dtype == jnp.bfloat16
    want = _gmm_ref(lhs.astype(jnp.float32), rhs.astype(jnp.float32),
                    (30, 20))
    np.testing.assert_allclose(out.astype(jnp.float32), want, rtol=1e-2,
                               atol=0.1)


def test_backend_names():
    assert gmm.backend_for("tpu") == "pallas"
    assert gmm.backend_for("cpu") == "ragged"
    with pytest.raises(ValueError, match="backend"):
        gmm.Spec(backend="cuda")
