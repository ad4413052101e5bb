"""Executed hybrid (data x model) parallelism: the C2C chooser's verdicts
materialized as real tensor-parallel sharding on the ("node"=2, "local"=4)
mesh — f/g activation collectives, plan gating and clean DP fallback, the
engine's per-bucket reduce axes, and step-for-step equivalence with pure DP.
"""

from __future__ import annotations

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs import registry
from repro.core import c2c, collectives as cl, hw, planner as pl
from repro.data import pipeline
from repro.models.transformer import Batch, Model
from repro.optim import optimizers as opt_lib
from repro.train import trainer as tr

AXES = {"node", "local"}


# ---------------------------------------------------------------------------
# f/g activation collectives
# ---------------------------------------------------------------------------

def test_fg_ops_match_dense_reference(mesh8):
    """Column-sharded w1 / row-sharded w2 through tp_replicate (f) and
    tp_psum (g) reproduces the dense forward AND all gradients — the
    transpose-correctness property the custom_vjp pair exists for."""
    d, h = 8, 16
    x = jax.random.normal(jax.random.PRNGKey(0), (4, d), jnp.float32)
    w1 = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (d, h), jnp.float32)
    w2 = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (h, d), jnp.float32)

    def dense_loss(w1, w2, x):
        return jnp.sum(jax.nn.relu(x @ w1) @ w2)

    def inner(w1, w2, x):
        # grads taken INSIDE the manual region, exactly like the trainer:
        # the f/g pair routes the activation cotangents between ranks
        def loss_fn(w1, w2, x):
            xr = cl.tp_replicate(x, "local")
            y = cl.tp_psum(jax.nn.relu(xr @ w1) @ w2, "local")
            return jnp.sum(y)
        return jax.value_and_grad(loss_fn, argnums=(0, 1, 2))(w1, w2, x)

    w_specs = (P(None, "local"), P("local", None), P())
    sharded = jax.shard_map(inner, mesh=mesh8, in_specs=w_specs,
                            out_specs=(P(), w_specs), axis_names=AXES,
                            check_vma=False)

    with jax.set_mesh(mesh8):
        loss, (g1, g2, gx) = sharded(w1, w2, x)
    ref = dense_loss(w1, w2, x)
    d1, d2, dx = jax.grad(dense_loss, argnums=(0, 1, 2))(w1, w2, x)
    np.testing.assert_allclose(np.asarray(loss), np.asarray(ref), atol=1e-4)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(d1), atol=1e-4)
    np.testing.assert_allclose(np.asarray(g2), np.asarray(d2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(dx), atol=1e-4)


def test_tp_psum_scatter_matches_tp_psum(mesh8):
    """The bandwidth-shaped psum (reduce_scatter + all_gather over the
    trailing dim) is numerically the plain psum."""
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 8), jnp.float32)

    def run(op):
        def inner(v):
            # make per-rank values distinct so the reduction is exercised
            r = jax.lax.axis_index("local").astype(jnp.float32)
            return op(v * (1.0 + r), "local")
        return jax.shard_map(inner, mesh=mesh8, in_specs=P(),
                             out_specs=P(), axis_names=AXES,
                             check_vma=False)(x)

    with jax.set_mesh(mesh8):
        a = run(cl.tp_psum)
        b = run(cl.tp_psum_scatter)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


# ---------------------------------------------------------------------------
# plan gating: chooser verdict -> executed sharding
# ---------------------------------------------------------------------------

def _amesh():
    return jax.sharding.AbstractMesh((2, 4), ("node", "local"))


def test_plan_hybrid_verdicts_match_execution():
    cfg = registry.get_smoke_config("yi-6b")
    plan = pl.plan_hybrid(cfg, _amesh(), batch=8, seq=64)
    assert plan.tp == 4 and plan.dp == 2 and plan.data_axes == ("node",)
    blk = plan.layer("p0_attn")
    assert blk.choice.strategy in (c2c.Strategy.HYBRID, c2c.Strategy.MODEL)
    assert blk.model_parallel and blk.reason == ""
    # chooser sends embed/head data-parallel or they are gated off — either
    # way they must not execute model-parallel
    for name in ("embed", "head"):
        lp = plan.layer(name)
        assert not lp.model_parallel
        assert lp.reason in ("chooser-data",) or \
            lp.reason.startswith("unsupported-kind")
    assert plan.any_model_parallel


def test_hybrid_planner_emits_sharded_specs():
    """The chooser's model-parallel verdict becomes actual PartitionSpecs:
    attention projections shard over "local", everything else replicates."""
    cfg = registry.get_smoke_config("yi-6b")
    planner = pl.make_hybrid_planner(_amesh(), cfg, batch=8, seq=64)
    specs = planner.tree_specs(Model(cfg).param_defs(),
                               stacked_paths=Model.stacked_path)
    attn = specs["blocks"]["p0_attn"]["attn"]
    assert attn["wq"] == P(None, None, "local")      # stacked: leading layer
    assert attn["wo"] == P(None, "local", None)
    mlp = specs["blocks"]["p0_attn"]["mlp"]
    assert mlp["w1"] == P(None, None, "local")
    assert mlp["w2"] == P(None, "local", None)
    assert specs["embed"] == P(None, None)
    assert specs["head"] == P(None, None)


def test_group_indivisible_falls_back_to_dp():
    cfg = registry.get_smoke_config("yi-6b")
    for g in (2, 3):
        plan = pl.plan_hybrid(cfg, _amesh(), batch=8, seq=64, group_size=g)
        assert not plan.any_model_parallel, g
        assert any(lp.reason.startswith("group-indivisible")
                   for lp in plan.layers), g
        planner = pl.make_hybrid_planner(_amesh(), cfg, batch=8, seq=64,
                                         group_size=g)
        specs = planner.tree_specs(Model(cfg).param_defs(),
                                   stacked_paths=Model.stacked_path)
        for spec in jax.tree_util.tree_leaves(
                specs, is_leaf=lambda s: isinstance(s, P)):
            assert all(ax is None for ax in spec), (g, spec)


def _indivisible_heads_cfg():
    cfg = registry.get_smoke_config("yi-6b")
    return dataclasses.replace(
        cfg, attn=dataclasses.replace(cfg.attn, n_heads=2, n_kv=2))


def test_indivisible_heads_fall_back_to_dp():
    plan = pl.plan_hybrid(_indivisible_heads_cfg(), _amesh(), batch=8, seq=64)
    assert not plan.any_model_parallel
    lp = plan.layer("p0_attn")
    if lp.choice.group_size > 1:          # chooser wanted the group anyway
        assert lp.reason.startswith("indivisible-heads")


def test_c2c_layer_names_match_param_tree():
    for arch in ("yi-6b", "chatglm3-6b", "deepseek-7b"):
        cfg = registry.get_smoke_config(arch)
        defs = Model(cfg).param_defs()
        valid = {"embed", "head"} | set(defs.get("blocks", {})) \
            | set(defs.get("tail", {}))
        for spec in c2c.layers_from_model_config(cfg, 64):
            assert spec.name in valid, (arch, spec.name)


# ---------------------------------------------------------------------------
# engine: per-bucket reduce axes
# ---------------------------------------------------------------------------

def _hybrid_engine(mesh8, comm=None, cfg=None):
    cfg = cfg or registry.get_smoke_config("yi-6b")
    planner = pl.make_hybrid_planner(mesh8, cfg, batch=8, seq=32)
    comm = comm or tr.CommConfig(mode="mlsl", hier=True)
    return tr.make_comm_engine(Model(cfg), mesh8, planner, comm)


def test_engine_hybrid_bucket_axes(mesh8):
    engine = _hybrid_engine(mesh8)
    plan = engine.plan
    assert plan.tp_axis == "local" and plan.tp == 4
    assert len(plan.bucket_axes) == plan.n_buckets
    # both flavors exist: sharded buckets reduce over the node axis only,
    # replicated ones keep the full two-level (node, local) reduction
    assert set(plan.bucket_axes) == {("node",), ("node", "local")}
    assert engine.tp is not None and engine.tp.axis == "local"
    # model-sharded buckets cannot take the two-level route
    for axes, algo in zip(plan.bucket_axes, plan.algos):
        if axes == ("node",):
            assert algo == pl.ALGO_FLAT


def test_engine_hybrid_rejects_error_feedback(mesh8):
    comm = tr.CommConfig(mode="mlsl", hier=True, wire="int8",
                         error_feedback=True)
    with pytest.raises(ValueError, match="error feedback"):
        _hybrid_engine(mesh8, comm=comm)


def test_trainer_hybrid_requires_mlsl(mesh8):
    cfg = registry.get_smoke_config("yi-6b")
    planner = pl.make_hybrid_planner(mesh8, cfg, batch=8, seq=32)
    with pytest.raises(ValueError, match="mlsl"):
        tr.make_train_step(Model(cfg), opt_lib.adamw(1e-3), mesh8, planner,
                           tr.CommConfig(mode="gspmd"))


# ---------------------------------------------------------------------------
# executed training: hybrid == pure DP, step for step
# ---------------------------------------------------------------------------

def _train(mesh, cfg, planner, steps=2, seq=16, batch=8):
    model = Model(cfg)
    opt = opt_lib.make_optimizer("sgd", 0.1)
    comm = tr.CommConfig(mode="mlsl", hier=True)
    dcfg = pipeline.DataConfig(vocab=cfg.vocab, seq_len=seq,
                               global_batch=batch, seed=3)
    with jax.set_mesh(mesh):
        state = tr.make_train_state(model, opt, jax.random.PRNGKey(0))
        step = jax.jit(tr.make_train_step(model, opt, mesh, planner, comm))
        metrics = []
        for raw in pipeline.iterate(dcfg, steps):
            b = Batch(tokens=jnp.asarray(raw["tokens"]),
                      labels=jnp.asarray(raw["labels"]))
            state, m = step(state, b)
            metrics.append((float(m["loss"]), float(m["grad_norm"])))
    return metrics, state


def _assert_same_training(cfg, mesh8, atol_loss=5e-4, atol_params=1e-4):
    dp_m, dp_state = _train(mesh8, cfg, pl.Planner(mesh=mesh8))
    hy_m, hy_state = _train(mesh8, cfg,
                            pl.make_hybrid_planner(mesh8, cfg, batch=8,
                                                   seq=16))
    for (dl, dg), (hl, hg) in zip(dp_m, hy_m):
        assert np.isfinite(hl) and np.isfinite(hg)
        assert abs(dl - hl) < atol_loss, (dp_m, hy_m)
    dp_leaves = jax.tree_util.tree_leaves(dp_state.params)
    hy_leaves = jax.tree_util.tree_leaves(hy_state.params)
    assert len(dp_leaves) == len(hy_leaves)
    for a, b in zip(dp_leaves, hy_leaves):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=atol_params)


def test_hybrid_step_matches_dp_with_sharded_layers(mesh8):
    """THE tentpole equivalence: the chooser sends p0_attn model-parallel,
    the weights really shard over "local", and two executed training steps
    land where pure DP-8 lands at the same global batch."""
    cfg = registry.get_smoke_config("yi-6b")
    planner = pl.make_hybrid_planner(mesh8, cfg, batch=8, seq=16)
    assert planner.hybrid.any_model_parallel
    _assert_same_training(cfg, mesh8)


def test_hybrid_step_matches_dp_on_fallback_config(mesh8):
    """When every layer is gated back to DP (indivisible heads) the hybrid
    machinery still runs — through the same manual region — and must be
    exactly a DP step on replicated weights."""
    cfg = _indivisible_heads_cfg()
    planner = pl.make_hybrid_planner(mesh8, cfg, batch=8, seq=16)
    assert not planner.hybrid.any_model_parallel
    _assert_same_training(cfg, mesh8)


# ---------------------------------------------------------------------------
# modeled exposed-comm win
# ---------------------------------------------------------------------------

def test_modeled_hybrid_beats_pure_dp():
    cfg = registry.get_smoke_config("yi-6b")
    plan = pl.plan_hybrid(cfg, _amesh(), batch=8, seq=64)
    layers = c2c.layers_from_model_config(cfg, 64)
    for topo in (hw.CLOUD_10G, hw.HPC_OPA):
        cm = pl.model_hybrid_comm(plan, layers, batch=8, nodes=plan.dp,
                                  topo=topo)
        assert cm.t_hybrid < cm.t_dp_flat, topo.name
        assert cm.reduction_vs_flat > 1.0
        # the hybrid fabric traffic is strictly smaller than full-gradient DP
        assert cm.hybrid_grad_bytes < cm.dp_grad_bytes
