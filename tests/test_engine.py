"""CommEngine: the unified bucket-reduction data path + overlap mode.

The tentpole claims verified here:

  * the EnginePlan compiles CommConfig + gradient structure + mesh into the
    same routing/fusion decisions the trainer previously inlined;
  * `engine.reduce` is a correct mean-allreduce over the data axes, per-leaf
    for non-fusable (model-sharded) buckets;
  * the overlap schedule is BIT-IDENTICAL to the blocking schedule at fp32
    (same operation sequence, different barrier structure) — the engine
    equivalence acceptance criterion;
  * the trainer is fully decoupled from hier/route_buckets (all bucket
    reduction flows through the engine);
  * the simulator's overlap-aware bucket-schedule estimate behaves.
"""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import registry
from repro.core import engine as eng
from repro.core import hier, hw, planner, scheduler, simulator as sim
from repro.core.api import Session
from repro.core.planner import Planner
from repro.data import pipeline
from repro.models.transformer import Batch, Model
from repro.optim import optimizers as opt_lib
from repro.train import trainer as tr

DSPEC = P((hier.NODE_AXIS, hier.LOCAL_AXIS))
DATA_AXES = (hier.NODE_AXIS, hier.LOCAL_AXIS)


def _tree():
    k = jax.random.PRNGKey(7)
    return {"embed": jax.random.normal(k, (32, 8)),
            "w": jax.random.normal(jax.random.fold_in(k, 1), (64, 16)),
            "head": jax.random.normal(jax.random.fold_in(k, 2), (8, 32))}


# --------------------------------------------------------------------------
# EnginePlan construction
# --------------------------------------------------------------------------

def test_build_plan_flat_defaults(mesh8):
    plan = eng.build_plan(_tree(), eng.CommConfig(mode="mlsl"), mesh8,
                          DATA_AXES)
    assert plan.n_buckets >= 1
    assert plan.dp == 8 and plan.n_node == 1 and plan.n_local == 8
    assert all(a == planner.ALGO_FLAT for a in plan.algos)
    assert all(plan.fusable)
    assert plan.hier_spec is None


def test_build_plan_hier_topo_routing(mesh8):
    comm = eng.CommConfig(mode="mlsl", hier=True, topo="xeon-shm-10gbe")
    plan = eng.build_plan(_tree(), comm, mesh8, DATA_AXES)
    assert plan.n_node == 2 and plan.n_local == 4
    assert plan.hier_spec is not None
    assert len(plan.algos) == plan.n_buckets
    assert all(a in (planner.ALGO_FLAT, planner.ALGO_HIER)
               for a in plan.algos)


def test_build_plan_requires_factored_mesh_for_hier(mesh11):
    with pytest.raises(AssertionError, match="node"):
        eng.build_plan(_tree(), eng.CommConfig(mode="mlsl", hier=True),
                       mesh11, ("data",))


def test_build_plan_unknown_topo(mesh8):
    with pytest.raises(ValueError, match="unknown topology"):
        eng.build_plan(_tree(),
                       eng.CommConfig(mode="mlsl", hier=True, topo="nope"),
                       mesh8, DATA_AXES)


def test_build_plan_zero_fusable_and_empty(mesh8):
    """All-model-sharded tree: no bucket may fuse; empty tree: no buckets."""
    plan = eng.build_plan(_tree(), eng.CommConfig(mode="mlsl"), mesh8,
                          DATA_AXES, leaf_replicated=lambda path: False)
    assert plan.n_buckets >= 1 and not any(plan.fusable)
    empty = eng.build_plan({}, eng.CommConfig(mode="mlsl"), mesh8, DATA_AXES)
    assert empty.n_buckets == 0 and empty.bucket_bytes_list() == ()


# --------------------------------------------------------------------------
# the data path
# --------------------------------------------------------------------------

def _reduce8(mesh8, comm, tree, **plan_kw):
    """engine.reduce inside a manual region over both mesh8 axes; inputs are
    split over the ranks, output is the (replicated) mean."""
    engine = eng.CommEngine.create(
        jax.eval_shape(lambda: jax.tree_util.tree_map(
            lambda x: x[0], tree)), comm, mesh8, DATA_AXES, **plan_kw)

    def f(t):
        local = jax.tree_util.tree_map(lambda x: x[0], t)
        out, _ = engine.reduce(local, None)
        return out

    return jax.jit(jax.shard_map(
        f, mesh=mesh8,
        in_specs=(jax.tree_util.tree_map(lambda _: DSPEC, tree),),
        out_specs=jax.tree_util.tree_map(lambda _: P(), tree),
        check_vma=False))(tree)


@pytest.fixture(scope="module")
def stacked_tree():
    k = jax.random.PRNGKey(3)
    return {"a": jax.random.normal(k, (8, 1000)),
            "b": jax.random.normal(jax.random.fold_in(k, 1), (8, 33, 7))}


def test_engine_reduce_is_mean_allreduce(mesh8, stacked_tree):
    got = _reduce8(mesh8, eng.CommConfig(mode="mlsl"), stacked_tree)
    jax.tree_util.tree_map(
        lambda g, x: np.testing.assert_allclose(
            np.asarray(g), np.mean(np.asarray(x), axis=0),
            rtol=1e-6, atol=1e-7),
        got, stacked_tree)


def test_engine_reduce_per_leaf_when_not_fusable(mesh8, stacked_tree):
    got = _reduce8(mesh8, eng.CommConfig(mode="mlsl"), stacked_tree,
                   leaf_replicated=lambda path: False)
    jax.tree_util.tree_map(
        lambda g, x: np.testing.assert_allclose(
            np.asarray(g), np.mean(np.asarray(x), axis=0),
            rtol=1e-6, atol=1e-7),
        got, stacked_tree)


def test_engine_skip_reduce_is_identity():
    m = jax.make_mesh((1, 1), ("node", "local"))
    t = _tree()
    engine = eng.CommEngine.create(t, eng.CommConfig(mode="mlsl",
                                                     skip_reduce=True),
                                   m, DATA_AXES)
    out, res = engine.reduce(t, None)
    assert res is None
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)), out, t)


def test_engine_gate_token(mesh8):
    """The blocking gate depends on every bucket (scalar), and degrades to
    a zero scalar on an empty plan."""
    t = _tree()
    engine = eng.CommEngine.create(t, eng.CommConfig(mode="mlsl"), mesh8,
                                   DATA_AXES)
    tok = engine.gate_token(t)
    assert tok.shape == () and tok.dtype == jnp.float32
    empty = eng.CommEngine.create({}, eng.CommConfig(mode="mlsl"), mesh8,
                                  DATA_AXES)
    assert float(empty.gate_token({})) == 0.0


def test_engine_ef_residual_state(mesh8):
    comm = eng.CommConfig(mode="mlsl", wire="int8", error_feedback=True)
    engine = eng.CommEngine.create(_tree(), comm, mesh8, DATA_AXES)
    assert engine.plan.use_ef
    res = engine.init_residuals()
    assert len(res) == engine.plan.n_buckets
    specs = engine.residual_specs(P(DATA_AXES))
    assert len(specs) == engine.plan.n_buckets
    # flat-routed bucket residuals: dp * per-rank fabric shard, held in the
    # wire kernels' (blocks, QUANT_BLOCK) layout
    from repro.core import collectives as cl
    for bi, (r, b) in enumerate(zip(res, engine.plan.buckets.buckets)):
        assert engine.ef_applied(bi)
        n = cl.ef_residual_shape(b.n_elems, 8)[0]
        assert r.shape == (n // cl.QUANT_BLOCK * 8, cl.QUANT_BLOCK)


def test_engine_ef_residuals_only_where_applied(mesh8):
    """Non-fusable buckets ride the bf16 wire (no EF) and must not be
    allocated fp32 residual buffers — only zero-length placeholders that
    keep the residual-tuple arity and specs aligned."""
    comm = eng.CommConfig(mode="mlsl", wire="int8", error_feedback=True)
    engine = eng.CommEngine.create(_tree(), comm, mesh8, DATA_AXES,
                                   leaf_replicated=lambda path: False)
    assert engine.plan.use_ef and not any(engine.plan.fusable)
    res = engine.init_residuals()
    assert len(res) == engine.plan.n_buckets
    assert all(r.shape == (0,) for r in res)
    res_spec = engine.residual_specs(P(DATA_AXES))
    assert len(res_spec) == engine.plan.n_buckets
    # the data path carries the placeholders through unchanged
    tree = _tree()
    tspec = jax.tree_util.tree_map(lambda _: P(), tree)
    out, new_res = jax.jit(jax.shard_map(
        lambda t, r: engine.reduce(t, r), mesh=mesh8,
        in_specs=(tspec, res_spec), out_specs=(tspec, res_spec),
        axis_names=set(DATA_AXES), check_vma=False))(tree, res)
    assert all(r.shape == (0,) for r in new_res)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                                rtol=1e-2, atol=1e-2),
        out, tree)


# --------------------------------------------------------------------------
# trainer integration: decoupling + the overlap schedule
# --------------------------------------------------------------------------

def test_trainer_decoupled_from_comm_internals():
    """All bucket reduction flows through CommEngine: the trainer must not
    touch hier / route_buckets / collectives directly."""
    src = inspect.getsource(tr)
    assert "hier" not in src
    assert "route_buckets" not in src
    assert "from repro.core import collectives" not in src


def test_session_builds_engine(mesh8):
    sess = Session.create(mesh8,
                          comm=tr.CommConfig(mode="mlsl", hier=True,
                                             topo="xeon-shm-10gbe"))
    model = Model(registry.get_smoke_config("yi-6b"))
    engine = sess.comm_engine(model)
    assert engine.plan.n_buckets >= 1
    assert engine.plan.n_node == 2 and engine.plan.n_local == 4


def _train(mesh8, comm, steps=2, seed=0):
    cfg = registry.get_smoke_config("yi-6b")
    model = Model(cfg)
    opt = opt_lib.adamw(3e-3)
    pln = Planner(mesh=mesh8)
    dcfg = pipeline.DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=16,
                               seed=seed)
    with jax.set_mesh(mesh8):
        state = tr.make_train_state(model, opt, jax.random.PRNGKey(seed))
        step = jax.jit(tr.make_train_step(model, opt, mesh8, pln, comm))
        losses = []
        for raw in pipeline.iterate(dcfg, steps):
            batch = Batch(tokens=jnp.asarray(raw["tokens"]),
                          labels=jnp.asarray(raw["labels"]))
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
    return losses, state


def test_overlap_bit_identical_to_blocking_fp32(mesh8):
    """The engine equivalence criterion: overlap=True (pipelined microbatch
    reduction) computes the SAME fp32 bits as overlap=False (blocking) —
    only the barrier structure differs."""
    l_off, s_off = _train(mesh8, tr.CommConfig(mode="mlsl", wire="fp32",
                                               accum_steps=2, overlap=False))
    l_on, s_on = _train(mesh8, tr.CommConfig(mode="mlsl", wire="fp32",
                                             accum_steps=2, overlap=True))
    assert l_off == l_on, (l_off, l_on)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        s_off.params, s_on.params)
    # and the losses came from real training steps
    assert l_on[-1] < l_on[0], l_on


def test_overlap_with_hier_routing_trains(mesh8):
    """Pipelined microbatch reduction composes with per-bucket flat-vs-hier
    routing (the full engine path)."""
    comm = tr.CommConfig(mode="mlsl", hier=True, topo="xeon-shm-10gbe",
                         accum_steps=2, overlap=True)
    losses, _ = _train(mesh8, comm, steps=3)
    assert losses[-1] < losses[0], losses


def test_overlap_requires_mlsl(mesh8):
    with pytest.raises(ValueError, match="mlsl"):
        tr.make_train_step(Model(registry.get_smoke_config("yi-6b")),
                           opt_lib.adamw(1e-3), mesh8, Planner(mesh=mesh8),
                           tr.CommConfig(mode="gspmd", overlap=True))


# --------------------------------------------------------------------------
# overlap-aware schedule estimate (simulator + planner)
# --------------------------------------------------------------------------

def test_simulate_bucket_schedule_blocking_exposes_everything():
    st = sim.simulate_bucket_schedule((1e-3, 2e-3), 4, 10e-3, overlap=False)
    np.testing.assert_allclose(st.exposed_comm, 4 * 3e-3)
    np.testing.assert_allclose(st.compute_time, 40e-3)
    np.testing.assert_allclose(st.comm_busy, 4 * 3e-3)


def test_simulate_bucket_schedule_overlap_hides_all_but_drain():
    # comm fits entirely under the next microbatch's compute: only the last
    # microbatch's chain is exposed
    st = sim.simulate_bucket_schedule((1e-3, 2e-3), 4, 10e-3, overlap=True)
    np.testing.assert_allclose(st.exposed_comm, 3e-3)
    off = sim.simulate_bucket_schedule((1e-3, 2e-3), 4, 10e-3, overlap=False)
    assert st.exposed_comm < off.exposed_comm
    np.testing.assert_allclose(off.exposed_comm / st.exposed_comm, 4.0)


def test_simulate_bucket_schedule_single_microbatch_degenerates():
    on = sim.simulate_bucket_schedule((5e-3,), 1, 10e-3, overlap=True)
    off = sim.simulate_bucket_schedule((5e-3,), 1, 10e-3, overlap=False)
    # reduce-at-end either way, fully exposed
    assert (on.total_time, on.exposed_comm) == (off.total_time,
                                                off.exposed_comm)
    np.testing.assert_allclose(on.exposed_comm, 5e-3)


def test_simulate_bucket_schedule_comm_bound_queues():
    # comm >> compute: the link is the bottleneck; exposed = total queue
    # drain past the compute, and overlap still helps vs blocking
    on = sim.simulate_bucket_schedule((50e-3,), 3, 1e-3, overlap=True)
    off = sim.simulate_bucket_schedule((50e-3,), 3, 1e-3, overlap=False)
    np.testing.assert_allclose(on.total_time, 1e-3 + 3 * 50e-3)
    assert on.exposed_comm < off.exposed_comm


def test_estimate_overlap_on_engine_plan(mesh8):
    plan = eng.build_plan(_tree(), eng.CommConfig(mode="mlsl"), mesh8,
                          DATA_AXES)
    off, on = planner.estimate_overlap(plan.buckets.buckets, plan.algos,
                                       2, hw.CLOUD_10G, 4, 5e-3)
    assert off.exposed_comm >= on.exposed_comm >= 0.0
    assert off.comm_busy == on.comm_busy > 0.0
    times = planner.bucket_allreduce_times(plan.buckets.buckets, plan.algos,
                                           2, hw.CLOUD_10G)
    assert len(times) == plan.n_buckets and all(t > 0 for t in times)
