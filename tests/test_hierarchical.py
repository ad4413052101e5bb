"""Hierarchical two-level collectives on the 8-virtual-device harness, plus
the JAX sharding behaviour they sit on.

The mesh8 fixture (conftest) factors the 8 fake host devices into
("node"=2, "local"=4): "local" stands for the fast intra-node link, "node"
for the slow fabric. The tentpole claims verified here:

  * fp32 legs: the two-level decomposition is BIT-EXACT against the
    per-axis psum reference (psum over local, then node -- the same
    reduction tree) and within float32 ulp of the flat one-shot
    ``lax.psum`` over both axes (XLA's 8-rank allreduce associates in its
    own internal order, so last-ulp equality with it is not defined);
  * lossy legs (bf16 intra, int8 fabric, error feedback) stay within their
    wire tolerances;
  * the Comm facade, bucket-scheduler routing, and per-level cost model
    agree on when the hierarchy pays.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import collectives as cl
from repro.core import hier, hw, planner, scheduler, simulator as sim

DSPEC = P((hier.NODE_AXIS, hier.LOCAL_AXIS))


def _run8(fn, mesh8, *args, in_specs=None, out_specs=P()):
    """Run fn manually over both data axes of the (2, 4) mesh."""
    if in_specs is None:
        in_specs = tuple(DSPEC for _ in args)
    return jax.jit(jax.shard_map(fn, mesh=mesh8, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))(*args)


@pytest.fixture(scope="module")
def x8():
    return jax.random.normal(jax.random.PRNGKey(0), (8, 4097),
                             jnp.float32) * 1e-3


def _psum_ref(mesh8, x8):
    return np.asarray(_run8(
        lambda u: lax.psum(u[0], (hier.NODE_AXIS, hier.LOCAL_AXIS)),
        mesh8, x8))


def test_hier_fp32_bit_exact_vs_per_axis_psum(mesh8, x8):
    """fp32 legs == the controlled two-level reduction tree, bitwise."""
    seq = np.asarray(_run8(
        lambda u: lax.psum(lax.psum(u[0], hier.LOCAL_AXIS), hier.NODE_AXIS),
        mesh8, x8))
    got = np.asarray(_run8(lambda u: hier.hier_allreduce(u[0]), mesh8, x8))
    np.testing.assert_array_equal(got, seq)


def test_hier_fp32_matches_flat_psum_to_ulp(mesh8, x8):
    ref = _psum_ref(mesh8, x8)
    got = np.asarray(_run8(lambda u: hier.hier_allreduce(u[0]), mesh8, x8))
    # 8-way fp32 sums of ~1e-3 values: a few ulp of headroom
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-8)


def test_hier_fp32_matches_flat_collectives_allreduce(mesh8, x8):
    flat = np.asarray(_run8(
        lambda u: cl.allreduce(u[0], (hier.NODE_AXIS, hier.LOCAL_AXIS)),
        mesh8, x8))
    got = np.asarray(_run8(lambda u: hier.hier_allreduce(u[0]), mesh8, x8))
    np.testing.assert_allclose(got, flat, rtol=1e-6, atol=1e-8)


@pytest.mark.parametrize("spec,tol", [
    (hier.HierSpec(wire_intra="bf16"), 3e-2),
    (hier.HierSpec(wire_intra="bf16", wire_inter="bf16"), 3e-2),
    (hier.HierSpec(wire_intra="bf16", wire_inter="int8"), 2e-2),
    (hier.HierSpec(wire_inter="int8"), 2e-2),
])
def test_hier_lossy_legs_within_wire_tolerance(mesh8, x8, spec, tol):
    ref = _psum_ref(mesh8, x8)
    got = np.asarray(_run8(
        lambda u, s=spec: hier.hier_allreduce(u[0], s), mesh8, x8))
    err = np.max(np.abs(got - ref)) / np.max(np.abs(ref))
    assert err < tol, (spec, err)


def test_hier_mean_divides_by_total_ranks(mesh8, x8):
    ref = _psum_ref(mesh8, x8) / 8.0
    got = np.asarray(_run8(
        lambda u: hier.hier_allreduce(u[0], mean=True), mesh8, x8))
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-9)


def test_hier_error_feedback_roundtrip(mesh8, x8):
    spec = hier.HierSpec(wire_intra="bf16", wire_inter="int8",
                         error_feedback=True)
    shard = hier.ef_residual_shape(x8[0].size, local=4, node=2)
    res0 = jnp.zeros((shard[0] * 8,), jnp.float32)  # global view, 8 ranks

    def f(u, r):
        return hier.hier_allreduce_ef(u[0], r, spec)

    y, res = jax.jit(jax.shard_map(
        f, mesh=mesh8, in_specs=(DSPEC, DSPEC),
        out_specs=(P(), DSPEC), check_vma=False))(x8, res0)
    ref = _psum_ref(mesh8, x8)
    err = np.max(np.abs(np.asarray(y) - ref)) / np.max(np.abs(ref))
    assert err < 2e-2, err
    # the residual carries the (nonzero) per-rank quantization error
    assert res.shape == res0.shape
    assert float(jnp.max(jnp.abs(res))) > 0


def test_hier_spec_validation():
    with pytest.raises(ValueError):
        hier.HierSpec(wire_intra="int8")           # lossy wire can't reduce
    with pytest.raises(ValueError):
        hier.HierSpec(error_feedback=True)         # EF needs int8 fabric
    with pytest.raises(ValueError):
        hier.HierSpec(wire_inter="fp8")            # unknown wire


# --------------------------------------------------------------------------
# Comm facade
# --------------------------------------------------------------------------

def test_comm_hierarchical_facade(mesh8, x8):
    comm = cl.Comm(mesh=mesh8, data_axes=(hier.NODE_AXIS, hier.LOCAL_AXIS),
                   model_axis=None, node_axis=hier.NODE_AXIS,
                   local_axis=hier.LOCAL_AXIS)
    assert comm.hierarchical
    assert comm.node_size == 2 and comm.local_size == 4
    assert comm.data_parallel_size == 8

    ref = _psum_ref(mesh8, x8)
    y = jax.jit(lambda v: comm.run(
        lambda u: comm.allreduce(u[0]), DSPEC, P(), v))(x8)
    np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-6, atol=1e-8)
    # lossy fabric leg defaults the intra legs to bf16
    y8 = jax.jit(lambda v: comm.run(
        lambda u: comm.allreduce(u[0], wire="int8"), DSPEC, P(), v))(x8)
    err = np.max(np.abs(np.asarray(y8) - ref)) / np.max(np.abs(ref))
    assert err < 2e-2, err


def test_comm_flat_mesh_stays_flat(mesh11):
    comm = cl.Comm(mesh=mesh11, data_axes=("data",))
    assert not comm.hierarchical
    y = jax.jit(lambda v: comm.run(
        lambda u: comm.allreduce(u), P(), P(), v))(jnp.ones((8,)))
    np.testing.assert_array_equal(np.asarray(y), np.ones(8))


# --------------------------------------------------------------------------
# cost model: planner choice, scheduler routing, simulator integration
# --------------------------------------------------------------------------

def test_hier_time_beats_flat_for_bulk_messages():
    for topo in (hw.CLOUD_10G, hw.HPC_OPA):
        t_flat = hw.flat_allreduce_time(100e6, 16, topo)
        t_hier = hw.hier_allreduce_time(100e6, 16, topo)
        assert t_hier < t_flat, topo.name


def test_choose_allreduce_algo_degenerate_hierarchies():
    assert planner.choose_allreduce_algo(1e6, nodes=1, topo=hw.CLOUD_10G) \
        == planner.ALGO_FLAT
    flat_topo = hw.Topology("flat", intra=hw.SHM_LINK, inter=hw.ETH_10G,
                            local_size=1)
    assert planner.choose_allreduce_algo(1e8, nodes=16, topo=flat_topo) \
        == planner.ALGO_FLAT


def test_choose_allreduce_algo_prefers_hier_for_bulk():
    assert planner.choose_allreduce_algo(1e8, nodes=16, topo=hw.CLOUD_10G) \
        == planner.ALGO_HIER


def test_scheduler_routes_bulk_buckets_hierarchically():
    tree = {"first": jnp.zeros((4,)), "bulk": jnp.zeros((64, 1024, 256))}
    plan = scheduler.plan_buckets(tree, bucket_bytes=1 << 16)
    routes = scheduler.route_buckets(plan, hw.CLOUD_10G, nodes=16)
    assert len(routes) == len(plan.buckets)
    assert all(r in (planner.ALGO_FLAT, planner.ALGO_HIER) for r in routes)
    by_size = {b.n_elems: r for b, r in zip(plan.buckets, routes)}
    assert by_size[64 * 1024 * 256] == planner.ALGO_HIER


def test_simulator_hier_topology_improves_iteration():
    layers = [sim.SimLayer(f"l{i}", fwd_time=1e-3, bwd_time=2e-3,
                           wgrad_bytes=50e6) for i in range(8)]
    flat = sim.simulate_iteration(layers, 16, hw.ETH_10G,
                                  topo=hw.CLOUD_10G, comm_algo="flat")
    hier_st = sim.simulate_iteration(layers, 16, hw.ETH_10G,
                                     topo=hw.CLOUD_10G, comm_algo="hier")
    auto = sim.simulate_iteration(layers, 16, hw.ETH_10G,
                                  topo=hw.CLOUD_10G, comm_algo="auto")
    assert hier_st.total_time < flat.total_time
    assert auto.total_time <= min(hier_st.total_time, flat.total_time) + 1e-12
    # hierarchy lifts weak-scaling efficiency at fixed node count
    eff_flat = sim.scaling_efficiency(layers, 16, hw.ETH_10G,
                                      topo=hw.CLOUD_10G, comm_algo="flat")
    eff_hier = sim.scaling_efficiency(layers, 16, hw.ETH_10G,
                                      topo=hw.CLOUD_10G, comm_algo="hier")
    assert eff_hier > eff_flat


def test_wire_bytes_per_level_accounting():
    spec = hier.HierSpec(wire_intra="bf16", wire_inter="int8")
    wb = hier.hier_wire_bytes_per_elem(spec, local=4, node=2)
    flat = hier.flat_wire_bytes_per_elem("int8")
    # the fabric sees exactly 1/local of the flat int8 volume
    np.testing.assert_allclose(wb.inter, flat.inter / 4)
    assert wb.total == wb.intra + wb.inter
    # degenerate levels carry nothing
    assert hier.hier_wire_bytes_per_elem(spec, local=1, node=2).intra == 0.0
    assert hier.hier_wire_bytes_per_elem(spec, local=4, node=1).inter == 0.0


# --------------------------------------------------------------------------
# end-to-end: trainer routes buckets through the two-level path
# --------------------------------------------------------------------------

def test_trainer_hier_matches_flat_mlsl(mesh8):
    from repro.configs import registry
    from repro.core.planner import Planner
    from repro.data import pipeline
    from repro.models.transformer import Batch, Model
    from repro.optim import optimizers as opt_lib
    from repro.train import trainer as tr

    cfg = registry.get_smoke_config("yi-6b")
    model = Model(cfg)
    opt = opt_lib.adamw(3e-3)
    pln = Planner(mesh=mesh8)
    dcfg = pipeline.DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=8)
    results = {}
    for name, comm in (("flat", tr.CommConfig(mode="mlsl")),
                       ("hier", tr.CommConfig(mode="mlsl", hier=True))):
        with jax.set_mesh(mesh8):
            state = tr.make_train_state(model, opt, jax.random.PRNGKey(0))
            step = jax.jit(tr.make_train_step(model, opt, mesh8, pln, comm))
            for raw in pipeline.iterate(dcfg, 3):
                batch = Batch(tokens=jnp.asarray(raw["tokens"]),
                              labels=jnp.asarray(raw["labels"]))
                state, m = step(state, batch)
        results[name] = (float(m["loss"]), state.params)
    # fp32 legs: same math up to reduction-order ulp; Adam amplifies noise
    assert abs(results["flat"][0] - results["hier"][0]) < 1e-4, results
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=1e-2, atol=5e-4),
        results["flat"][1], results["hier"][1])


def test_trainer_topo_routing_trains(mesh8):
    """CommConfig(topo=...) routes each bucket flat-vs-hier via the cost
    model; the result must still be a correct (converging) fp32 reduction."""
    from repro.configs import registry
    from repro.core.planner import Planner
    from repro.data import pipeline
    from repro.models.transformer import Batch, Model
    from repro.optim import optimizers as opt_lib
    from repro.train import trainer as tr

    cfg = registry.get_smoke_config("yi-6b")
    model = Model(cfg)
    opt = opt_lib.adamw(3e-3)
    comm = tr.CommConfig(mode="mlsl", hier=True, topo="xeon-shm-10gbe")
    dcfg = pipeline.DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=8)
    with jax.set_mesh(mesh8):
        state = tr.make_train_state(model, opt, jax.random.PRNGKey(0))
        step = jax.jit(tr.make_train_step(model, opt, mesh8,
                                          Planner(mesh=mesh8), comm))
        losses = []
        for raw in pipeline.iterate(dcfg, 3):
            batch = Batch(tokens=jnp.asarray(raw["tokens"]),
                          labels=jnp.asarray(raw["labels"]))
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses


def test_scaling_efficiency_single_node_topo_not_trivially_one():
    layers = [sim.SimLayer("l", fwd_time=1e-3, bwd_time=2e-3,
                           wgrad_bytes=100e6)]
    eff = sim.scaling_efficiency(layers, 1, hw.ETH_10G, topo=hw.CLOUD_10G,
                                 comm_algo="hier")
    # one node of local_size=4 ranks still pays intra-node communication
    assert eff < 1.0
    assert sim.scaling_efficiency(layers, 1, hw.ETH_10G) == 1.0


def test_trainer_hier_requires_factored_mesh(mesh11):
    from repro.configs import registry
    from repro.core.planner import Planner
    from repro.models.transformer import Model
    from repro.optim import optimizers as opt_lib
    from repro.train import trainer as tr

    cfg = registry.get_smoke_config("yi-6b")
    with pytest.raises(AssertionError, match="node"):
        tr.make_train_step(Model(cfg), opt_lib.adamw(1e-3), mesh11,
                           Planner(mesh=mesh11),
                           tr.CommConfig(mode="mlsl", hier=True))


# --------------------------------------------------------------------------
# the JAX sharding behaviour the data path relies on
# --------------------------------------------------------------------------

def test_mesh_helpers_make_auto_axes():
    """jax.make_mesh defaults to Explicit axes; the launch helpers build
    every mesh Auto, which the GSPMD baseline and the partial-manual
    regions below need."""
    from jax.sharding import AxisType

    from repro.launch import mesh as mesh_lib
    for m, shape in ((mesh_lib.make_host_mesh(), {"data": 1, "model": 1}),
                     (mesh_lib.make_hier_mesh(2, 4),
                      {"node": 2, "local": 4}),
                     (mesh_lib.make_hier_mesh(2, 2, 2),
                      {"node": 2, "local": 2, "model": 2})):
        assert dict(m.shape) == shape
        assert m.axis_types == (AxisType.Auto,) * len(shape)


def test_abstract_mesh_shape_and_names(abstract_pod):
    assert dict(abstract_pod.shape) == {"data": 16, "model": 16}
    assert tuple(abstract_pod.axis_names) == ("data", "model")


def test_shard_map_fully_manual_default(mesh8):
    x = jnp.arange(8.0)
    y = jax.jit(jax.shard_map(
        lambda u: lax.psum(u, (hier.NODE_AXIS, hier.LOCAL_AXIS)),
        mesh=mesh8, in_specs=DSPEC, out_specs=P(), check_vma=False))(x)
    np.testing.assert_allclose(np.asarray(y), [28.0])


def test_shard_map_partial_manual_keeps_model_axis_auto():
    """axis_names is the manual set: the model axis stays GSPMD while
    node/local are manual (the trainer's mlsl region under a model axis)."""
    from repro.launch import mesh as mesh_lib
    mesh = mesh_lib.make_hier_mesh(2, 2, 2)
    x = jnp.arange(8.0)
    y = jax.jit(jax.shard_map(
        lambda u: lax.psum(u, ("node", "local")),
        mesh=mesh, in_specs=P(("node", "local")), out_specs=P(),
        axis_names={"node", "local"}, check_vma=False))(x)
    np.testing.assert_allclose(np.asarray(y), [12.0, 16.0])


def test_axis_size_in_manual_region(mesh8):
    sizes = jax.jit(jax.shard_map(
        lambda: (jnp.asarray(cl.axis_size(hier.NODE_AXIS), jnp.int32),
                 jnp.asarray(cl.axis_size((hier.NODE_AXIS,
                                           hier.LOCAL_AXIS)), jnp.int32)),
        mesh=mesh8, in_specs=(), out_specs=(P(), P()), check_vma=False))()
    assert int(sizes[0]) == 2 and int(sizes[1]) == 8
