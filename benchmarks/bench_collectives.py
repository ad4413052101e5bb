"""Collectives-API microbenchmark (the paper's lower-level interface, C7).

Times the MLSL-style collectives data path end to end on the local device
(allreduce in each wire precision, including the fuse/quantize/unfuse work
that would wrap the wire ops on TPU), and emits the MODELED mesh-scale time
for each wire format on the production pod (derived column) -- the analog of
an OSU-style latency/bandwidth table for the library.

With ``--hier`` (run as a script, so the XLA flag below lands before jax is
imported) the sweep runs on 8 virtual host devices: flat vs hierarchical
allreduce on a ("node"=2, "local"=4) mesh -- wall time of each
decomposition, per-element wire bytes by level (the fabric-byte saving is
the paper's scale-out headline), and the per-level cost model's flat/hier
choice across message sizes on the canonical topologies. If jax was already
imported with fewer devices (e.g. via benchmarks/run.py), the sweep emits a
"skipped" line instead.
"""

from __future__ import annotations

import os
import sys

if __name__ == "__main__" \
        and any(f in sys.argv for f in ("--hier", "--hybrid")) \
        and "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    # must be set before jax import (SNIPPETS.md idiom)
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                               + os.environ.get("XLA_FLAGS", ""))

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from benchmarks import common
from benchmarks.common import emit, time_fn
from repro.configs import registry
from repro.core import c2c, collectives, hier, hw, planner
from repro.launch import mesh as mesh_lib


def run():
    mesh = mesh_lib.make_host_mesh()

    for n in (1 << 16, 1 << 21):
        x = jax.random.normal(jax.random.PRNGKey(0), (n,), jnp.float32)
        for wire in collectives.WIRES:
            fn = jax.jit(lambda v, wire=wire: jax.shard_map(
                lambda u: collectives.allreduce(u, ("data",), wire=wire),
                mesh=mesh, in_specs=P(), out_specs=P(),
                axis_names={"data"}, check_vma=False)(v))
            us = time_fn(fn, x)
            nbytes = n * collectives.wire_bytes_per_elem(wire)
            t_pod = hw.ring_allreduce_time(nbytes, 16, hw.ICI_LINK)
            emit(f"collectives/allreduce/{wire}/n{n}", us,
                 f"modeled_pod_ring_ms={t_pod*1e3:.3f};"
                 f"wire_bytes={nbytes:.0f}")

    # reduce_scatter / all_gather path (the int8 composition's two legs)
    x = jax.random.normal(jax.random.PRNGKey(1), (1 << 18,), jnp.float32)
    for name, fn_ in (
        ("reduce_scatter",
         lambda u: collectives.reduce_scatter(u, ("data",))),
        ("all_gather", lambda u: collectives.all_gather(u, ("data",))),
    ):
        f = jax.jit(lambda v, fn_=fn_: jax.shard_map(
            fn_, mesh=mesh, in_specs=P(), out_specs=P(),
            axis_names={"data"}, check_vma=False)(v))
        us = time_fn(f, x)
        emit(f"collectives/{name}/n{1 << 18}", us, "local_1rank_path")

    # executed-hybrid comm model: the C2C chooser's plan for the canonical
    # smoke transformer on a (node=2, local=4) mesh, costed against pure
    # (flat) DP on each topology. Pure analysis -- device-independent, hence
    # a STABLE ledger metric the perf gate can fail on.
    cfg = registry.get_smoke_config("yi-6b")
    batch, seq = 8, 64
    amesh = jax.sharding.AbstractMesh((2, 4),
                                      (hier.NODE_AXIS, hier.LOCAL_AXIS))
    plan = planner.plan_hybrid(cfg, amesh, batch=batch, seq=seq)
    specs = c2c.layers_from_model_config(cfg, seq)
    for topo in (hw.CLOUD_10G, hw.HPC_OPA):
        cm = planner.model_hybrid_comm(plan, specs, batch=batch,
                                       nodes=plan.dp, topo=topo)
        # the acceptance bar: executed hybrid strictly beats pure DP
        assert cm.t_hybrid < cm.t_dp_flat, (topo.name, cm)
        emit(f"collectives/hybrid_model/{topo.name}", 0.0,
             f"exposed_dp_ms={cm.t_dp_flat*1e3:.3f};"
             f"exposed_dp_hier_ms={cm.t_dp_hier*1e3:.3f};"
             f"exposed_hybrid_ms={cm.t_hybrid*1e3:.3f};"
             f"reduction_vs_dp_x={cm.reduction_vs_flat:.2f};"
             f"model_layers={len(plan.model_layer_names)}")


def run_hier():
    """Flat vs hierarchical sweep on a ("node"=2, "local"=4) factored mesh."""
    n_dev = jax.device_count()
    if n_dev < 8:
        emit("collectives/hier/skipped", 0.0,
             f"needs 8 virtual devices, have {n_dev}")
        return
    node, local = 2, 4
    mesh = jax.make_mesh((node, local), (hier.NODE_AXIS, hier.LOCAL_AXIS))
    dspec = P((hier.NODE_AXIS, hier.LOCAL_AXIS))

    configs = (
        ("flat/fp32", None, collectives.WIRE_FP32),
        ("flat/int8", None, collectives.WIRE_INT8),
        ("hier/fp32-fp32", hier.HierSpec(), None),
        ("hier/bf16-int8",
         hier.HierSpec(wire_intra=collectives.WIRE_BF16,
                       wire_inter=collectives.WIRE_INT8), None),
    )
    for n in (1 << 16, 1 << 21):
        x = jax.random.normal(jax.random.PRNGKey(0),
                              (node * local, n), jnp.float32)
        for name, spec, wire in configs:
            if spec is None:
                inner = lambda u, w=wire: collectives.allreduce(  # noqa: E731
                    u[0], (hier.NODE_AXIS, hier.LOCAL_AXIS), wire=w)
                wb = hier.flat_wire_bytes_per_elem(wire)
            else:
                inner = lambda u, s=spec: hier.hier_allreduce(  # noqa: E731
                    u[0], s)
                wb = hier.hier_wire_bytes_per_elem(spec, local, node)
            fn = jax.jit(jax.shard_map(inner, mesh=mesh, in_specs=dspec,
                                       out_specs=P(), check_vma=False))
            us = time_fn(fn, x)
            emit(f"collectives/hier_sweep/{name}/n{n}", us,
                 f"wire_B_per_elem_total={wb.total:.3f};"
                 f"intra={wb.intra:.3f};inter={wb.inter:.3f}")

    # the per-level cost model's choice across message sizes
    for topo in (hw.CLOUD_10G, hw.HPC_OPA):
        for nbytes in (4e3, 4e5, 4e7):
            algo = planner.choose_allreduce_algo(nbytes, nodes=16, topo=topo)
            t_flat = hw.flat_allreduce_time(nbytes, 16, topo)
            t_hier = hw.hier_allreduce_time(nbytes, 16, topo)
            emit(f"collectives/hier_choice/{topo.name}/b{int(nbytes)}",
                 0.0, f"algo={algo};flat_ms={t_flat*1e3:.3f};"
                 f"hier_ms={t_hier*1e3:.3f}")


def run_hybrid():
    """Measured hybrid vs pure-DP train steps on the ("node"=2, "local"=4)
    mesh: the chooser's model-parallel layers execute tensor-parallel over
    "local" while pure DP replicates everything. Wall-clock, so the metrics
    are unstable; the gate-able modeled comparison lives in run()."""
    n_dev = jax.device_count()
    if n_dev < 8:
        emit("collectives/hybrid/skipped", 0.0,
             f"needs 8 virtual devices, have {n_dev}")
        return
    from repro.data import pipeline
    from repro.models.transformer import Batch, Model
    from repro.optim import optimizers as opt_lib
    from repro.train import trainer as tr

    cfg = registry.get_smoke_config("yi-6b")
    batch, seq = 8, 32
    mesh = mesh_lib.make_hier_mesh(2, 4)
    model = Model(cfg)
    optimizer = opt_lib.make_optimizer("adamw", 1e-3)
    dcfg = pipeline.DataConfig(vocab=cfg.vocab, seq_len=seq,
                               global_batch=batch, seed=0)
    raw = next(iter(pipeline.iterate(dcfg, 1)))
    b = Batch(tokens=jnp.asarray(raw["tokens"]),
              labels=jnp.asarray(raw["labels"]))
    results = {}
    with jax.set_mesh(mesh):
        for name, plnr in (
            ("dp", planner.Planner(mesh=mesh)),
            ("hybrid", planner.make_hybrid_planner(mesh, cfg, batch=batch,
                                                   seq=seq)),
        ):
            comm = tr.CommConfig(mode="mlsl", hier=True)
            state = tr.make_train_state(model, optimizer,
                                        jax.random.PRNGKey(0))
            step = jax.jit(tr.make_train_step(model, optimizer, mesh, plnr,
                                              comm))
            us = time_fn(step, state, b, iters=3, warmup=1)
            results[name] = us
            emit(f"collectives/hybrid_step/{name}", us,
                 f"step_us={us:.0f}us", stable=False)
    emit("collectives/hybrid_step/ratio", 0.0,
         f"dp_over_hybrid={results['dp'] / max(results['hybrid'], 1e-9):.2f}x",
         stable=False)


def main():
    if "--hier" in sys.argv:
        # distinct artifact: the 8-virtual-device sweep measures a different
        # thing than the single-device run() and must not clobber its ledger
        common.run_with_ledger("bench_collectives_hier", run_hier)
    elif "--hybrid" in sys.argv:
        common.run_with_ledger("bench_collectives_hybrid", run_hybrid)
    else:
        common.run_with_ledger("bench_collectives", run)


if __name__ == "__main__":
    main()
