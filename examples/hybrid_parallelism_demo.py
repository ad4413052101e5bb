"""The paper's analysis, reproduced interactively — and then executed:

  1. per-layer C2C ratios for ResNet-50/VGG-16 and what the DL Layer API
     picks (data vs model vs hybrid node groups);
  2. the message-prioritization effect on exposed communication time;
  3. the C2C chooser's hybrid plan for a transformer, gated on what the
     mesh can actually execute, with the modeled exposed-comm win;
  4. real hybrid training steps on an 8-device (node=2, local=4) mesh:
     the chooser's model-parallel layers run tensor-parallel over "local"
     through shard_map while gradients reduce data-parallel over "node".

  PYTHONPATH=src python examples/hybrid_parallelism_demo.py
"""

import os

_FLAG = "--xla_force_host_platform_device_count=8"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " " + _FLAG).strip()

import jax
import jax.numpy as jnp

from repro.configs import cnn_tables, registry
from repro.core import c2c, hw, planner as pl, simulator as sim
from repro.data import pipeline
from repro.launch import mesh as mesh_lib
from repro.models.transformer import Batch, Model
from repro.optim import optimizers as opt_lib
from repro.train import trainer as tr


def main():
    print("=== 1. C2C ratios and strategy choice (64 nodes, batch 2048) ===")
    for topo in ("resnet50", "vgg16"):
        layers = cnn_tables.TOPOLOGIES[topo]()
        report = pl.plan_report(layers, batch=2048, p=64)
        interesting = [r for r in report
                       if r.choice.strategy != c2c.Strategy.DATA][:4]
        print(f"{topo}: {len(report)} layers, "
              f"{sum(r.choice.strategy == c2c.Strategy.DATA for r in report)}"
              f" data-parallel")
        for r in interesting:
            print(f"   {r.name:12s} {r.kind:5s} -> {r.choice.strategy.value}"
                  f" (group={r.choice.group_size},"
                  f" ratio={r.choice.ratio:.0f} flop/B)")

    print("\n=== 2. message prioritization (ResNet-50, 64 nodes, 10GbE) ===")
    layers = sim.layers_from_specs(cnn_tables.resnet50_layers(), 32,
                                   hw.XEON_6148)
    for pol in sim.Policy:
        st = sim.simulate_iteration(layers, 64, hw.ETH_10G, pol,
                                    overlap_eff=0.7)
        print(f"   {pol.value:9s} exposed={st.exposed_comm*1e3:7.1f}ms "
              f"total={st.total_time*1e3:7.1f}ms")

    print("\n=== 3. executed hybrid plan (yi-6b smoke, node=2 x local=4) ===")
    cfg = registry.get_smoke_config("yi-6b")
    batch, seq = 8, 64
    amesh = jax.sharding.AbstractMesh((2, 4), ("node", "local"))
    plan = pl.plan_hybrid(cfg, amesh, batch=batch, seq=seq)
    for lp in plan.layers:
        note = f" [{lp.reason}]" if lp.reason else ""
        print(f"   {lp.name:12s} {lp.kind:6s} "
              f"chooser={lp.choice.strategy.value}(g={lp.choice.group_size}) "
              f"executed={lp.executed}{note}")
    specs = c2c.layers_from_model_config(cfg, seq)
    cm = pl.model_hybrid_comm(plan, specs, batch=batch, nodes=plan.dp,
                              topo=hw.CLOUD_10G)
    print(f"   modeled exposed comm on {hw.CLOUD_10G.name}: "
          f"pure DP {cm.t_dp_flat*1e3:.2f}ms, "
          f"hybrid {cm.t_hybrid*1e3:.2f}ms "
          f"({cm.reduction_vs_flat:.1f}x less)")

    print("\n=== 4. hybrid training on the real 8-device mesh ===")
    if jax.device_count() < 8:
        print(f"   skipped: {jax.device_count()} devices "
              f"(run without XLA_FLAGS already set)")
        return
    mesh = mesh_lib.make_hier_mesh(2, 4)
    planner = pl.make_hybrid_planner(mesh, cfg, batch=batch, seq=seq)
    model = Model(cfg)
    defs = model.param_defs()
    pspecs = planner.tree_specs(defs, stacked_paths=Model.stacked_path)
    print(f"   wq   -> {pspecs['blocks']['p0_attn']['attn']['wq']}")
    print(f"   wo   -> {pspecs['blocks']['p0_attn']['attn']['wo']}")
    print(f"   embed-> {pspecs['embed']}")
    comm = tr.CommConfig(mode="mlsl", hier=True, topo=hw.CLOUD_10G.name)
    optimizer = opt_lib.make_optimizer("adamw", 3e-3)
    dcfg = pipeline.DataConfig(vocab=cfg.vocab, seq_len=seq,
                               global_batch=batch, seed=0)
    with jax.set_mesh(mesh):
        state = tr.make_train_state(model, optimizer, jax.random.PRNGKey(0))
        step_fn = jax.jit(tr.make_train_step(model, optimizer, mesh, planner,
                                             comm))
        for s, raw in enumerate(pipeline.iterate(dcfg, 3)):
            b = Batch(tokens=jnp.asarray(raw["tokens"]),
                      labels=jnp.asarray(raw["labels"]))
            state, metrics = step_fn(state, b)
            print(f"   step {s} loss {float(metrics['loss']):.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f}")


if __name__ == "__main__":
    main()
