"""Multi-rank semantics, exercised in subprocesses with 8 fake host devices
(so the main pytest process keeps the normal 1-device view)."""

import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, n_dev: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_wire_formats_match_psum_across_8_ranks():
    _run(r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import collectives as cl
mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
x = jax.random.normal(jax.random.PRNGKey(0), (8, 4096)) * 1e-3
def f(wire):
    def inner(u):
        return cl.allreduce(u[0], ("data",), wire=wire)
    return jax.jit(jax.shard_map(inner, mesh=mesh, in_specs=P("data"),
                         out_specs=P(), axis_names={"data"},
                         check_vma=False))(x)
ref = np.asarray(jnp.sum(x, 0))
for wire, tol in (("fp32", 1e-6), ("bf16", 3e-2), ("int8", 2e-2)):
    got = np.asarray(f(wire))
    err = np.max(np.abs(got - ref)) / np.max(np.abs(ref))
    assert err < tol, (wire, err)
print("ok")
""")


def test_mlsl_8rank_training_matches_gspmd():
    _run(r"""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import registry
from repro.core.planner import Planner
from repro.data import pipeline
from repro.models.transformer import Batch, Model
from repro.optim import optimizers as opt_lib
from repro.train import trainer as tr
mesh = jax.make_mesh((4, 2), ("data", "model"),
                        axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = registry.get_smoke_config("yi-6b")
model = Model(cfg); opt = opt_lib.adamw(3e-3)
planner = Planner(mesh=mesh)
dcfg = pipeline.DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=8)
results = {}
for mode in ("gspmd", "mlsl"):
    comm = tr.CommConfig(mode=mode)
    with jax.set_mesh(mesh):
        state = tr.make_train_state(model, opt, jax.random.PRNGKey(0))
        step = jax.jit(tr.make_train_step(model, opt, mesh, planner, comm))
        for raw in pipeline.iterate(dcfg, 3):
            batch = Batch(tokens=jnp.asarray(raw["tokens"]),
                          labels=jnp.asarray(raw["labels"]))
            state, m = step(state, batch)
    results[mode] = (float(m["loss"]), state.params)
assert abs(results["gspmd"][0] - results["mlsl"][0]) < 1e-4, results
# identical math, different reduction order: mean-of-shard-means vs global
# mean; Adam's normalizer amplifies the fp noise, so tolerances are loose
jax.tree_util.tree_map(
    lambda a, b: np.testing.assert_allclose(np.asarray(a, np.float32),
                                            np.asarray(b, np.float32),
                                            rtol=1e-2, atol=5e-4),
    results["gspmd"][1], results["mlsl"][1])
print("ok")
""")


def test_ep_moe_matches_gather_moe_8ranks():
    _run(r"""
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.configs.base import MoEConfig
from repro.models import moe as moe_lib
mesh = jax.make_mesh((2, 4), ("data", "model"),
                        axis_types=(jax.sharding.AxisType.Auto,) * 2)
d, E = 16, 8
cfg = MoEConfig(n_experts=E, top_k=2, d_ff=32, capacity_factor=8.0)
key = jax.random.PRNGKey(0)
p = {"router": jax.random.normal(key, (d, E)),
     "w1": jax.random.normal(jax.random.fold_in(key, 1), (E, d, 32)) * .1,
     "w2": jax.random.normal(jax.random.fold_in(key, 2), (E, 32, d)) * .1,
     "w3": jax.random.normal(jax.random.fold_in(key, 3), (E, d, 32)) * .1}
x = jax.random.normal(jax.random.fold_in(key, 4), (4, 8, d)) * .5
with jax.set_mesh(mesh):
    y_ref, aux_ref = jax.jit(lambda p, x: moe_lib.moe_apply(p, x, cfg))(p, x)
    y_ep, aux_ep = jax.jit(lambda p, x: moe_lib.moe_apply_ep(
        p, x, cfg, act="silu", mesh=mesh, batch_axes=("data",)))(p, x)
np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref), rtol=2e-3,
                           atol=2e-4)
print("ok")
""")


def test_ep_int8_wgather_grads_flow():
    """Quantized ZeRO weight gathers must pass straight-through gradients
    (a plain grad-of-round would silently zero the expert updates)."""
    _run(r'''
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import MoEConfig
from repro.models import moe as moe_lib
mesh = jax.make_mesh((2, 4), ("data", "model"),
                        axis_types=(jax.sharding.AxisType.Auto,) * 2)
d, E = 16, 8
cfg = MoEConfig(n_experts=E, top_k=2, d_ff=32, capacity_factor=8.0)
key = jax.random.PRNGKey(0)
p = {"router": jax.random.normal(key, (d, E)),
     "w1": jax.random.normal(jax.random.fold_in(key, 1), (E, d, 32)) * .1,
     "w2": jax.random.normal(jax.random.fold_in(key, 2), (E, 32, d)) * .1,
     "w3": jax.random.normal(jax.random.fold_in(key, 3), (E, d, 32)) * .1}
x = jax.random.normal(jax.random.fold_in(key, 4), (4, 8, d)) * .5
def loss(p, x, wire):
    y, aux = moe_lib.moe_apply_ep(p, x, cfg, act="silu", mesh=mesh,
                                  batch_axes=("data",), fsdp_axes=("data",),
                                  wgather_wire=wire)
    return jnp.mean(y.astype(jnp.float32) ** 2)
with jax.set_mesh(mesh):
    g_ref = jax.jit(jax.grad(loss), static_argnums=2)(p, x, "bf16")
    g_q = jax.jit(jax.grad(loss), static_argnums=2)(p, x, "int8")
for k in ("w1", "w2", "w3"):
    assert float(jnp.max(jnp.abs(g_q[k]))) > 0, k
    err = float(jnp.max(jnp.abs(g_q[k] - g_ref[k])))
    ref = float(jnp.max(jnp.abs(g_ref[k]))) + 1e-9
    assert err / ref < 0.1, (k, err / ref)
print("ok")
''')


@pytest.mark.slow
def test_dryrun_one_combo_subprocess():
    """launch/dryrun end to end on the 512-device production mesh."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "mamba2-2.7b",
         "--shape", "long_500k", "--out", "/tmp/dryrun_test"],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "[ok]" in out.stdout
