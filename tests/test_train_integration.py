"""End-to-end training: losses decrease, comm modes agree numerically."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core.planner import Planner
from repro.data import pipeline
from repro.launch import mesh as mesh_lib
from repro.models.transformer import Batch, Model
from repro.optim import optimizers as opt_lib
from repro.train import trainer as tr


@pytest.fixture(scope="module")
def mesh():
    return mesh_lib.make_host_mesh()


def _train(mesh, comm, steps=25, arch="yi-6b", seed=0):
    cfg = registry.get_smoke_config(arch)
    model = Model(cfg)
    opt = opt_lib.adamw(3e-3)
    planner = Planner(mesh=mesh)
    dcfg = pipeline.DataConfig(vocab=cfg.vocab, seq_len=48, global_batch=4,
                               seed=seed)
    engine = (tr.make_comm_engine(model, mesh, planner, comm)
              if comm.mode == "mlsl" else None)
    with jax.set_mesh(mesh):
        state = tr.make_train_state(model, opt, jax.random.PRNGKey(seed),
                                    engine=engine)
        step = jax.jit(tr.make_train_step(model, opt, mesh, planner, comm,
                                          engine=engine))
        losses = []
        for raw in pipeline.iterate(dcfg, steps):
            batch = Batch(tokens=jnp.asarray(raw["tokens"]),
                          labels=jnp.asarray(raw["labels"]))
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
    return losses, state


def test_loss_decreases_gspmd(mesh):
    losses, _ = _train(mesh, tr.CommConfig(mode="gspmd"))
    assert losses[-1] < losses[0] - 0.3, losses


def test_mlsl_fp32_matches_gspmd_exactly(mesh):
    """With an fp32 wire and one rank, the MLSL data path must be numerically
    identical to the GSPMD baseline."""
    l1, s1 = _train(mesh, tr.CommConfig(mode="gspmd", prioritize=True),
                    steps=5)
    l2, s2 = _train(mesh, tr.CommConfig(mode="mlsl", wire="fp32",
                                        prioritize=True), steps=5)
    np.testing.assert_allclose(l1, l2, rtol=1e-6)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=2e-5,
            atol=1e-6),
        s1.params, s2.params)


@pytest.mark.parametrize("wire,ef", [("bf16", False), ("int8", False),
                                     ("int8", True)])
def test_low_precision_wires_still_train(mesh, wire, ef):
    losses, _ = _train(mesh, tr.CommConfig(mode="mlsl", wire=wire,
                                           error_feedback=ef))
    assert losses[-1] < losses[0] - 0.3, (wire, ef, losses)


def test_prioritization_changes_schedule_not_math(mesh):
    l1, s1 = _train(mesh, tr.CommConfig(mode="mlsl", prioritize=True),
                    steps=4)
    l2, s2 = _train(mesh, tr.CommConfig(mode="mlsl", prioritize=False),
                    steps=4)
    np.testing.assert_allclose(l1, l2, rtol=1e-6)


def test_moe_arch_trains(mesh):
    losses, _ = _train(mesh, tr.CommConfig(), arch="arctic-480b", steps=15)
    assert losses[-1] < losses[0] - 0.15, losses


def test_ssm_arch_trains(mesh):
    # the SSD mixer has a slow first ~20 steps on this toolchain (flat loss,
    # then steady descent); 40 steps clears the threshold with margin
    losses, _ = _train(mesh, tr.CommConfig(), arch="mamba2-2.7b", steps=40)
    assert losses[-1] < losses[0] - 0.15, losses
