"""End-to-end behaviour: the public API pipeline (Session -> train -> save ->
restore -> serve) on a reduced model."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt
from repro.configs import registry
from repro.core.api import Session
from repro.data import pipeline
from repro.launch import mesh as mesh_lib
from repro.models.transformer import Batch, Model
from repro.optim import optimizers as opt_lib
from repro.serve.engine import Engine, EngineConfig
from repro.train import trainer as tr


def test_full_pipeline(tmp_path):
    mesh = mesh_lib.make_host_mesh()
    cfg = registry.get_smoke_config("yi-6b")
    model = Model(cfg)
    sess = Session.create(mesh, n_params=model.n_params(),
                          comm=tr.CommConfig(mode="mlsl", wire="bf16"))
    opt = opt_lib.adamw(3e-3)
    dcfg = pipeline.DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=4)
    with jax.set_mesh(mesh):
        state = tr.make_train_state(model, opt, jax.random.PRNGKey(0))
        step = jax.jit(sess.make_train_step(model, opt))
        first = last = None
        for raw in pipeline.iterate(dcfg, 20):
            b = Batch(tokens=jnp.asarray(raw["tokens"]),
                      labels=jnp.asarray(raw["labels"]))
            state, m = step(state, b)
            first = first if first is not None else float(m["loss"])
            last = float(m["loss"])
    assert last < first - 0.2

    d = ckpt.save(str(tmp_path / "ck"), {"params": state.params}, step=20)
    restored = ckpt.restore(d, {"params": state.params})["params"]
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32)),
        state.params, restored)

    eng = Engine(model, restored, EngineConfig(max_seq=48))
    out = eng.generate(np.zeros((2, 4), np.int32), 5)
    assert out.shape == (2, 5)
    assert sess.wire_savings() > 1.5     # bf16 wire halves the volume
