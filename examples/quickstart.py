"""Quickstart: build a model from the registry, train it with the MLSL comm
stack, and decode from it -- in under a minute on CPU.

  PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.core.api import Session
from repro.data import pipeline
from repro.launch import mesh as mesh_lib
from repro.models.transformer import Batch, Model
from repro.optim import optimizers as opt_lib
from repro.serve.engine import Engine, EngineConfig
from repro.train import trainer as tr


def main():
    # 1. any assigned architecture, reduced to laptop scale
    cfg = registry.get_smoke_config("yi-6b")
    model = Model(cfg)
    print(f"model: {cfg.name}  params: {model.n_params():,}")

    # 2. a Session = mesh + planner + MLSL comm config (paper C7)
    mesh = mesh_lib.make_host_mesh()
    sess = Session.create(
        mesh, n_params=model.n_params(),
        comm=tr.CommConfig(mode="mlsl", wire="bf16", prioritize=True))
    print(f"wire saving vs fp32: {sess.wire_savings():.1f}x")

    # 3. train
    opt = opt_lib.adamw(3e-3)
    data = pipeline.DataConfig(vocab=cfg.vocab, seq_len=64, global_batch=8)
    with jax.set_mesh(mesh):
        state = tr.make_train_state(model, opt, jax.random.PRNGKey(0))
        step = jax.jit(sess.make_train_step(model, opt))
        for i, raw in enumerate(pipeline.iterate(data, 40)):
            batch = Batch(tokens=jnp.asarray(raw["tokens"]),
                          labels=jnp.asarray(raw["labels"]))
            state, m = step(state, batch)
            if i % 10 == 0:
                print(f"step {i:3d}  loss {float(m['loss']):.4f}")

    # 4. serve
    eng = Engine(model, state.params, EngineConfig(max_seq=96))
    prompt = np.asarray(pipeline.batch_at(data, 999)["tokens"][:2, :16])
    out = eng.generate(prompt, 8)
    print("generated:", out.tolist())


if __name__ == "__main__":
    main()
