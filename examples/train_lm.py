"""End-to-end training driver.

Default preset trains a ~2M-param llama-family model for 300 steps on CPU in
a few minutes and reports the loss curve + checkpoint. The `100m` preset is
the same driver at ~100M params (run it on real accelerators; on this CPU
container it is compile-checked but slow).

  PYTHONPATH=src python examples/train_lm.py [--preset tiny|100m]
      [--comm mlsl --wire int8 --error-feedback]
"""

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import ckpt
from repro.configs.base import AttnConfig, ModelConfig
from repro.core.planner import Planner
from repro.data import pipeline
from repro.launch import mesh as mesh_lib
from repro.models.transformer import Batch, Model
from repro.optim import optimizers as opt_lib, schedules
from repro.train import trainer as tr

PRESETS = {
    # ~2.4M params: minutes on CPU
    "tiny": dict(n_layers=4, d_model=128, n_heads=4, n_kv=2, d_ff=384,
                 vocab=2048, seq=128, batch=8, steps=300),
    # ~106M params: the assignment's "train ~100M for a few hundred steps"
    # target -- sized for a real device, compile-checked here
    "100m": dict(n_layers=12, d_model=768, n_heads=12, n_kv=4, d_ff=2048,
                 vocab=32000, seq=512, batch=32, steps=300),
}


def build_config(p) -> ModelConfig:
    return ModelConfig(
        name=f"lm-{p['d_model']}", arch_type="dense", n_layers=p["n_layers"],
        d_model=p["d_model"], vocab=p["vocab"], block_pattern=("attn",),
        d_ff=p["d_ff"],
        attn=AttnConfig(n_heads=p["n_heads"], n_kv=p["n_kv"],
                        head_dim=p["d_model"] // p["n_heads"]),
        dtype=jnp.float32, remat=False)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="tiny", choices=sorted(PRESETS))
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--comm", default="mlsl", choices=["gspmd", "mlsl"])
    ap.add_argument("--wire", default="fp32", choices=["fp32", "bf16", "int8"])
    ap.add_argument("--error-feedback", action="store_true")
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt", default="/tmp/repro_lm_ckpt")
    args = ap.parse_args()

    p = PRESETS[args.preset]
    steps = args.steps or p["steps"]
    cfg = build_config(p)
    model = Model(cfg)
    mesh = mesh_lib.make_host_mesh()
    planner = Planner(mesh=mesh)
    lr = schedules.warmup_cosine(3e-3, steps // 10, steps)
    opt = opt_lib.adamw(lr)
    comm = tr.CommConfig(mode=args.comm, wire=args.wire,
                         error_feedback=args.error_feedback,
                         accum_steps=args.accum)
    data = pipeline.DataConfig(vocab=cfg.vocab, seq_len=p["seq"],
                               global_batch=p["batch"])
    print(f"preset={args.preset} params={model.n_params():,} "
          f"comm={args.comm}/{args.wire} steps={steps}")
    engine = (tr.make_comm_engine(model, mesh, planner, comm)
              if comm.mode == "mlsl" else None)
    with jax.set_mesh(mesh):
        state = tr.make_train_state(model, opt, jax.random.PRNGKey(0),
                                    engine=engine)
        step = jax.jit(tr.make_train_step(model, opt, mesh, planner, comm,
                                          engine=engine))
        t0 = time.time()
        for i, raw in enumerate(pipeline.iterate(data, steps)):
            batch = Batch(tokens=jnp.asarray(raw["tokens"]),
                          labels=jnp.asarray(raw["labels"]))
            state, m = step(state, batch)
            if i % 25 == 0 or i == steps - 1:
                print(f"step {i:4d}  loss {float(m['loss']):.4f}  "
                      f"gnorm {float(m['grad_norm']):.2f}  "
                      f"{time.time()-t0:.0f}s", flush=True)
    ckpt.save(args.ckpt, {"params": state.params}, step=steps)
    print(f"saved checkpoint to {args.ckpt}")


if __name__ == "__main__":
    main()
