"""Training driver.

Trains on JAX's devices (TPU chips, or CPU devices for tests and CI), with
the MLSL comm stack selectable from the CLI:

  PYTHONPATH=src python -m repro.launch.train --arch yi-6b --smoke \
      --steps 50 --comm mlsl --wire int8 --batch 8 --seq 64

--smoke uses the reduced config of the same family; full configs are for
real hardware (the dry-run covers them at mesh scale). `run` does the CLI's
work for any ModelConfig (chip_smoke.py calls it at published widths).
"""

from __future__ import annotations

import argparse
import dataclasses
import itertools
import os
import pathlib
import time
from typing import Any

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import ckpt
from repro.configs import registry
from repro.configs.base import ModelConfig
from repro.core import planner as pl
from repro.data import pipeline
from repro.launch import mesh as mesh_lib
from repro.models.transformer import Batch, Model
from repro.optim import optimizers as opt_lib, schedules
from repro.train import trainer as tr


# the persistent compile cache's home when JAX_COMPILATION_CACHE_DIR is not
# set: fixed, because the path is part of what a later run looks up
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Keep compiled programs across runs: in $JAX_COMPILATION_CACHE_DIR
    when it is set (JAX reads it itself), otherwise in CACHE_DIR inside the
    checkout. Call at start-up, before the first compile."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=registry.ARCH_IDS, default="yi-6b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--no-smoke", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--optimizer", default="adamw",
                    choices=sorted(opt_lib.OPTIMIZERS))
    ap.add_argument("--comm", default="gspmd", choices=["gspmd", "mlsl"])
    ap.add_argument("--wire", default="fp32", choices=["fp32", "bf16", "int8"])
    ap.add_argument("--error-feedback", action="store_true")
    ap.add_argument("--no-prioritize", action="store_true")
    ap.add_argument("--data-parallel", type=int, default=1)
    ap.add_argument("--model-parallel", type=int, default=1)
    # two-level collectives over a ("node", "local") factored mesh; needs
    # node*local devices (or XLA_FLAGS=--xla_force_host_platform_device_count)
    ap.add_argument("--hier", action="store_true")
    # execute the C2C chooser's hybrid plan: tensor parallelism over the
    # "local" mesh axis for the layers the chooser sends model-parallel,
    # data parallelism across "node" (implies the hier mesh; needs --comm
    # mlsl)
    ap.add_argument("--hybrid", action="store_true")
    ap.add_argument("--nodes", type=int, default=2)
    ap.add_argument("--local", type=int, default=4)
    ap.add_argument("--wire-intra", default=None,
                    choices=[None, "fp32", "bf16"])
    # name a machine hierarchy (repro.core.hw.TOPOLOGIES) to let the
    # per-level cost model route each bucket flat vs two-level
    ap.add_argument("--topo", default=None)
    # MLSL-style compute/communication overlap: with --microbatches N > 1
    # the engine reduces microbatch k's buckets interleaved with microbatch
    # k+1's forward/backward (requires --comm mlsl)
    ap.add_argument("--overlap", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="gradient-accumulation microbatches per step")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    # observability (repro.obs): --stats prints the MLSL-style per-bucket
    # CommStats table + step meter and writes them into the perf ledger
    # (BENCH_comm_stats.json in $BENCH_DIR); it blocks on every step's
    # result to time it (small overhead). --trace DIR takes a jax.profiler
    # trace of the training loop into DIR: the device ops under the step's
    # layer scopes (model, optim/, comm/) and the host spans (train steps,
    # data/batch) on one clock, for TensorBoard's profiler or Perfetto.
    ap.add_argument("--stats", action="store_true")
    ap.add_argument("--trace", default=None, metavar="DIR")
    # streaming telemetry + online health monitor (repro.obs.telemetry /
    # repro.obs.detect): --telemetry DIR leaves a schema-versioned JSONL
    # (DIR/telemetry.jsonl — step time, tok/s, modeled exposed-comm share,
    # sampled per-bucket reduce times) and watches the run for sustained
    # measured-vs-modeled drift (straggler / link_degraded /
    # step_time_drift alarms, also surfaced in the post-run table). The
    # per-bucket replay runs BETWEEN steps every --telemetry-sample steps
    # (default 25, 0 disables it), so the hot step path is never perturbed
    # beyond the same per-step blocking --stats already does.
    ap.add_argument("--telemetry", default=None, metavar="DIR")
    ap.add_argument("--telemetry-sample", type=int, default=None,
                    metavar="N",
                    help="bucket-replay sampling period in steps for "
                         "--telemetry (default 25; 0 disables the replay)")
    return ap


@dataclasses.dataclass
class RunResult:
    state: Any                # the final TrainState (the step donates it)
    history: list             # (step, loss, grad_norm, t) at logged steps;
                              # t = host clock once that step's result is in
    compiled: Any             # the step's compiled executable
    compile_s: float          # lower + compile of the step (cache included)
    n_compiles: int           # executables the jitted step holds (1)


def main():
    args = build_parser().parse_args()
    enable_compile_cache()
    cfg = (registry.get_smoke_config(args.arch) if args.smoke
           else registry.get_config(args.arch))
    res = run(cfg, args)
    if args.ckpt_dir:
        ckpt.save(args.ckpt_dir, {"params": res.state.params},
                  step=args.steps)
        print(f"checkpoint -> {args.ckpt_dir}")
    return 0


def run(cfg: ModelConfig, args: argparse.Namespace) -> RunResult:
    """Set up mesh, planner, comm engine, state and data for `cfg` as the
    CLI `args` say, then train `args.steps` steps."""
    model = Model(cfg)
    if args.hybrid:
        if args.comm != "mlsl":
            raise SystemExit("--hybrid needs --comm mlsl (the activation "
                             "f/g collectives run in the explicit data path)")
        mesh = mesh_lib.make_hier_mesh(args.nodes, args.local)
        planner = pl.make_hybrid_planner(mesh, cfg, batch=args.batch,
                                         seq=args.seq)
        for lp in planner.hybrid.layers:
            note = f" [{lp.reason}]" if lp.reason else ""
            print(f"plan {lp.name:12s} {lp.kind:6s} "
                  f"chooser={lp.choice.strategy.value}"
                  f"(g={lp.choice.group_size}) "
                  f"executed={lp.executed}{note}")
    elif args.hier:
        mesh = mesh_lib.make_hier_mesh(args.nodes, args.local,
                                       args.model_parallel)
        planner = pl.Planner(mesh=mesh)
    else:
        mesh = mesh_lib.make_host_mesh(args.data_parallel,
                                       args.model_parallel)
        planner = pl.Planner(mesh=mesh)
    lr = schedules.warmup_cosine(args.lr, max(args.steps // 10, 1), args.steps)
    optimizer = opt_lib.make_optimizer(args.optimizer, lr)
    comm = tr.CommConfig(mode=args.comm, wire=args.wire,
                         prioritize=not args.no_prioritize,
                         error_feedback=args.error_feedback,
                         hier=args.hier or args.hybrid,
                         wire_intra=args.wire_intra,
                         topo=args.topo, accum_steps=args.microbatches,
                         overlap=args.overlap)
    dcfg = pipeline.DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                               global_batch=args.batch, seed=args.seed)
    engine = (tr.make_comm_engine(model, mesh, planner, comm)
              if args.comm == "mlsl" else None)

    meter = None
    if args.stats or args.telemetry:
        from repro.obs import meter as obs_meter
        meter = obs_meter.StepMeter(tokens_per_step=args.batch * args.seq)

    telem = monitor = timer = None
    t_model_tel: list = []
    n_micro = max(args.microbatches, 1)
    if args.telemetry:
        from repro.core import simulator as sim_lib
        from repro.obs import detect as obs_detect
        from repro.obs import telemetry as obs_telemetry
        os.makedirs(args.telemetry, exist_ok=True)
        sample_every = (obs_telemetry.DEFAULT_SAMPLE_EVERY
                        if args.telemetry_sample is None
                        else args.telemetry_sample)
        telem = obs_telemetry.TelemetryWriter(
            os.path.join(args.telemetry, "telemetry.jsonl"),
            run_info={"source": "train", "arch": cfg.name,
                      "comm": args.comm, "wire": args.wire,
                      "mesh": dict(mesh.shape), "batch": args.batch,
                      "seq": args.seq, "steps": args.steps},
            sample_every=sample_every)
        # live detection runs on the de-tuned wall-clock preset: CPU step
        # times jitter far more than the simulator's episodes
        wcfg = obs_detect.DetectorConfig.wallclock()
        if engine is not None:
            monitor = obs_detect.HealthMonitor.from_plan(engine.plan,
                                                         config=wcfg)
            t_model_tel = list(monitor.t_model)
        else:
            # gspmd's reductions are partitioner-inserted, not bucket
            # messages: only the generic step_time_drift alarm is reachable
            monitor = obs_detect.HealthMonitor(config=wcfg)

    batch_sh = tr.batch_shardings(planner, model, args.batch)

    def make_batch(raw):
        kw = {}
        if cfg.vlm_img_tokens:
            kw["img_embeds"] = np.zeros(
                (args.batch, cfg.vlm_img_tokens, cfg.vlm_d_vision),
                np.float32)
        if cfg.encoder is not None:
            kw["frame_embeds"] = np.zeros(
                (args.batch, cfg.encoder.n_frames, cfg.encoder.d_input),
                np.float32)
        return jax.device_put(Batch(tokens=raw["tokens"],
                                    labels=raw["labels"], **kw), batch_sh)

    dev = jax.devices()[0]
    # the state is built in place in the layout the step keeps it in, and
    # donated to the step: one copy of it on the devices, and one compile
    state_sh = tr.state_shardings(planner, model, optimizer, engine)
    with jax.set_mesh(mesh):
        state = jax.jit(
            lambda key: tr.make_train_state(model, optimizer, key,
                                            engine=engine),
            out_shardings=state_sh)(jax.random.PRNGKey(args.seed))
        step_fn = jax.jit(
            tr.make_train_step(model, optimizer, mesh, planner, comm,
                               engine=engine),
            out_shardings=(state_sh, NamedSharding(mesh, P())),
            donate_argnums=0)
        print(f"arch={cfg.name} params={model.n_params():,} comm={args.comm}"
              f"/{args.wire} mesh={dict(mesh.shape)} devices={dev.platform}:"
              f"{dev.device_kind}x{jax.device_count()}")
        batches = (make_batch(raw)
                   for raw in pipeline.iterate(dcfg, args.steps))
        first = next(batches)
        t0 = time.perf_counter()
        compiled = step_fn.lower(state, first).compile()
        compile_s = time.perf_counter() - t0
        print(f"compiled step in {compile_s:.1f}s", flush=True)
        history = []
        if args.trace:
            jax.profiler.start_trace(args.trace)
        t0 = time.perf_counter()
        for s, batch in enumerate(itertools.chain([first], batches)):
            if meter is not None:
                # metering blocks on each step's result (async dispatch would
                # attribute step k's time to k+1)
                meter.start()
                state, metrics = _step(step_fn, state, batch, s)
                jax.block_until_ready(metrics)
                meter.update(loss=float(metrics["loss"]),
                             grad_norm=float(metrics["grad_norm"]))
                if t_model_tel:
                    # modeled exposed-comm share at the CURRENT measured
                    # compute scale (pure host math, a few buckets)
                    meter.exposed_comm_model = \
                        sim_lib.simulate_bucket_schedule(
                            t_model_tel, n_micro,
                            meter.step_time / n_micro,
                            overlap=comm.overlap).exposed_comm
                exposed = meter.exposed_comm_frac
                if telem is not None:
                    telem.step(step=s, t_step_s=meter.last_dt,
                               tok_s=meter.tokens_per_sec,
                               loss=meter.last_loss, exposed_frac=exposed)
                    fired = monitor.observe_step(s, meter.last_dt,
                                                 exposed_frac=exposed)
                    if engine is not None and telem.should_sample(s):
                        # sampled standalone replay BETWEEN steps — the hot
                        # path never runs it; first sample pays the compile
                        if timer is None:
                            timer = engine.bucket_timer(mesh)
                            sampled = timer.sample(warmup=1)
                        else:
                            sampled = timer.sample()
                        telem.bucket_times(s, sampled, modeled=t_model_tel)
                        fired += monitor.observe_bucket_times(s, sampled)
                    for a in fired:
                        telem.alarm(step=a.step, kind=a.kind,
                                    factor=a.factor, level=a.level,
                                    rank=a.rank, detail=a.detail)
            else:
                state, metrics = _step(step_fn, state, batch, s)
            if s % args.log_every == 0 or s == args.steps - 1:
                loss, gnorm = (float(metrics["loss"]),
                               float(metrics["grad_norm"]))
                t = time.perf_counter()
                history.append((s, loss, gnorm, t))
                moe = "".join(f" {k} {float(v):.6g}"
                              for k, v in sorted(metrics.items())
                              if k.startswith("moe/"))
                if meter is not None:
                    print(f"{meter.summary()}{moe} ({t - t0:.1f}s)",
                          flush=True)
                else:
                    print(f"step {s:5d} loss {loss:.4f} gnorm {gnorm:.3f}"
                          f"{moe} ({t - t0:.1f}s)", flush=True)
        if args.trace:
            jax.block_until_ready(state)
            jax.profiler.stop_trace()
            print(f"trace: {args.trace} (jax.profiler xplane)")
        if args.stats:
            _emit_observability(args, mesh, planner, comm, model, meter,
                                engine=engine)
        if telem is not None:
            telem.close()
            print(f"telemetry: {telem.path} ({telem.n_records} records)")
            _report_health(monitor)
    return RunResult(state=state, history=history, compiled=compiled,
                     compile_s=compile_s, n_compiles=step_fn._cache_size())


def _step(step_fn, state, batch, s: int):
    """One training step, marked in a profiler trace as step `s` of
    `train`."""
    with jax.profiler.StepTraceAnnotation("train", step_num=s):
        return step_fn(state, batch)


def _report_health(monitor) -> None:
    """Post-run alarm table for --telemetry (the operator's summary)."""
    if not monitor.alarms:
        print("health: no alarms")
        return
    print(f"health: {len(monitor.alarms)} alarm(s)")
    for a in monitor.alarms:
        print(f"  {a.describe()}")
        if monitor.bucket_bytes:
            print(f"    -> {monitor.reroute(a).summary()}")


def _emit_observability(args, mesh, planner, comm, model, meter,
                        engine=None):
    """Post-run stats (--stats).

    For the mlsl data path: replay each bucket's exchange standalone to get
    measured per-bucket service times, print the CommStats table and write
    the comm_stats entries into the perf ledger (BENCH_comm_stats.json —
    all informational/unstable, never gated).
    """
    from repro.core import simulator as sim
    from repro.obs import stats as obs_stats

    st = None
    if args.comm == "mlsl":
        if engine is None:
            engine = tr.make_comm_engine(model, mesh, planner, comm)
        measured = obs_stats.measure_bucket_times(engine, mesh, iters=2)
        st = engine.stats(measured=measured)
        # the modeled schedule for this config: per-bucket cost-model times
        # through the engine's own microbatch pipeline, at the measured
        # compute scale when a meter ran
        n_micro = max(comm.accum_steps, 1)
        micro_compute = (meter.step_time / n_micro
                         if meter is not None and meter.steps else 1e-3)
        modeled = sim.simulate_bucket_schedule(
            [b.t_model or 0.0 for b in st.buckets], n_micro, micro_compute,
            overlap=comm.overlap)
        if meter is not None:
            meter.exposed_comm_model = modeled.exposed_comm
        print(st.table())
    else:
        print("stats: per-bucket CommStats need --comm mlsl (gspmd's "
              "reductions are partitioner-inserted, not bucket messages)")
    if meter is not None and meter.steps:
        print(meter.summary())

    try:
        from benchmarks import common as bench_common
    except ImportError:
        bench_common = None     # repo root not on sys.path
    if bench_common is not None:
        led = bench_common.Ledger("comm_stats")
        for m in (st.to_metrics() if st is not None else []):
            led.record(**m)
        if meter is not None and meter.steps:
            for m in meter.to_metrics():
                led.record(**m)
        print(f"stats ledger: {led.write()}")


if __name__ == "__main__":
    raise SystemExit(main())
