"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) combination against the production mesh, with 512 placeholder host
devices standing in for the chips (no real allocation: all inputs are
ShapeDtypeStructs).

Per combination this records:
  * compile success (the deliverable: the distribution config is coherent),
  * compiled.memory_analysis()  -- proves the per-chip footprint fits,
  * compiled.cost_analysis()    -- FLOPs / bytes for the roofline,
  * parsed collective wire bytes (launch/roofline.py),
  * the roofline terms + dominant bottleneck.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
  ... [--comm gspmd|mlsl] [--wire fp32|bf16|int8] [--moe-impl gather|ep]
      [--out artifacts/dryrun]
"""

import argparse
import dataclasses
import json
import os
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import registry
from repro.configs.base import (ModelConfig, active_param_count_estimate,
                                param_count_estimate)
from repro.configs.shapes import SHAPES, InputShape
from repro.core.planner import Planner, make_planner
from repro.launch import mesh as mesh_lib
from repro.launch import roofline as rf
from repro.models import blocks as blocks_lib
from repro.models import common
from repro.models.transformer import Batch, Model
from repro.optim import optimizers as opt_lib
from repro.train import trainer as tr


# --------------------------------------------------------------------------
# input / state specs (ShapeDtypeStructs only -- nothing is allocated)
# --------------------------------------------------------------------------

def _sds(shape, dtype, mesh, spec):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def batch_specs(cfg: ModelConfig, shape: InputShape, mesh, planner: Planner,
                *, with_labels: bool) -> Batch:
    B = shape.global_batch
    S = shape.seq_len
    if cfg.vlm_img_tokens:
        S = S - cfg.vlm_img_tokens
    tok = planner.tokens_spec(B, extra_dims=1)
    emb = planner.tokens_spec(B, extra_dims=2)
    return Batch(
        tokens=_sds((B, S), jnp.int32, mesh, tok),
        labels=_sds((B, S), jnp.int32, mesh, tok) if with_labels else None,
        mask=None,
        img_embeds=_sds((B, cfg.vlm_img_tokens, cfg.vlm_d_vision), jnp.bfloat16,
                        mesh, emb) if cfg.vlm_img_tokens else None,
        frame_embeds=_sds((B, cfg.encoder.n_frames, cfg.encoder.d_input),
                          jnp.bfloat16, mesh, emb)
        if cfg.encoder is not None else None)


def param_shardings(model: Model, mesh, planner: Planner):
    return planner.tree_shardings(model.param_defs(),
                                  stacked_paths=Model.stacked_path)


def param_specs_sds(model: Model, mesh, planner: Planner):
    defs = model.param_defs()
    sh = param_shardings(model, mesh, planner)
    return common.abstract_tree(defs, sh)


def train_state_sds(model: Model, optimizer, mesh, planner: Planner):
    params = param_specs_sds(model, mesh, planner)
    opt_shape = jax.eval_shape(optimizer.init, params)
    # optimizer states mirror the parameter shardings
    p_leaves = jax.tree_util.tree_leaves(params)
    opt = jax.tree_util.tree_map(
        lambda s: None, opt_shape)
    opt = {}
    for name, sub in opt_shape.items():
        sub_leaves = jax.tree_util.tree_leaves(sub)
        td = jax.tree_util.tree_structure(sub)
        opt[name] = jax.tree_util.tree_unflatten(
            td, [jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=p.sharding)
                 for l, p in zip(sub_leaves, p_leaves)])
    step = _sds((), jnp.int32, mesh, P())
    return tr.TrainState(params=params, opt_state=opt, step=step,
                         comm_residuals=None)


def cache_spec_tree(cache_shapes, planner: Planner, batch: int, mesh):
    """Assign PartitionSpecs to a decode-cache tree by leaf name."""
    ms, mx = planner.model_size, planner.model_axis
    baxes = planner.batch_spec_axes(batch)
    lead = baxes if len(baxes) > 1 else (baxes[0] if baxes else None)

    def div(n):
        return ms > 1 and n % ms == 0

    def one(path, sds):
        keys = [str(p.key) for p in path if hasattr(p, "key")]
        stacked = "blocks" in keys
        off = 1 if stacked else 0
        name = keys[-1]
        dims = [None] * sds.ndim
        if sds.ndim > off:
            dims[off] = lead
        if name in ("k", "v", "k_s", "v_s"):       # (B, S, KV, hd|1)
            if div(sds.shape[off + 2]):
                dims[off + 2] = mx
            elif div(sds.shape[off + 1]):
                dims[off + 1] = mx
        elif name in ("ckv", "kpe"):               # (B, S, r)
            if div(sds.shape[off + 1]):
                dims[off + 1] = mx
        elif name == "state":                      # (B, H, N, P)
            if div(sds.shape[off + 1]):
                dims[off + 1] = mx
        elif name in ("conv", "conv_x", "conv_B", "conv_C"):  # (B, W-1, C)
            if div(sds.shape[off + 2]):
                dims[off + 2] = mx
        elif name == "h":                          # (B, width)
            if div(sds.shape[off + 1]):
                dims[off + 1] = mx
        return jax.ShapeDtypeStruct(sds.shape, sds.dtype,
                                    sharding=NamedSharding(mesh, P(*dims)))

    return jax.tree_util.tree_map_with_path(one, cache_shapes)


# --------------------------------------------------------------------------
# step builders
# --------------------------------------------------------------------------

def _opt_for(cfg: ModelConfig):
    big = param_count_estimate(cfg) > 100e9
    return opt_lib.adamw(1e-4, state_dtype=jnp.bfloat16 if big else
                         jnp.float32)


def _ctx_kw(cfg: ModelConfig, shape: InputShape, comm: tr.CommConfig,
            mesh, planner: Planner) -> dict:
    kw = {}
    if shape.name == "long_500k" and not cfg.is_native_long:
        kw["window_override"] = cfg.long_context_window
    if comm.moe_impl == "ep":
        kw.update(moe_impl="ep", mesh=mesh, batch_axes=planner.batch_axes,
                  fsdp_axes=planner.batch_axes if planner.fsdp else (),
                  wgather_wire=comm.wgather_wire)
    if comm.kv_chunk and shape.kind != "decode":
        kw["kv_chunk"] = comm.kv_chunk
    if comm.kv_dtype != "native" and shape.kind in ("decode", "prefill"):
        kw["kv_dtype"] = comm.kv_dtype
    return kw


def build_train(cfg, shape, mesh, planner, comm):
    model = Model(cfg)
    optimizer = _opt_for(cfg)
    step_fn = tr.make_train_step(model, optimizer, mesh, planner, comm)
    state = train_state_sds(model, optimizer, mesh, planner)
    batch = batch_specs(cfg, shape, mesh, planner, with_labels=True)
    return step_fn, (state, batch)


def build_prefill(cfg, shape, mesh, planner, comm):
    model = Model(cfg)
    kw = _ctx_kw(cfg, shape, comm, mesh, planner)

    def fn(params, batch):
        logits, cache, _ = model.prefill(params, batch, shape.seq_len, **kw)
        return logits, cache

    params = param_specs_sds(model, mesh, planner)
    batch = batch_specs(cfg, shape, mesh, planner, with_labels=False)
    return fn, (params, batch)


def build_decode(cfg, shape, mesh, planner, comm):
    model = Model(cfg)
    kw = _ctx_kw(cfg, shape, comm, mesh, planner)
    B = shape.global_batch

    def fn(params, cache, token, pos):
        return model.decode_step(params, cache, token, pos, **kw)

    params = param_specs_sds(model, mesh, planner)
    cache_shape = jax.eval_shape(
        lambda: model.init_cache(B, shape.seq_len, **kw))
    cache = cache_spec_tree(cache_shape, planner, B, mesh)
    token = _sds((B, 1), jnp.int32, mesh,
                 planner.tokens_spec(B, extra_dims=1))
    pos = _sds((), jnp.int32, mesh, P())
    return fn, (params, cache, token, pos)


# -- single-superblock steps for layerwise roofline correction --------------

def build_block_step(cfg, shape, mesh, planner, comm, kind_of_step):
    model = Model(cfg)
    kw = _ctx_kw(cfg, shape, comm, mesh, planner)
    B, S = shape.global_batch, shape.seq_len
    if kind_of_step == "train" and comm.accum_steps > 1:
        B = max(B // comm.accum_steps, 1)     # per-microbatch block cost
    if cfg.vlm_img_tokens:
        S = S  # hidden states include image positions; keep S
    ctx = model._ctx(**kw)
    defs = {f"p{i}_{k}": blocks_lib.block_defs(k, cfg)
            for i, k in enumerate(cfg.block_pattern)}
    sh = planner.tree_shardings(defs)
    pspecs = common.abstract_tree(defs, sh)
    hspec = planner.tokens_spec(B, extra_dims=2)
    enc_closure = None
    if cfg.encoder is not None:
        enc_closure = _sds((B, cfg.encoder.n_frames, cfg.d_model),
                           jnp.bfloat16, mesh, hspec)

    if kind_of_step == "train":
        h = _sds((B, S, cfg.d_model), cfg.dtype, mesh, hspec)

        def fn(params, hh, enc=None):
            c = dataclasses.replace(ctx, enc_out=enc)

            def lf(params, hh):
                out = hh
                for i, k in enumerate(cfg.block_pattern):
                    out, _ = blocks_lib.block_apply(k, params[f"p{i}_{k}"],
                                                    out, c)
                return jnp.sum(out.astype(jnp.float32))

            return jax.grad(lf, argnums=(0, 1))(params, hh)

        args = (pspecs, h) + ((enc_closure,) if enc_closure is not None else ())
        return fn, args

    if kind_of_step == "prefill":
        h = _sds((B, S, cfg.d_model), cfg.dtype, mesh, hspec)

        def fn(params, hh, enc=None):
            c = dataclasses.replace(ctx, enc_out=enc)
            for i, k in enumerate(cfg.block_pattern):
                hh, _ = blocks_lib.block_apply(k, params[f"p{i}_{k}"], hh, c)
            return hh

        args = (pspecs, h) + ((enc_closure,) if enc_closure is not None else ())
        return fn, args

    assert kind_of_step == "decode"
    h = _sds((B, 1, cfg.d_model), cfg.dtype, mesh, hspec)
    cache_shape = jax.eval_shape(lambda: {
        f"p{i}_{k}": blocks_lib.block_init_cache(k, cfg, B, shape.seq_len, ctx)
        for i, k in enumerate(cfg.block_pattern)})
    cache = cache_spec_tree(cache_shape, planner, B, mesh)
    pos = _sds((), jnp.int32, mesh, P())

    def fn(params, hh, cch, pp):
        outs = {}
        for i, k in enumerate(cfg.block_pattern):
            key = f"p{i}_{k}"
            hh, outs[key] = blocks_lib.block_decode(k, params[key], hh,
                                                    cch[key], pp, ctx)
        return hh, outs

    return fn, (pspecs, h, cache, pos)


# --------------------------------------------------------------------------
# the dry-run itself
# --------------------------------------------------------------------------

BUILDERS = {"train": build_train, "prefill": build_prefill,
            "decode": build_decode}


def should_skip(cfg: ModelConfig, shape: InputShape) -> Optional[str]:
    if shape.name == "long_500k" and not cfg.supports_long_decode:
        return ("enc-dec full attention (no windowed variant in the family); "
                "see DESIGN.md §5")
    return None


def model_flops_for(cfg: ModelConfig, shape: InputShape) -> float:
    n_active = active_param_count_estimate(cfg)
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch          # decode: 1 token


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool = False,
               comm: tr.CommConfig | None = None,
               with_block_cost: bool = True,
               fsdp_override: Optional[bool] = None,
               parallelism: str = "hybrid",
               minipod: bool = False,
               comm_stats: bool = False,
               telemetry_path: Optional[str] = None) -> dict:
    cfg = registry.get_config(arch)
    shape = SHAPES[shape_name]
    comm = comm or tr.CommConfig()
    if minipod:
        # 64-chip (8, 8) analysis mesh: used for wire-format studies where
        # XLA:CPU cannot compile the manual-mode pattern at 512 partitions
        mesh = jax.make_mesh((8, 8), ("data", "model"),
                                axis_types=(jax.sharding.AxisType.Auto,) * 2)
        mesh_name = "minipod8x8"
    else:
        mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
        mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    chips = mesh_lib.n_chips(mesh)
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                 "chips": chips, "comm": dataclasses.asdict(comm)}

    skip = should_skip(cfg, shape)
    if skip:
        rec["status"] = "skipped"
        rec["reason"] = skip
        return rec

    train = shape.kind == "train"
    bpp = (2.0 + 2.0 * (2.0 if param_count_estimate(cfg) > 100e9 else 4.0)
           if train else 2.0)
    planner = make_planner(mesh, param_count_estimate(cfg), train=train,
                           bytes_per_param_state=bpp)
    if parallelism == "dp":
        # paper C2: node-group size 1 -- pure data parallelism with
        # ZeRO-sharded parameters/optimizer over every mesh axis
        planner = Planner(mesh=mesh, fsdp=True, dp_only=True)
    if fsdp_override is not None:
        planner.fsdp = fsdp_override
    rec["fsdp"] = planner.fsdp
    rec["parallelism"] = parallelism
    rec["n_params"] = Model(cfg).n_params()

    if (comm_stats or telemetry_path) and comm.mode == "mlsl" \
            and shape.kind == "train":
        # the bucket plan is pure host math -- record the MLSL-style per-
        # bucket wire stats (repro.obs.stats) alongside the roofline so the
        # dry-run artifact says what each fused bucket would put on the wire
        st = tr.make_comm_engine(Model(cfg), mesh, planner, comm).stats()
        if comm_stats:
            rec["comm_stats"] = {
                "n_buckets": len(st.buckets),
                "topo": st.topo_name,
                "total_bytes": st.total_bytes,
                "intra_bytes": st.intra_bytes,
                "inter_bytes": st.inter_bytes,
                "t_model_total_s": st.t_model_total,
            }
            print(st.table())
        if telemetry_path:
            # healthy modeled baseline card in the telemetry schema: a live
            # run at this config can hand these bucket_times to the health
            # monitor (obs.detect) as the measured-vs-modeled denominator
            from repro.obs import telemetry as obs_telemetry
            with obs_telemetry.TelemetryWriter(
                    telemetry_path,
                    run_info={"source": "dryrun", "arch": arch,
                              "shape": shape_name, "mesh": mesh_name,
                              "topo": st.topo_name,
                              "n_buckets": len(st.buckets)},
                    sample_every=0) as tel:
                tel.bucket_times(
                    0, modeled=[b.t_model or 0.0 for b in st.buckets])
            rec["telemetry"] = telemetry_path

    fn, args = BUILDERS[shape.kind](cfg, shape, mesh, planner, comm)
    t0 = time.time()
    lowered = jax.jit(fn).lower(*args)
    rec["lower_s"] = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = time.time() - t0

    ma = compiled.memory_analysis()
    rec["memory"] = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "generated_code_bytes": int(ma.generated_code_size_in_bytes),
    }
    ca = compiled.cost_analysis() or {}
    cost_full = {k: float(ca.get(k, 0.0)) for k in ("flops", "bytes accessed")}
    rec["cost_full"] = cost_full

    cost_block = None
    reps = cfg.pattern_repeats
    if with_block_cost and reps > 1:
        bfn, bargs = build_block_step(cfg, shape, mesh, planner, comm,
                                      shape.kind)
        bcompiled = jax.jit(bfn).lower(*bargs).compile()
        bca = bcompiled.cost_analysis() or {}
        cost_block = {k: float(bca.get(k, 0.0))
                      for k in ("flops", "bytes accessed")}
        rec["cost_block"] = cost_block

    hlo = compiled.as_text()
    roof = rf.analyze(arch=arch, shape=shape_name, mesh_name=mesh_name,
                      chips=chips, cost_full=cost_full, cost_block=cost_block,
                      repeats=reps, hlo_text=hlo,
                      model_flops=model_flops_for(cfg, shape),
                      accum=comm.accum_steps if shape.kind == "train" else 1)
    rec["roofline"] = roof.as_dict()
    rec["status"] = "ok"
    return rec


def main():
    # the 512 placeholder host devices (last in the flags, so it wins over
    # an inherited count); read when the first device query starts the CPU
    # backend, so this runs before anything touches a device
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=512")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=registry.ARCH_IDS)
    ap.add_argument("--shape", choices=sorted(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--minipod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--comm", default="gspmd", choices=["gspmd", "mlsl"])
    ap.add_argument("--wire", default="fp32", choices=["fp32", "bf16", "int8"])
    ap.add_argument("--moe-impl", default="gather", choices=["gather", "ep"])
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--overlap", action="store_true",
                    help="pipeline microbatch reduction (mlsl, --accum > 1)")
    ap.add_argument("--wgather-wire", default="bf16",
                    choices=["bf16", "int8"])
    ap.add_argument("--kv-dtype", default="native",
                    choices=["native", "int8"])
    ap.add_argument("--kv-chunk", type=int, default=0)
    ap.add_argument("--parallelism", default="hybrid",
                    choices=["hybrid", "dp"])
    # observability: with --comm mlsl, print + record the per-bucket
    # CommStats table (repro.obs.stats) for each train combination;
    # --telemetry DIR additionally writes DIR/<tag>.telemetry.jsonl — the
    # modeled-only bucket_times baseline card in the telemetry schema
    ap.add_argument("--stats", action="store_true")
    ap.add_argument("--telemetry", default=None, metavar="DIR")
    ap.add_argument("--tag", default="")
    ap.add_argument("--no-prioritize", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    comm = tr.CommConfig(mode=args.comm, wire=args.wire,
                         prioritize=not args.no_prioritize,
                         moe_impl=args.moe_impl, accum_steps=args.accum,
                         overlap=args.overlap, kv_chunk=args.kv_chunk,
                         wgather_wire=args.wgather_wire,
                         kv_dtype=args.kv_dtype)
    combos = []
    meshes = [args.multi_pod] if not args.both_meshes else [False, True]
    if args.all:
        for arch in registry.ARCH_IDS:
            for shape in SHAPES:
                for mp in meshes:
                    combos.append((arch, shape, mp))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        combos = [(args.arch, args.shape, mp) for mp in meshes]

    os.makedirs(args.out, exist_ok=True)
    if args.telemetry:
        os.makedirs(args.telemetry, exist_ok=True)
    n_ok = n_skip = n_fail = 0
    for arch, shape, mp in combos:
        mesh_tag = ("minipod8x8" if args.minipod
                    else ("pod2x16x16" if mp else "pod16x16"))
        tag = f"{arch}__{shape}__{mesh_tag}"
        if args.tag:
            tag += f"__{args.tag}"
        elif comm.mode != "gspmd" or comm.moe_impl != "gather" \
                or comm.wire != "fp32" or comm.accum_steps != 1 \
                or comm.kv_chunk or args.parallelism != "hybrid":
            tag += (f"__{comm.mode}-{comm.wire}-{comm.moe_impl}"
                    f"-a{comm.accum_steps}{'-ov' if comm.overlap else ''}"
                    f"-kc{comm.kv_chunk}-{args.parallelism}")
        path = os.path.join(args.out, tag + ".json")
        if args.skip_existing and os.path.exists(path):
            print(f"[skip-existing] {tag}")
            continue
        t0 = time.time()
        try:
            rec = dryrun_one(arch, shape, multi_pod=mp, comm=comm,
                             parallelism=args.parallelism,
                             minipod=args.minipod, comm_stats=args.stats,
                             telemetry_path=(os.path.join(
                                 args.telemetry,
                                 tag + ".telemetry.jsonl")
                                 if args.telemetry else None))
        except Exception as e:      # noqa: BLE001 -- record and continue
            rec = {"arch": arch, "shape": shape, "status": "failed",
                   "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-4000:]}
        rec["wall_s"] = time.time() - t0
        with open(path, "w") as f:
            json.dump(rec, f, indent=1, default=str)
        st = rec["status"]
        n_ok += st == "ok"
        n_skip += st == "skipped"
        n_fail += st == "failed"
        extra = ""
        if st == "ok":
            r = rec["roofline"]
            extra = (f" dom={r['dominant']} tc={r['t_compute']:.3e}"
                     f" tm={r['t_memory']:.3e} tx={r['t_collective']:.3e}")
        elif st == "failed":
            extra = " " + rec["error"][:160]
        print(f"[{st}] {tag} ({rec['wall_s']:.1f}s){extra}", flush=True)
    print(f"done: ok={n_ok} skipped={n_skip} failed={n_fail}")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
