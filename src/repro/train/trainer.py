"""Training step factory: forward/backward + MLSL communication + optimizer.

Two first-class communication modes (DESIGN.md §4):

  * ``gspmd``  -- the baseline: pjit with partitioner-inserted gradient
    reductions; the priority scheduler contributes bucket ordering barriers
    between the gradients and the optimizer.

  * ``mlsl``   -- the paper's data path: the whole step runs inside a
    shard_map that is MANUAL over the batch ("pod"/"data") axes and AUTO over
    the model axis. Per-device gradients are fused into priority buckets and
    reduced explicitly through the CommEngine (repro.core.engine), which owns
    bucket planning, flat-vs-two-level routing, wire precision (fp32 / bf16 /
    int8 with optional error feedback) and the priority chain.

Gradient accumulation (``accum_steps > 1``) in mlsl mode reduces each
microbatch's buckets as they are produced (DDP-style) and accumulates the
*reduced* gradients; ``overlap=True`` software-pipelines that exchange so
microbatch k's buckets reduce interleaved with microbatch k+1's
forward/backward — the XLA-static analogue of MLSL's endpoint servers
progressing communication under compute. The two schedules compute
bit-identical fp32 values (same operations, different barrier structure).
With ``accum_steps == 1`` the step reduces once after the backward
(reduce-at-end), regardless of ``overlap``.

The returned step function is `jax.jit`-compatible with sharded TrainState /
Batch and is what launch/train.py, the dry-run, and the tests all use.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import scheduler
from repro.core.engine import CommConfig, CommEngine
from repro.core.planner import Planner
from repro.kernels import gmm as gmm_lib
from repro.models.transformer import Batch, Model
from repro.optim import optimizers as opt_lib

__all__ = ["CommConfig", "TrainState", "make_train_state", "make_comm_engine",
           "make_train_step", "state_shardings", "batch_shardings"]


@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: jax.Array
    comm_residuals: Any = None       # error-feedback residuals per bucket


jax.tree_util.register_dataclass(
    TrainState, data_fields=["params", "opt_state", "step", "comm_residuals"],
    meta_fields=[])


def make_train_state(model: Model, optimizer: opt_lib.Optimizer,
                     key: jax.Array, *,
                     engine: CommEngine | None = None) -> TrainState:
    """Fresh state. Pass the step's `engine` when it applies error feedback:
    the residuals then exist before the first step, so the state's tree
    structure never changes and the step compiles once."""
    params = model.init(key)
    return TrainState(params=params, opt_state=optimizer.init(params),
                      step=jnp.zeros((), jnp.int32),
                      comm_residuals=(None if engine is None
                                      else engine.init_residuals()))


def _layer_index_fn():
    return scheduler.default_layer_index


def _named(mesh: Mesh, specs):
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), specs,
                                  is_leaf=lambda x: isinstance(x, P))


def batch_shardings(planner: Planner, model: Model,
                    batch_size: int) -> Batch:
    """Where a global batch goes: split over the batch axes."""
    cfg = model.cfg
    tok = planner.tokens_spec(batch_size, extra_dims=1)
    three = planner.tokens_spec(batch_size, extra_dims=2)
    return _named(planner.mesh, Batch(
        tokens=tok, labels=tok, mask=None,
        img_embeds=three if cfg.vlm_img_tokens else None,
        frame_embeds=three if cfg.encoder is not None else None))


def state_shardings(planner: Planner, model: Model,
                    optimizer: opt_lib.Optimizer,
                    engine: CommEngine | None = None) -> TrainState:
    """Where the train step keeps its state: parameters as the planner lays
    them out (the optimizer state mirrors them), the error-feedback
    residuals (with `engine`) split over the batch axes. Building the
    state in this layout keeps every device busy from the start, and the
    step's outputs come back in it, so the step compiles once."""
    defs = model.param_defs()
    pspecs = planner.tree_specs(defs, stacked_paths=Model.stacked_path)
    params_shape = jax.eval_shape(lambda: jax.tree_util.tree_map(
        lambda pd: jnp.zeros(pd.shape, pd.dtype), defs, is_leaf=_is_pd))
    opt_shape = jax.eval_shape(optimizer.init, params_shape)
    # all in-tree optimizers keep {name: params-shaped tree} states
    opt_specs = {k: pspecs for k in opt_shape}
    res_specs = None
    if engine is not None:
        axes = planner.batch_axes
        res_specs = engine.residual_specs(P(axes if len(axes) > 1
                                            else axes[0]))
    return _named(planner.mesh, TrainState(
        params=pspecs, opt_state=opt_specs, step=P(),
        comm_residuals=res_specs))


def _grad_struct(model: Model):
    """Abstract f32 gradient tree matching the parameter structure."""
    return jax.eval_shape(
        lambda: jax.tree_util.tree_map(
            lambda pd: jnp.zeros(pd.shape, jnp.float32),
            model.param_defs(), is_leaf=_is_pd))


def make_comm_engine(model: Model, mesh: Mesh, planner: Planner,
                     comm: CommConfig) -> CommEngine:
    """The model's CommEngine: bucket plan + routing from its parameter
    structure and sharding groups (the glue the Session facade and the
    benchmarks also use)."""
    grad_struct = _grad_struct(model)
    # fuse only within same-sharding groups: flattening a tensor that is
    # sharded over the (auto) model axis would reshard it
    pspecs = planner.tree_specs(model.param_defs(),
                                stacked_paths=Model.stacked_path)
    spec_by_path = {jax.tree_util.keystr(path): spec for path, spec in
                    jax.tree_util.tree_leaves_with_path(
                        pspecs, is_leaf=lambda x: isinstance(x, P))}

    def group_key(path):
        return str(spec_by_path.get(jax.tree_util.keystr(path), P()))

    def leaf_replicated(path):
        spec = spec_by_path.get(jax.tree_util.keystr(path), P())
        return all(a is None for a in spec)

    hybrid = planner.hybrid
    if hybrid is None:
        return CommEngine.create(grad_struct, comm, mesh, planner.batch_axes,
                                 layer_index=_layer_index_fn(),
                                 group_key=group_key,
                                 leaf_replicated=leaf_replicated)

    # Hybrid execution: the engine runs inside a manual region over
    # data_axes + tp_axis, so it plans on what each rank actually reduces —
    # model-sharded leaves shrink to their local 1/tp shard.
    def leaf_sharded(path):
        return not leaf_replicated(path)

    def shard_struct(path, leaf):
        spec = spec_by_path.get(jax.tree_util.keystr(path), P())
        shape = list(leaf.shape)
        for d, ax in enumerate(spec):
            if ax == hybrid.tp_axis:
                shape[d] //= hybrid.tp
        return jax.ShapeDtypeStruct(tuple(shape), leaf.dtype)

    local_struct = jax.tree_util.tree_map_with_path(shard_struct, grad_struct)
    return CommEngine.create(local_struct, comm, mesh, hybrid.data_axes,
                             layer_index=_layer_index_fn(),
                             group_key=group_key,
                             leaf_replicated=leaf_replicated,
                             tp_axis=hybrid.tp_axis,
                             leaf_sharded=leaf_sharded)


def make_train_step(model: Model, optimizer: opt_lib.Optimizer, mesh: Mesh,
                    planner: Planner, comm: CommConfig,
                    *, grad_clip: float = 1.0,
                    engine: CommEngine | None = None):
    """Returns train_step(state, batch) -> (state, metrics).

    mlsl mode reduces through `engine` (built here when not given); with
    error feedback the state must carry its residuals from the start
    (make_train_state(..., engine=engine))."""
    cfg = model.cfg
    data_axes = planner.batch_axes
    fsdp_axes = planner.batch_axes if planner.fsdp else ()
    if comm.overlap and comm.mode != "mlsl":
        raise ValueError("CommConfig(overlap=True) needs the explicit mlsl "
                         "data path; gspmd reductions are partitioner-"
                         "inserted and cannot be pipelined from here")

    # Hybrid (data x model) execution: the step goes manual over the batch
    # axes AND the tp axis; parameters/optimizer state enter as local shards
    # per the planner's per-layer specs, model-sharded layers exchange
    # activations through the f/g collectives, and the engine splits the
    # gradient reduction (sharded leaves over data axes only).
    hybrid = planner.hybrid
    tp_axis = hybrid.tp_axis if hybrid is not None else None
    if hybrid is not None and comm.mode != "mlsl":
        raise ValueError("hybrid execution (planner.hybrid) needs comm mode "
                         "'mlsl': the activation f/g collectives and the "
                         "split gradient reduction run inside the explicit "
                         "manual data path")

    # mlsl mode runs the step in a shard_map manual over the batch axes (plus
    # the tp axis under hybrid). Other axes of size 1 go manual too: that
    # changes no sharding, and Mosaic kernels (the int8 wire) cannot be
    # auto-partitioned, so they need a region with no auto axis.
    manual_axes = tuple(data_axes) + ((tp_axis,) if tp_axis else ())
    manual_axes += tuple(a for a in mesh.axis_names
                         if a not in manual_axes and mesh.shape[a] == 1)

    loss_kw = dict(moe_impl=comm.moe_impl, mesh=mesh,
                   batch_axes=data_axes, fsdp_axes=fsdp_axes,
                   wgather_wire=comm.wgather_wire) \
        if comm.moe_impl == "ep" else {}
    if comm.kv_chunk:
        loss_kw["kv_chunk"] = comm.kv_chunk
    if tp_axis is not None:
        # blocks detect model-sharded weights by their shard shapes and
        # place the f/g activation collectives; DP-fallback layers see
        # full-size (replicated) weights and ignore the axis
        loss_kw["tp_axis"] = tp_axis
    if cfg.moe is not None and cfg.moe.dropless:
        # the grouped-matmul kernels for the platform the step runs on
        loss_kw["gmm_backend"] = gmm_lib.backend_for(
            mesh.devices.flat[0].platform)

    def loss_stats_fn(params, batch: Batch):
        """(loss, stats): the model's statistics (MoE counters) beside the
        loss, for value_and_grad(has_aux=True)."""
        # profile attribution: forward ops read `jvp(model)`, backward ops
        # `transpose(jvp(model))`, the forward recomputed under remat
        # `rematted_computation`
        with jax.named_scope("model"):
            return model.loss(params, batch, with_stats=True, **loss_kw)

    def loss_fn(params, batch: Batch):
        return loss_stats_fn(params, batch)[0]

    def _split_micro(batch, acc):
        def split(x):
            assert x.shape[0] % acc == 0, (x.shape, acc)
            return x.reshape(acc, x.shape[0] // acc, *x.shape[1:])
        return jax.tree_util.tree_map(split, batch)

    def grads_fn(params, batch: Batch):
        """(loss, grads, stats), microbatched over comm.accum_steps (C3:
        large global batches at bounded activation memory). Gradients here
        are UNREDUCED (local); used by gspmd (partitioner reduces) and by
        the mlsl accum_steps == 1 path (engine reduces at end). The model's
        stats are reported without accumulation only."""
        if comm.accum_steps <= 1:
            (loss, stats), grads = jax.value_and_grad(
                loss_stats_fn, has_aux=True)(params, batch)
            return loss, grads, stats
        acc = comm.accum_steps
        micro = _split_micro(batch, acc)
        gz = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), params)

        def body(carry, mb):
            gsum, lsum = carry
            loss, g = jax.value_and_grad(loss_fn)(params, mb)
            gsum = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(jnp.float32), gsum, g)
            return (gsum, lsum + loss), None

        (gsum, lsum), _ = jax.lax.scan(body, (gz, jnp.zeros(())), micro)
        grads = jax.tree_util.tree_map(
            lambda g, pp: (g / acc).astype(pp.dtype), gsum, params)
        return lsum / acc, grads, {}

    if comm.mode == "gspmd":
        def train_step(state: TrainState, batch: Batch):
            loss, grads, stats = grads_fn(state.params, batch)
            grads, gnorm = opt_lib.clip_by_global_norm(grads, grad_clip)
            if comm.prioritize:
                # barrier-chain only (fuse=False): under GSPMD the reductions
                # are partitioner-inserted and fusing sharded leaves would
                # force all-gathers (§Perf iteration A0)
                plan = scheduler.plan_buckets(
                    grads, _layer_index_fn(), bucket_bytes=comm.bucket_bytes)
                grads = scheduler.reduce_with_priority(
                    grads, lambda flat, b: flat, plan, prioritize=True,
                    fuse=False)
            params, opt_state = optimizer.update(grads, state.opt_state,
                                                 state.params, state.step)
            new = TrainState(params=params, opt_state=opt_state,
                             step=state.step + 1,
                             comm_residuals=state.comm_residuals)
            return new, {"loss": loss, "grad_norm": gnorm, **stats}
        return train_step

    assert comm.mode == "mlsl", comm.mode
    assert not planner.fsdp, ("comm=mlsl manages gradient communication "
                              "explicitly and requires replicated (non-FSDP) "
                              "parameters over the batch axes; use gspmd for "
                              "ZeRO-sharded giants")

    # The engine owns the whole bucket-reduction data path: planning,
    # flat-vs-two-level routing, wire precision, error feedback, priority
    # chain.
    if engine is None:
        engine = make_comm_engine(model, mesh, planner, comm)

    if tp_axis is None:
        pspecs = None
        clip_grads = opt_lib.clip_by_global_norm
    else:
        pspecs = planner.tree_specs(model.param_defs(),
                                    stacked_paths=Model.stacked_path)
        sharded_flags = [any(ax == tp_axis for ax in s)
                         for s in jax.tree_util.tree_leaves(
                             pspecs, is_leaf=lambda x: isinstance(x, P))]

        def clip_grads(grads, max_norm):
            """opt_lib.clip_by_global_norm with the model-sharded leaves'
            sum-of-squares psum'd over the tp axis (each rank holds a
            distinct shard; replicated leaves are counted once). The norm
            comes out replicated everywhere, so replicated parameters keep
            taking identical updates across the tp group."""
            leaves = jax.tree_util.tree_leaves(grads)
            z = jnp.zeros((), jnp.float32)
            sq_sh = sum((jnp.sum(g.astype(jnp.float32) ** 2)
                         for g, sh in zip(leaves, sharded_flags) if sh), z)
            sq_rep = sum((jnp.sum(g.astype(jnp.float32) ** 2)
                          for g, sh in zip(leaves, sharded_flags) if not sh),
                         z)
            gn = jnp.sqrt(sq_rep + jax.lax.psum(sq_sh, tp_axis))
            scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-9))
            return jax.tree_util.tree_map(
                lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype),
                grads), gn

    def _to_f32(tree):
        return jax.tree_util.tree_map(
            lambda x: x.astype(jnp.float32), tree)

    def accum_reduce(params, batch: Batch, residuals):
        """Per-microbatch exchange over the accumulation scan.

        Each microbatch's gradients are reduced (mean over ranks) and the
        REDUCED gradients accumulated. overlap=False is the blocking
        baseline: the barrier token gates microbatch k+1's inputs on
        microbatch k's reduction chain retiring. overlap=True software-
        pipelines: microbatch k's reduction is issued with no data
        dependence on microbatch k+1's compute (only the collective chain
        itself is token-ordered), so the compiler may overlap the two —
        MLSL's EP servers, expressed statically. Both schedules perform the
        identical fp32 operation sequence, so they are bit-identical.

        The accumulator lives in the engine's BUCKET layout (one flat f32
        buffer per fused bucket, engine.init_accum) rather than as a
        gradient tree: the per-microbatch add then rides the gather-side
        dequantize_accumulate pass on the int8 wire (and stays one
        bucket-sized add on float wires) instead of a full extra
        read+write of the model per microbatch. The tree is restored once,
        after the last microbatch (engine.unfuse_accum).
        """
        acc = comm.accum_steps
        micro = _split_micro(batch, acc)
        token0 = jnp.zeros((), jnp.float32)

        # Microbatch 0 is peeled out of the scan in BOTH schedules so the
        # loss_fn call sites match exactly (prologue + scan-of-rest): XLA
        # fuses a top-level instance and an in-scan-body instance of the
        # same function differently, and matched call sites are what makes
        # the two schedules bit-identical, not just close.
        mb0 = jax.tree_util.tree_map(lambda x: x[0], micro)
        rest = jax.tree_util.tree_map(lambda x: x[1:], micro)
        # named scopes (profile attribution only) are applied SYMMETRICALLY
        # across the blocking and overlap schedules — matched call sites are
        # part of the bit-identity contract above
        with jax.named_scope("microbatch/fwd_bwd"):
            loss0, g0 = jax.value_and_grad(loss_fn)(params, mb0)

        if not comm.overlap:
            # blocking baseline: reduce each microbatch's buckets before the
            # next microbatch's compute. Without prioritization the engine
            # does not thread its own token, so the gate is derived from
            # every bucket's accumulator instead — blocking must not
            # silently weaken under prioritize=False.
            def exchange(g, bacc, res, token):
                with jax.named_scope("microbatch/exchange"):
                    bacc, res, token = engine.reduce_accum_chained(
                        _to_f32(g), bacc, res, token)
                if not comm.prioritize:
                    token = engine.gate_token_accum(bacc)
                return bacc, res, token

            bacc, residuals, token = exchange(g0, engine.init_accum(),
                                              residuals, token0)

            def body(carry, mb):
                bacc, lsum, res, token = carry
                mb, token = scheduler.chain_barrier(mb, token)
                with jax.named_scope("microbatch/fwd_bwd"):
                    loss, g = jax.value_and_grad(loss_fn)(params, mb)
                bacc, res, token = exchange(g, bacc, res, token)
                return (bacc, lsum + loss, res, token), None

            (bacc, lsum, residuals, _), _ = jax.lax.scan(
                body, (bacc, loss0, residuals, token), rest)
        else:
            # software pipeline: iteration k reduces microbatch k-1's
            # buckets beside microbatch k's compute (the reduction chain is
            # token-ordered but carries no dependence on the compute); the
            # epilogue drains the last microbatch
            def body(carry, mb):
                bacc, lsum, pending, res, token = carry
                with jax.named_scope("microbatch/fwd_bwd"):
                    loss, g = jax.value_and_grad(loss_fn)(params, mb)
                with jax.named_scope("microbatch/exchange"):
                    bacc, res, token = engine.reduce_accum_chained(
                        pending, bacc, res, token)
                return (bacc, lsum + loss, _to_f32(g), res, token), None

            (bacc, lsum, pending, residuals, token), _ = jax.lax.scan(
                body, (engine.init_accum(), loss0, _to_f32(g0), residuals,
                       token0), rest)
            with jax.named_scope("microbatch/exchange"):
                bacc, residuals, _ = engine.reduce_accum_chained(
                    pending, bacc, residuals, token)

        gsum = engine.unfuse_accum(bacc)
        with jax.named_scope("optim/cast"):
            grads = jax.tree_util.tree_map(
                lambda g, pp: (g / acc).astype(pp.dtype), gsum, params)
        return lsum / acc, grads, residuals

    # shard_map specs: manual over batch axes only; model axis stays auto.
    bspec = data_axes if len(data_axes) > 1 else data_axes[0]
    replicated = P()

    def inner(params, opt_state, step, residuals, batch: Batch):
        # per-device local loss; gradient = d(local mean)/d(params). Device
        # ops carry the layer's scope: `model`, `comm/` (the engine) and
        # `optim/` (everything from the reduced gradients to the new
        # parameters)
        stats = {}
        if comm.accum_steps > 1:
            loss, grads, residuals = accum_reduce(params, batch, residuals)
        else:
            loss, grads, stats = grads_fn(params, batch)
            grads, residuals = engine.reduce(grads, residuals)
            # the engine reduces in f32; hand the optimizer the parameters'
            # dtype, as the gspmd step does. The barrier keeps that cast:
            # XLA's excess-precision rewrite would otherwise hold the f32
            # messages as the tree the global norm waits on (3.5 GB at
            # Yi-6B widths on one chip)
            with jax.named_scope("optim/cast"):
                grads = jax.lax.optimization_barrier(jax.tree_util.tree_map(
                    lambda g, pp: g.astype(pp.dtype), grads, params))
        with jax.named_scope("optim/clip"):
            grads, gnorm = clip_grads(grads, grad_clip)
        loss = jax.lax.pmean(loss, data_axes)
        stats = _reduce_stats(stats, data_axes)
        with jax.named_scope("optim/update"):
            params, opt_state = optimizer.update(grads, opt_state, params,
                                                 step)
        return params, opt_state, residuals, loss, gnorm, stats

    grad_treedef = engine.plan.buckets.treedef
    if tp_axis is None:
        params_specs = jax.tree_util.tree_unflatten(
            grad_treedef, [replicated] * grad_treedef.num_leaves)
    else:
        # per-layer hybrid sharding: model-parallel layers' weights enter as
        # local shards over tp_axis, everything else replicated
        params_specs = pspecs
    batch_in_specs = Batch(tokens=P(bspec), labels=P(bspec), mask=None,
                           img_embeds=P(bspec) if cfg.vlm_img_tokens else None,
                           frame_embeds=P(bspec) if cfg.encoder is not None
                           else None)
    res_spec = engine.residual_specs(P(bspec))

    def train_step(state: TrainState, batch: Batch):
        if tp_axis is None:
            opt_specs = jax.tree_util.tree_map(lambda _: replicated,
                                               state.opt_state,
                                               is_leaf=lambda x: x is None)
        else:
            # all in-tree optimizers keep {name: params-shaped tree} states
            opt_specs = {k: params_specs for k in state.opt_state}
        residuals = state.comm_residuals
        if engine.plan.use_ef and residuals is None:
            raise ValueError(
                "error feedback needs its residuals in the state before the "
                "first step: make_train_state(..., engine=engine)")

        out = jax.shard_map(
            inner, mesh=mesh,
            in_specs=(params_specs, opt_specs, replicated, res_spec,
                      batch_in_specs),
            out_specs=(params_specs, opt_specs, res_spec, replicated,
                       replicated, replicated),
            axis_names=set(manual_axes), check_vma=False,
        )(state.params, state.opt_state, state.step, residuals, batch)
        params, opt_state, residuals, loss, gnorm, stats = out
        new = TrainState(params=params, opt_state=opt_state,
                         step=state.step + 1, comm_residuals=residuals)
        return new, {"loss": loss, "grad_norm": gnorm, **stats}

    return train_step


def _reduce_stats(stats: dict, axes) -> dict:
    """The model's per-chip statistics over the data axes: row counts
    summed, maxima the largest, the aux loss averaged as the loss is."""
    out = {}
    for k, v in stats.items():
        if k.startswith("moe/max_"):
            out[k] = jax.lax.pmax(v, axes)
        elif k == "moe/aux":
            out[k] = jax.lax.pmean(v, axes)
        else:
            out[k] = jax.lax.psum(v, axes)
    return out


def _is_pd(x):
    from repro.core.planner import ParamDef
    return isinstance(x, ParamDef)
