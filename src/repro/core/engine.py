"""CommEngine: the unified bucket-reduction data path (paper §2, MLSL EP servers).

MLSL puts every performance decision of the gradient exchange — message
fusion, per-message algorithm choice, wire precision, prioritization, and
asynchronous progress — behind one library object so frameworks stay thin.
This module is that object for the reproduction:

  * ``CommConfig``  -- the declarative knobs (mode, wire precision, bucket
    size, error feedback, two-level hierarchy, overlap), shared by the
    trainer, the Session facade, the launch drivers, and the dry-run;
  * ``EnginePlan``  -- the static plan compiled from a gradient structure +
    CommConfig + mesh: bucket boundaries (scheduler.plan_buckets), which
    buckets may travel fused, and each bucket's flat-vs-hierarchical route
    (scheduler.route_buckets over the hw.Topology cost model);
  * ``CommEngine``  -- executes the plan inside a shard_map manual region:
    ``engine.reduce(grads, residuals)`` is the whole exchange, and
    ``engine.reduce_chained`` threads the optimization_barrier token across
    calls so reductions issued from consecutive microbatches form one
    priority chain — the structural analogue of MLSL's endpoint servers
    making progress on microbatch k's buckets while microbatch k+1 computes
    (see train.trainer's overlap mode).

Everything the engine runs must be INSIDE a shard_map manual region over
``data_axes``, same contract as repro.core.collectives / repro.core.hier.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import collectives as cl
from repro.core import hier as hier_lib
from repro.core import hw
from repro.core import planner as planner_lib
from repro.core import scheduler
from repro.kernels import ops as kops


@dataclasses.dataclass(frozen=True)
class CommConfig:
    """Declarative communication configuration (consumed by CommEngine and
    the train-step factory; ``train.trainer.CommConfig`` is this class)."""

    mode: str = "gspmd"              # gspmd | mlsl
    wire: str = cl.WIRE_FP32
    prioritize: bool = True
    bucket_bytes: float = 25e6
    error_feedback: bool = False     # int8 wire only
    moe_impl: str = "gather"         # gather | ep  (expert-parallel a2a)
    accum_steps: int = 1             # microbatch gradient accumulation
    kv_chunk: int = 0                # >0: online-softmax attention chunking
    wgather_wire: str = "bf16"       # int8: quantized ZeRO weight gathers (ep)
    kv_dtype: str = "native"         # int8: quantized GQA KV cache (serving)
    # two-level collectives over a ("node", "local") factored data dimension
    # (repro.core.hier): `wire` selects the inter-node fabric leg and
    # `wire_intra` the intra-node legs (None: hier.default_wire_intra).
    # `topo` optionally names a machine hierarchy (repro.core.hw.TOPOLOGIES);
    # when set, each fused bucket is routed flat vs two-level by the
    # per-level cost model (scheduler.route_buckets) instead of always
    # taking the hierarchical path.
    hier: bool = False
    wire_intra: Optional[str] = None
    topo: Optional[str] = None
    # MLSL-style compute/communication overlap (mlsl mode, accum_steps > 1):
    # microbatch k's buckets are reduced interleaved with microbatch k+1's
    # forward/backward inside the accumulation scan. With accum_steps == 1
    # the engine falls back to the single reduce-at-end exchange.
    overlap: bool = False
    # int8 wire kernel dispatch: "auto" resolves through the single
    # kernels/ops.py policy for the mesh's platform (compiled pallas on TPU,
    # jnp/interpreted pallas elsewhere); the resolved choice is recorded in
    # EnginePlan.quant_backend.
    # `fused_quant=False` falls back to the composed (multi-pass) kernels --
    # an ablation/debug path, not a production setting.
    quant_backend: str = "auto"
    fused_quant: bool = True
    # Benchmark ablation: skip gradient reduction entirely. The step then
    # trains on unreduced per-rank gradients (numerically meaningless at
    # dp > 1) — used only to measure the compute-only floor that exposed-
    # communication accounting subtracts (benchmarks/bench_overlap.py).
    skip_reduce: bool = False


@dataclasses.dataclass(frozen=True)
class EnginePlan:
    """Static description of one model's gradient exchange.

    Built once from the (abstract) gradient structure; everything traced at
    step time just walks these tuples.
    """

    buckets: scheduler.BucketPlan
    algos: tuple                     # planner.ALGO_FLAT|ALGO_HIER per bucket
    fusable: tuple                   # bool per bucket: may travel flattened
    data_axes: tuple
    dp: int                          # total data-parallel ranks
    wire: str
    prioritize: bool
    use_ef: bool
    hier_spec: Optional[hier_lib.HierSpec]
    n_node: int                      # 1 when not hierarchical
    n_local: int
    overlap: bool
    accum_steps: int
    skip_reduce: bool = False
    # hybrid (data x model) execution: gradients of model-sharded parameters
    # reduce over the data axes only (each rank owns a distinct 1/tp shard),
    # while replicated-parameter gradients reduce over data axes + tp_axis
    # (their per-rank copies are identical, so the mean is unchanged and the
    # two-level path gets the intra link back). bucket_axes records the
    # reduce axes per bucket; () means "use data_axes for every bucket".
    tp_axis: Optional[str] = None
    tp: int = 1
    bucket_axes: tuple = ()
    # int8 wire execution detail, resolved once at plan-build time: which
    # kernel backend every quantized leg runs (kops.BACKENDS), whether the
    # single-pass fused kernels are used, and the per-bucket padding waste
    # fraction the (TILE_ROWS x QUANT_BLOCK) tiling charges (only non-trivial
    # for tiny buckets; () when the wire is not int8).
    quant_backend: str = "jnp"
    fused_quant: bool = True
    quant_pad: tuple = ()
    # the hw.TOPOLOGIES name the buckets were routed against (None when no
    # cost-model routing was requested) — kept on the plan so observability
    # reports (repro.obs.stats) model time on the same topology
    topo: Optional[str] = None

    def axes_for(self, bi: int) -> tuple:
        return self.bucket_axes[bi] if self.bucket_axes else self.data_axes

    @property
    def n_buckets(self) -> int:
        return len(self.buckets.buckets)

    def bucket_bytes_list(self, bytes_per_elem: float = 4.0) -> tuple:
        return tuple(b.n_elems * bytes_per_elem for b in self.buckets.buckets)

    def describe(self, *, topo=None) -> str:
        """The MLSL-style per-bucket stats table for this plan (wire bytes
        per leg, route, modeled service time). Lazy import: repro.obs sits
        above core, so the plan only reaches it when a human asks."""
        from repro.obs import stats as obs_stats
        return obs_stats.CommStats.from_plan(self, topo=topo).table()


def build_plan(grad_struct, comm: CommConfig, mesh, data_axes, *,
               layer_index: Callable[[tuple], float] | None = None,
               group_key: Callable[[tuple], object] | None = None,
               leaf_replicated: Callable[[tuple], bool] | None = None,
               tp_axis: Optional[str] = None,
               leaf_sharded: Callable[[tuple], bool] | None = None
               ) -> EnginePlan:
    """Compile CommConfig + gradient structure + mesh into an EnginePlan.

    `grad_struct` is any pytree of arrays/ShapeDtypeStructs with the
    gradients' shapes. `group_key(path)` marks sharding groups that must not
    fuse across; `leaf_replicated(path)` says whether a leaf is fully
    replicated over the auto axes (only such buckets may travel as one flat
    message — flattening a model-sharded gradient would reshard it).

    `tp_axis` + `leaf_sharded` switch on hybrid (data x model) execution:
    the engine then runs inside a manual region over data_axes + tp_axis,
    `grad_struct` describes each rank's LOCAL gradient shards, and
    `leaf_sharded(path)` marks leaves whose parameter is model-sharded over
    `tp_axis`. Sharded buckets reduce over the data axes only; replicated
    buckets reduce over data axes + tp_axis (identical per-rank copies, so
    the mean is unchanged and the hierarchical route stays available). In
    this fully-manual region every leaf is a local array, so all buckets may
    travel fused.
    """
    if layer_index is None:
        layer_index = scheduler.default_layer_index
    plan = scheduler.plan_buckets(grad_struct, layer_index,
                                  bucket_bytes=comm.bucket_bytes,
                                  group_key=group_key)
    leaf_paths = [path for path, _ in
                  jax.tree_util.tree_leaves_with_path(grad_struct)]
    if leaf_replicated is None:
        fusable = tuple(True for _ in plan.buckets)
    else:
        fusable = tuple(
            all(leaf_replicated(leaf_paths[i]) for i in b.leaf_ids)
            for b in plan.buckets)

    dp = 1
    for a in data_axes:
        dp *= mesh.shape[a]
    use_ef = comm.error_feedback and comm.wire == cl.WIRE_INT8
    # resolve the kernel backend ONCE here, for the platform of the mesh's
    # devices (the plan records the choice; the traced data path never
    # consults the policy again) and account the tiling pad waste per
    # bucket so undersized int8 buckets are visible
    platform = (mesh.devices.flat[0].platform
                if isinstance(mesh, jax.sharding.Mesh) else None)
    qb = kops.wire_backend(comm.quant_backend, platform)
    quant_pad = ()
    if comm.wire == cl.WIRE_INT8:
        quant_pad = tuple(kops.pad_info(b.n_elems).waste_frac
                          for b in plan.buckets)

    tp = 1
    bucket_axes = ()
    sharded_buckets = tuple(False for _ in plan.buckets)
    if tp_axis is not None:
        if leaf_sharded is None:
            raise ValueError("tp_axis requires a leaf_sharded predicate")
        if use_ef:
            raise ValueError(
                "error feedback is unsupported with hybrid tensor "
                "parallelism: the int8 residual is a per-rank fabric shard, "
                "but model-sharded gradients reduce over the node axis only "
                "while replicated ones reduce over (node, local)")
        tp = int(mesh.shape[tp_axis])
        sharded_buckets = tuple(
            any(leaf_sharded(leaf_paths[i]) for i in b.leaf_ids)
            for b in plan.buckets)
        full = tuple(data_axes) + (tp_axis,)
        bucket_axes = tuple(tuple(data_axes) if sh else full
                            for sh in sharded_buckets)
        fusable = tuple(True for _ in plan.buckets)

    hier_spec = None
    n_node, n_local = 1, dp
    if comm.hier:
        hier_axes = tuple(data_axes) + ((tp_axis,) if tp_axis else ())
        assert hier_lib.NODE_AXIS in hier_axes and \
            hier_lib.LOCAL_AXIS in hier_axes, (
                "comm.hier needs the data dimension factored over "
                f"({hier_lib.NODE_AXIS!r}, {hier_lib.LOCAL_AXIS!r}) mesh "
                f"axes (launch.mesh.make_hier_mesh); got {hier_axes}")
        wire_intra = comm.wire_intra or hier_lib.default_wire_intra(comm.wire)
        hier_spec = hier_lib.HierSpec(wire_intra=wire_intra,
                                      wire_inter=comm.wire,
                                      error_feedback=use_ef,
                                      backend=qb,
                                      fused=comm.fused_quant)
        n_node = mesh.shape[hier_lib.NODE_AXIS]
        n_local = mesh.shape[hier_lib.LOCAL_AXIS]
        if comm.topo is not None:
            if comm.topo not in hw.TOPOLOGIES:
                raise ValueError(
                    f"unknown topology {comm.topo!r}; known: "
                    f"{sorted(hw.TOPOLOGIES)}")
            # per-bucket flat-vs-two-level routing from the per-level cost
            # model: small latency-bound buckets may stay flat while bulk
            # buckets take the hierarchy (MLSL per-message phase choice)
            algos = scheduler.route_buckets(plan, hw.TOPOLOGIES[comm.topo],
                                            nodes=n_node, wire=comm.wire,
                                            ef=use_ef,
                                            fused_quant=comm.fused_quant)
        else:
            algos = tuple(planner_lib.ALGO_HIER for _ in plan.buckets)
        if tp_axis is not None:
            # the two-level path needs BOTH hierarchy axes in a bucket's
            # reduce axes; model-sharded buckets reduce over the node axis
            # only, so they always go flat
            algos = tuple(planner_lib.ALGO_FLAT if sh else a
                          for a, sh in zip(algos, sharded_buckets))
    else:
        algos = tuple(planner_lib.ALGO_FLAT for _ in plan.buckets)

    return EnginePlan(buckets=plan, algos=algos, fusable=fusable,
                      data_axes=tuple(data_axes), dp=dp, wire=comm.wire,
                      prioritize=comm.prioritize, use_ef=use_ef,
                      hier_spec=hier_spec, n_node=n_node, n_local=n_local,
                      overlap=comm.overlap, accum_steps=comm.accum_steps,
                      skip_reduce=comm.skip_reduce, tp_axis=tp_axis, tp=tp,
                      bucket_axes=bucket_axes, quant_backend=qb,
                      fused_quant=comm.fused_quant, quant_pad=quant_pad,
                      topo=comm.topo)


@dataclasses.dataclass(frozen=True)
class CommEngine:
    """Executes an EnginePlan: the single entry point for bucket reduction."""

    plan: EnginePlan

    @classmethod
    def create(cls, grad_struct, comm: CommConfig, mesh, data_axes,
               **kw) -> "CommEngine":
        return cls(plan=build_plan(grad_struct, comm, mesh, data_axes, **kw))

    @property
    def tp(self) -> Optional[cl.TPComm]:
        """Activation-exchange communicator for the plan's model axis (None
        on pure-DP plans): the f/g operator pair model-parallel layers place
        around their sharded projections (collectives.tp_replicate /
        tp_psum), handed out here so the activation flow and the gradient-
        bucket flow share one comm surface."""
        if self.plan.tp_axis is None:
            return None
        return cl.TPComm(self.plan.tp_axis)

    # -- residual (error-feedback) state -----------------------------------

    def ef_applied(self, bi: int) -> bool:
        """Does bucket `bi` actually run the error-feedback int8 wire?

        Non-fusable (model-sharded) buckets are forced onto the bf16 wire by
        `reduce_chained` and carry their residual entry through unchanged, so
        allocating them a real residual buffer would waste fp32 memory
        proportional to the model's sharded footprint."""
        return self.plan.use_ef and self.plan.fusable[bi]

    def init_residuals(self):
        """Global-view zero residuals: per-rank shard x dp ranks along dim 0
        (the shard_map in_spec splits them back to one fabric shard per
        rank). Each shard is held in the wire kernels' (blocks, QUANT_BLOCK)
        layout, so the fused error-feedback kernel updates it in place: a
        flat copy would cost a relayout into and out of that layout, two
        residual-sized temporaries per bucket.

        Only buckets whose data path applies error feedback (fusable ones —
        see `ef_applied`) get real buffers; the rest hold zero-length
        placeholders so the residual tuple keeps one entry per bucket and
        `residual_specs` stays aligned."""
        p = self.plan
        if not p.use_ef:
            return None

        def init(bi, b):
            if not self.ef_applied(bi):
                return jnp.zeros((0,), jnp.float32)
            if p.algos[bi] == planner_lib.ALGO_HIER:
                n = hier_lib.ef_residual_shape(b.n_elems, p.n_local,
                                               p.n_node)[0]
            else:
                n = cl.ef_residual_shape(b.n_elems, p.dp)[0]
            return jnp.zeros((n // cl.QUANT_BLOCK * p.dp, cl.QUANT_BLOCK),
                             jnp.float32)

        return tuple(init(bi, b) for bi, b in enumerate(p.buckets.buckets))

    def residual_specs(self, bucket_spec):
        """shard_map in/out specs for the residual state (None without EF)."""
        if not self.plan.use_ef:
            return None
        return tuple(bucket_spec for _ in self.plan.buckets.buckets)

    # -- the data path ------------------------------------------------------

    def _reduce_bucket(self, flat, residual, bi: int, acc=None):
        """One fused message over the data axes: flat or two-level path per
        the bucket routing. Returns (reduced, new_residual_or_None).

        `acc` (f32, flat's shape) folds an existing accumulator into the
        gather-side dequantize (kernels.ops.dequantize_accumulate): on the
        int8 wire the sum lands in the same pass that expands the wire
        payload, instead of a separate full-size read-add-write.

        The message is wrapped in a `jax.named_scope` naming its route and
        wire, inside the caller's `comm/bucket{bi}` scope, so XLA profiles
        attribute device time to the bucket and route (metadata only —
        numerics and schedules are untouched)."""
        p = self.plan
        route = "hier" if p.algos[bi] == planner_lib.ALGO_HIER else "flat"
        with jax.named_scope(f"{route}_allreduce_{p.wire}"):
            if p.algos[bi] == planner_lib.ALGO_HIER:
                if p.use_ef:
                    return hier_lib.hier_allreduce_ef(flat, residual,
                                                      p.hier_spec, mean=True,
                                                      acc=acc)
                return hier_lib.hier_allreduce(flat, p.hier_spec, mean=True,
                                               acc=acc), None
            if p.use_ef:
                return cl.allreduce_ef(flat, residual, p.data_axes,
                                       mean=True, backend=p.quant_backend,
                                       fused=p.fused_quant, acc=acc)
            return cl.allreduce(flat, p.axes_for(bi), wire=p.wire,
                                mean=True, backend=p.quant_backend,
                                fused=p.fused_quant, acc=acc), None

    def _reduce_leafwise(self, vals, bi: int, token):
        """A model-sharded bucket, reduced leaf by leaf and shape-
        preserving (the int8 wire's flatten/scatter composition would
        reshard its leaves, so it takes the bf16 wire instead). Returns
        (reduced leaves, token)."""
        p = self.plan
        wire = p.wire if p.wire != cl.WIRE_INT8 else cl.WIRE_BF16
        with jax.named_scope(f"leafwise_allreduce_{wire}"):
            vals = [cl.allreduce(v, p.axes_for(bi), wire=wire, mean=True)
                    for v in vals]
        if p.prioritize:
            token = scheduler._token_of(vals[0])
        return vals, token

    def reduce_chained(self, grads, residuals, token):
        """Fused, prioritized, wire-precision gradient exchange, continuing
        an existing priority chain.

        Replicated buckets travel as one fused flat message (MLSL message
        fusion + optional int8 block quantization and error feedback).
        Model-sharded buckets are reduced per-leaf, shape-preserving (no
        resharding); the int8 wire's flatten/scatter composition would
        reshard them, so those leaves use the bf16 wire instead.

        `token` is the optimization_barrier chain carried in from a previous
        exchange (or None / a constant scalar to start a fresh chain): with
        prioritization, bucket k+1's message is made data-dependent on bucket
        k's reduced result, so the compiler issues collectives in forward-
        layer order across ALL chained calls — in the trainer's overlap mode
        the chain spans microbatches, ordering microbatch k's reduction ahead
        of microbatch k+1's without tying it to k+1's compute.
        Returns (reduced_tree, new_residuals, token).

        Device ops are named per bucket: `comm/bucket{bi}/pack` (fusion
        into the message and the chain barrier), the message's route and
        wire (`_reduce_bucket`), and `comm/bucket{bi}/unpack`.
        """
        p = self.plan
        if p.skip_reduce:
            return grads, residuals, token
        leaves = jax.tree_util.tree_leaves(grads)
        new_leaves = list(leaves)
        new_residuals = []
        for bi, bucket in enumerate(p.buckets.buckets):
            with jax.named_scope(f"comm/bucket{bi}"):
                if p.fusable[bi]:
                    with jax.named_scope("pack"):
                        flat = scheduler.fuse_bucket(leaves, bucket)
                        if p.prioritize:
                            flat, token = scheduler.chain_barrier(flat, token)
                    red, res = self._reduce_bucket(
                        flat, residuals[bi] if p.use_ef else None, bi)
                    if p.use_ef:
                        new_residuals.append(res)
                    with jax.named_scope("unpack"):
                        if p.prioritize:
                            token = scheduler._token_of(red)
                        for lid, leaf in scheduler.unfuse_bucket(
                                red, bucket).items():
                            new_leaves[lid] = leaf
                else:
                    vals = [leaves[i] for i in bucket.leaf_ids]
                    if p.prioritize:
                        with jax.named_scope("pack"):
                            vals, token = scheduler.chain_barrier(vals, token)
                    vals, token = self._reduce_leafwise(vals, bi, token)
                    if p.use_ef:
                        new_residuals.append(residuals[bi])
                    for lid, leaf in zip(bucket.leaf_ids, vals):
                        new_leaves[lid] = leaf
        out = jax.tree_util.tree_unflatten(p.buckets.treedef, new_leaves)
        return out, (tuple(new_residuals) if p.use_ef else residuals), token

    # -- flat gradient accumulation (microbatch loop) -----------------------
    #
    # The trainer's accumulation loop used to materialize a reduced gradient
    # TREE per microbatch and tree-add it into a sum. With the int8 wire that
    # is a full extra read+write of the model per microbatch. These methods
    # keep the accumulator in the engine's own bucket layout (one flat f32
    # buffer per fused bucket) so the add rides the gather-side
    # dequantize_accumulate pass instead.

    def init_accum(self):
        """Zero accumulators in bucket layout: one flat f32 buffer per
        fusable bucket, a per-leaf f32 tuple for non-fusable ones."""
        p = self.plan
        return tuple(
            jnp.zeros((b.n_elems,), jnp.float32) if p.fusable[bi]
            else tuple(jnp.zeros(shape, jnp.float32) for shape in b.shapes)
            for bi, b in enumerate(p.buckets.buckets))

    def reduce_accum_chained(self, grads, acc, residuals, token):
        """reduce_chained, but the reduced messages land IN the bucket-layout
        accumulator (`acc`, from `init_accum`) instead of coming back as a
        gradient tree: acc'[bi] = acc[bi] + reduce(bucket bi of grads).

        On the int8 wire the accumulate is fused into the gather-side
        dequantize (one pass); on float wires it is a plain add on the
        reduced message (still bucket-sized, never tree-shaped). Returns
        (new_acc, new_residuals, token) — unbucketed via `unfuse_accum`
        after the last microbatch. Device ops are named per bucket as in
        `reduce_chained`.
        """
        p = self.plan
        leaves = jax.tree_util.tree_leaves(grads)
        new_acc = []
        new_residuals = []
        for bi, bucket in enumerate(p.buckets.buckets):
            with jax.named_scope(f"comm/bucket{bi}"):
                if p.fusable[bi]:
                    with jax.named_scope("pack"):
                        flat = scheduler.fuse_bucket(leaves, bucket)
                        if p.prioritize and not p.skip_reduce:
                            flat, token = scheduler.chain_barrier(flat, token)
                    if p.skip_reduce:
                        new_acc.append(acc[bi] + flat)
                        if p.use_ef:
                            new_residuals.append(residuals[bi])
                        continue
                    red, res = self._reduce_bucket(
                        flat, residuals[bi] if p.use_ef else None, bi,
                        acc=acc[bi])
                    if p.use_ef:
                        new_residuals.append(res)
                    if p.prioritize:
                        token = scheduler._token_of(red)
                    new_acc.append(red)
                else:
                    vals = [leaves[i] for i in bucket.leaf_ids]
                    if p.skip_reduce:
                        new_acc.append(tuple(
                            a + v.astype(jnp.float32)
                            for a, v in zip(acc[bi], vals)))
                        if p.use_ef:
                            new_residuals.append(residuals[bi])
                        continue
                    if p.prioritize:
                        with jax.named_scope("pack"):
                            vals, token = scheduler.chain_barrier(vals, token)
                    vals, token = self._reduce_leafwise(vals, bi, token)
                    if p.use_ef:
                        new_residuals.append(residuals[bi])
                    new_acc.append(tuple(
                        a + v.astype(jnp.float32)
                        for a, v in zip(acc[bi], vals)))
        return (tuple(new_acc),
                (tuple(new_residuals) if p.use_ef else residuals), token)

    def unfuse_accum(self, acc):
        """Bucket-layout accumulator -> f32 gradient tree (no dtype cast:
        the trainer divides by accum_steps before casting to param dtype)."""
        p = self.plan
        leaves = [None] * p.buckets.treedef.num_leaves
        for bi, b in enumerate(p.buckets.buckets):
            if p.fusable[bi]:
                off = 0
                with jax.named_scope(f"comm/bucket{bi}/unpack"):
                    for lid, size, shape in zip(b.leaf_ids, b.sizes,
                                                b.shapes):
                        leaves[lid] = acc[bi][off:off + size].reshape(shape)
                        off += size
            else:
                for lid, a in zip(b.leaf_ids, acc[bi]):
                    leaves[lid] = a
        return jax.tree_util.tree_unflatten(p.buckets.treedef, leaves)

    def gate_token_accum(self, acc):
        """`gate_token` over a bucket-layout accumulator (blocking schedule:
        gate the next microbatch on every collective having retired)."""
        p = self.plan
        toks = []
        for bi in range(p.n_buckets):
            if p.fusable[bi]:
                toks.append(acc[bi].reshape(-1)[0])
            else:
                toks.extend(a.reshape(-1)[0] for a in acc[bi])
        if not toks:
            return jnp.zeros((), jnp.float32)
        out = toks[0]
        for t in toks[1:]:
            out = out + t
        return out

    def gate_token(self, grads):
        """A scalar data-dependent on EVERY collective of the exchange.

        The trainer's blocking schedule gates the next microbatch's inputs
        on this, so compute cannot start before the whole exchange retires
        even when prioritization (and with it the engine's own token
        threading) is off. A fused bucket is one collective (its first leaf
        covers it); a non-fusable bucket reduces per leaf, so every leaf
        contributes. Returns a zero scalar for an empty plan."""
        leaves = jax.tree_util.tree_leaves(grads)
        toks = []
        for bi, b in enumerate(self.plan.buckets.buckets):
            ids = b.leaf_ids[:1] if self.plan.fusable[bi] else b.leaf_ids
            toks.extend(leaves[i].reshape(-1)[0] for i in ids)
        if not toks:
            return jnp.zeros((), jnp.float32)
        out = toks[0]
        for t in toks[1:]:
            out = out + t
        return out

    def reduce(self, grads, residuals):
        """The whole exchange as one call (fresh priority chain).

        Returns (reduced_tree, new_residuals)."""
        out, residuals, _ = self.reduce_chained(grads, residuals, None)
        return out, residuals

    # -- observability -------------------------------------------------------

    def stats(self, *, measured=None, topo=None):
        """MLSL-style per-message statistics for this engine's plan
        (repro.obs.stats.CommStats): per-bucket wire bytes by leg/level,
        route, modeled service time on `topo` (defaults to the plan's
        routing topology), and — when `measured` (a per-bucket seconds
        sequence, e.g. obs.stats.measure_bucket_times) is given — the
        measured column. Lazy import keeps core independent of obs."""
        from repro.obs import stats as obs_stats
        return obs_stats.CommStats.from_plan(self.plan, measured=measured,
                                             topo=topo)

    def bucket_timer(self, mesh, *, seed: int = 0):
        """Compile-once per-bucket replay of this engine's reduce path
        (repro.obs.stats.BucketTimer). Building it jits one region per
        bucket; each ``sample()`` afterwards is cheap enough for the
        telemetry loop to run between steps every N steps. Lazy import
        keeps core independent of obs."""
        from repro.obs import stats as obs_stats
        return obs_stats.BucketTimer(self, mesh, seed=seed)
