"""MLSL-style collectives API (the paper's lower-level framework interface).

The paper's library exposes MPI-like collectives but implements the
performance-critical data path itself: asynchronous progress, message
prioritization, and low-precision wire formats. On TPU/JAX the data path is
expressed inside `shard_map` manual regions with `jax.lax` collectives; the
DL-specific optimizations live here:

  * wire-precision selection per collective ("fp32" | "bf16" | "int8"):
    int8 composes reduce_scatter(bf16) -> block-quantize -> all_gather(int8 +
    f32 scales) -> dequantize, cutting gathered wire bytes ~4x vs fp32;
  * optional error-feedback residual for the lossy int8 path;
  * fused/flattened bucket reduction (callers concatenate many small
    gradients into one message -- see repro.core.scheduler).

Everything here assumes it is called INSIDE a shard_map manual region over
`axes` (a name or tuple of names). `Comm.run` wraps a function in such a
region for convenience.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.kernels import ops as kops

WIRE_FP32 = "fp32"
WIRE_BF16 = "bf16"
WIRE_INT8 = "int8"
WIRES = (WIRE_FP32, WIRE_BF16, WIRE_INT8)

QUANT_BLOCK = 512


def wire_bytes_per_elem(wire: str, compute_dtype=jnp.float32) -> float:
    """Bytes that one gradient element occupies on the wire (amortized)."""
    if wire == WIRE_FP32:
        return jnp.dtype(compute_dtype).itemsize
    if wire == WIRE_BF16:
        return 2.0
    if wire == WIRE_INT8:
        # reduce-scatter leg in bf16 (2B/elem over 1 hop-volume) + all-gather
        # leg in int8 (1B/elem) + one f32 scale per QUANT_BLOCK elements.
        return (2.0 + 1.0 + 4.0 / QUANT_BLOCK) / 2.0
    raise ValueError(wire)


def _axes_tuple(axes) -> tuple:
    return (axes,) if isinstance(axes, str) else tuple(axes)


def axis_size(axes) -> int:
    """Product of the manual-axis sizes (callable inside shard_map)."""
    return jax.lax.axis_size(_axes_tuple(axes))


def _pad_flat(flat: jax.Array, quantum: int) -> jax.Array:
    n = flat.shape[0]
    padded = ((n + quantum - 1) // quantum) * quantum
    return jnp.pad(flat, (0, padded - n))


def allreduce(x: jax.Array, axes, *, wire: str = WIRE_FP32,
              mean: bool = False, backend: str = "auto", fused: bool = True,
              acc: jax.Array | None = None) -> jax.Array:
    """Allreduce with a selectable wire precision. Shape-preserving.

    `backend` selects the quantization kernels for the int8 wire and flows
    from the single kernels/ops.py policy (`kops.wire_backend`; the
    CommEngine resolves it once and records it in the EnginePlan). `fused`
    runs the single-pass kernels (set False only to measure the composed
    data path). `acc` (same shape as x, f32) fuses the gather-side
    accumulate: the reduced message is added into `acc` and the sum
    returned -- on the int8 wire via `dequantize_accumulate` so the gathered
    message is consumed in one pass.
    """
    ax = _axes_tuple(axes)
    p = axis_size(ax)
    if wire == WIRE_INT8:
        return _allreduce_int8(x, ax, mean=mean, backend=backend,
                               fused=fused, acc=acc)
    if wire == WIRE_FP32:
        out = lax.psum(x, ax)
    elif wire == WIRE_BF16:
        out = lax.psum(x.astype(jnp.bfloat16), ax).astype(x.dtype)
    else:
        raise ValueError(wire)
    if mean:
        out = out / p
    if acc is not None:
        out = acc.reshape(x.shape) + out
    return out


def _gather_quantized(q: jax.Array, s: jax.Array, ax: tuple):
    for a in reversed(ax):         # gather back in reverse scatter order
        q = lax.all_gather(q, a, axis=0, tiled=True)
        s = lax.all_gather(s, a, axis=0, tiled=True)
    return q, s


def _dequant_full(q, s, meta, n_full: int, *, size: int, shape, out_dtype,
                  mean_div: int, backend: str, acc):
    """Gather-side dequantize of the full (gathered) message.

    The mean is folded into the per-block scale vector (n/QUANT_BLOCK
    elements) instead of dividing the full-size dequantized message -- one
    full HBM pass saved. With `acc`, the dequantize accumulates directly
    into the f32 accumulator (quant8.dequantize_accumulate_blocks), so the
    gathered int8 message is read once and the sum written once."""
    if mean_div > 1:
        s = s / mean_div
    full_meta = dataclasses.replace(meta, shape=(n_full,), n=n_full,
                                    dtype=jnp.float32)
    if acc is not None:
        out = kops.dequantize_accumulate(q, s, acc.reshape(-1), full_meta,
                                         backend=backend)
        return out[:size].reshape(shape)          # stays f32 (acc's dtype)
    deq = kops.dequantize(q, s, full_meta, backend=backend)
    return deq[:size].reshape(shape).astype(out_dtype)


def _allreduce_int8(x: jax.Array, ax: tuple, *, mean: bool = False,
                    backend: str = "auto", fused: bool = True,
                    acc: jax.Array | None = None) -> jax.Array:
    """reduce_scatter(bf16) + quantize + all_gather(int8) + dequantize."""
    orig_dtype = x.dtype
    flat = x.reshape(-1).astype(jnp.bfloat16)
    p = axis_size(ax)
    # shard must be a whole number of (TILE_ROWS x block) quantization rows
    quantum = p * QUANT_BLOCK * 8  # kernels.quant8.TILE_ROWS == 8
    flat = _pad_flat(flat, quantum)
    shard = flat
    for a in ax:                   # sequential scatter over each axis
        shard = lax.psum_scatter(shard, a, scatter_dimension=0, tiled=True)
    if fused:
        # wire cast folded into the quantize tile: the bf16 shard is
        # consumed directly, no materialized f32 copy
        q, s, meta = kops.quantize(shard, block=QUANT_BLOCK, backend=backend)
    else:
        q, s, meta = kops.quantize(shard.astype(jnp.float32),
                                   block=QUANT_BLOCK, backend=backend)
    q, s = _gather_quantized(q, s, ax)
    return _dequant_full(q, s, meta, flat.shape[0], size=x.size,
                         shape=x.shape, out_dtype=orig_dtype,
                         mean_div=p if mean else 1, backend=backend, acc=acc)


def allreduce_ef(x: jax.Array, residual: jax.Array, axes, *,
                 mean: bool = False, backend: str = "auto",
                 fused: bool = True, acc: jax.Array | None = None):
    """int8 allreduce with error feedback.

    `residual` has the shape of this rank's reduce-scatter shard (see
    `ef_residual_shape`); the quantization error of the local shard is
    carried into the next call, making the compression unbiased over time
    (1-bit-SGD / DGC style -- paper refs [5,13,16]).

    The fabric leg reads and writes the gradient shard exactly once per
    direction: quantize-side, `kops.quantize_ef` consumes the bf16 wire
    shard and the f32 residual in one pass (cast + error-feedback add +
    quantize + residual update fused); gather-side, the mean folds into the
    scale vector and `acc` accumulates through `dequantize_accumulate`.
    `fused=False` runs the composed passes (same kernels, separate trips) --
    bit-identical at fp32, kept for the fused-vs-unfused tests/benchmarks.
    Returns (reduced, new_residual).
    """
    orig_dtype = x.dtype
    ax = _axes_tuple(axes)
    p = axis_size(ax)
    flat = x.reshape(-1).astype(jnp.bfloat16)
    quantum = p * QUANT_BLOCK * 8
    flat = _pad_flat(flat, quantum)
    shard = flat
    for a in ax:
        shard = lax.psum_scatter(shard, a, scatter_dimension=0, tiled=True)
    if fused:
        q, s, meta, new_residual = kops.quantize_ef(
            shard, residual, block=QUANT_BLOCK, backend=backend)
    else:
        # composed reference path: separate cast/add, quantize, and
        # residual-update trips; the residual still routes through the
        # fused dequantize_accumulate kernel (y + q * (-s) == y - q * s
        # bitwise), so both paths agree bit-for-bit at fp32
        y = shard.astype(jnp.float32) + residual
        q, s, meta = kops.quantize(y, block=QUANT_BLOCK, backend=backend)
        new_residual = kops.dequantize_accumulate(q, -s, y, meta,
                                                  backend=backend)
    q, s = _gather_quantized(q, s, ax)
    out = _dequant_full(q, s, meta, flat.shape[0], size=x.size,
                        shape=x.shape, out_dtype=orig_dtype,
                        mean_div=p if mean else 1, backend=backend, acc=acc)
    return out, new_residual


def ef_residual_shape(n_elems: int, p: int) -> tuple:
    """Shape of the error-feedback residual for an n_elems bucket on p ranks."""
    quantum = p * QUANT_BLOCK * 8
    padded = ((n_elems + quantum - 1) // quantum) * quantum
    return (padded // p,)


def reduce_scatter(x: jax.Array, axes, *, wire: str = WIRE_FP32) -> jax.Array:
    ax = _axes_tuple(axes)
    y = x.astype(jnp.bfloat16) if wire == WIRE_BF16 else x
    for a in ax:
        y = lax.psum_scatter(y, a, scatter_dimension=0, tiled=True)
    return y.astype(x.dtype)


def all_gather(x: jax.Array, axes, *, axis: int = 0) -> jax.Array:
    y = x
    for a in reversed(_axes_tuple(axes)):
        y = lax.all_gather(y, a, axis=axis, tiled=True)
    return y


def all_to_all(x: jax.Array, axes, *, split_axis: int,
               concat_axis: int) -> jax.Array:
    ax = _axes_tuple(axes)
    assert len(ax) == 1, "all_to_all over a single mesh axis"
    return lax.all_to_all(x, ax[0], split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def broadcast(x: jax.Array, axes, *, root: int = 0) -> jax.Array:
    """Broadcast rank `root`'s value (implemented as masked psum)."""
    ax = _axes_tuple(axes)
    idx = lax.axis_index(ax)
    mask = (idx == root).astype(x.dtype)
    return lax.psum(x * mask, ax)


# --- activation exchange for tensor/model parallelism (hybrid execution) -----
#
# The Megatron-style conjugate operator pair: a model-sharded block wraps its
# projections as
#
#     y = tp_psum(h @ W_out_shard, axis)   where   h = act(tp_replicate(x,
#     axis) @ W_in_shard)
#
# `tp_replicate` (the "f" operator) is identity in the forward pass and psums
# the cotangent in the backward pass — the residual stream enters replicated
# and its gradient must re-synchronize after each rank back-propagated only
# through its own head/feature shard. `tp_psum` ("g") is the conjugate: psum
# forward (the out-projection computes a partial sum over the sharded
# contraction dim), identity backward (the incoming cotangent is already
# replicated). Together they keep every residual-stream activation AND its
# gradient replicated across the model group while weights stay sharded.
#
# Both directions are written out explicitly via custom_vjp: inside the
# fully-manual shard_map regions this repo uses (check_vma=False), the
# built-in transpose of a bare lax.psum does NOT produce the
# replicated-input gradient this pattern needs (tests/test_hybrid.py pins
# the correct values against a dense single-rank reference).

@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def tp_replicate(x: jax.Array, axes) -> jax.Array:
    """f operator: identity forward; backward psums the cotangent over `axes`.

    Place on a replicated activation entering model-sharded projections."""
    del axes
    return x


def _tp_replicate_fwd(x, axes):
    del axes
    return x, None


def _tp_replicate_bwd(axes, _, ct):
    return (lax.psum(ct, _axes_tuple(axes)),)


tp_replicate.defvjp(_tp_replicate_fwd, _tp_replicate_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def tp_psum(x: jax.Array, axes) -> jax.Array:
    """g operator: psum forward (combine per-shard partial sums); identity
    backward (the cotangent arrives replicated across the model group)."""
    return lax.psum(x, _axes_tuple(axes))


def _tp_psum_fwd(x, axes):
    return lax.psum(x, _axes_tuple(axes)), None


def _tp_psum_bwd(axes, _, ct):
    del axes
    return (ct,)


tp_psum.defvjp(_tp_psum_fwd, _tp_psum_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def tp_psum_scatter(x: jax.Array, axes) -> jax.Array:
    """g operator in the bandwidth-optimal psum_scatter + all_gather form.

    Numerically identical to `tp_psum` but decomposed the way a ring
    allreduce is: each rank combines 1/g of the trailing feature dim, then
    the shards are gathered back. Requires the trailing dim to divide by the
    group size."""
    return _psum_scatter_gather(x, _axes_tuple(axes))


def _psum_scatter_gather(x, ax):
    dim = x.ndim - 1
    y = x
    for a in ax:
        y = lax.psum_scatter(y, a, scatter_dimension=dim, tiled=True)
    for a in reversed(ax):
        y = lax.all_gather(y, a, axis=dim, tiled=True)
    return y


def _tp_psum_scatter_fwd(x, axes):
    return _psum_scatter_gather(x, _axes_tuple(axes)), None


def _tp_psum_scatter_bwd(axes, _, ct):
    del axes
    return (ct,)


tp_psum_scatter.defvjp(_tp_psum_scatter_fwd, _tp_psum_scatter_bwd)


@dataclasses.dataclass(frozen=True)
class TPComm:
    """Activation-exchange communicator for one model-parallel mesh axis.

    The CommEngine hands this out (``engine.tp``) when its plan carries a
    tensor-parallel axis, so model code and the gradient-bucket path share
    one comm surface; the f/g ops are also callable directly
    (`tp_replicate` / `tp_psum`)."""

    axis: str

    def replicate(self, x: jax.Array) -> jax.Array:
        return tp_replicate(x, self.axis)

    def psum(self, x: jax.Array, *, scatter: bool = False) -> jax.Array:
        if scatter:
            return tp_psum_scatter(x, self.axis)
        return tp_psum(x, self.axis)

    @property
    def size(self) -> int:
        return axis_size(self.axis)


@dataclasses.dataclass(frozen=True)
class Comm:
    """A communicator bound to a mesh + manual axes (MLSL 'distribution').

    `data_axes` are the gradient-reduction axes (data parallel dimension);
    `model_axis` is the node-group axis used for model/hybrid parallelism.
    When the data-parallel dimension is factored over the machine hierarchy,
    `node_axis`/`local_axis` name the inter-node (fabric) and intra-node
    (fast link) axes and `allreduce` routes through the two-level path
    (repro.core.hier) with per-level wire precision.
    """

    mesh: jax.sharding.Mesh
    data_axes: tuple
    model_axis: str | None = "model"
    node_axis: str | None = None       # inter-node fabric axis
    local_axis: str | None = None      # intra-node fast-link axis

    def run(self, fn: Callable, in_specs, out_specs, *args,
            extra_manual_axes: Sequence[str] = ()):
        """Run `fn` manually over the data axes (model axis stays GSPMD)."""
        manual = set(self.data_axes) | set(extra_manual_axes)
        wrapped = jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                                out_specs=out_specs, axis_names=manual,
                                check_vma=False)
        return wrapped(*args)

    @property
    def data_parallel_size(self) -> int:
        size = 1
        for a in self.data_axes:
            size *= self.mesh.shape[a]
        return size

    @property
    def model_parallel_size(self) -> int:
        if self.model_axis is None:
            return 1
        return self.mesh.shape[self.model_axis]

    # -- machine-hierarchy awareness ---------------------------------------

    @property
    def hierarchical(self) -> bool:
        """True when the data axes are factored over the node hierarchy."""
        return (self.node_axis is not None and self.local_axis is not None
                and self.node_axis in self.data_axes
                and self.local_axis in self.data_axes)

    @property
    def node_size(self) -> int:
        return self.mesh.shape[self.node_axis] if self.node_axis else 1

    @property
    def local_size(self) -> int:
        return self.mesh.shape[self.local_axis] if self.local_axis else 1

    def hier_spec(self, *, wire_intra: str = WIRE_FP32,
                  wire_inter: str = WIRE_FP32, error_feedback: bool = False):
        from repro.core import hier as hier_lib
        assert self.hierarchical, (self.node_axis, self.local_axis,
                                   self.data_axes)
        return hier_lib.HierSpec(node_axis=self.node_axis,
                                 local_axis=self.local_axis,
                                 wire_intra=wire_intra,
                                 wire_inter=wire_inter,
                                 error_feedback=error_feedback)

    def allreduce(self, x: jax.Array, *, wire: str = WIRE_FP32,
                  wire_intra: str | None = None,
                  mean: bool = False) -> jax.Array:
        """Gradient allreduce over the data axes (callable inside `run`).

        On a hierarchical communicator this is the two-level path: `wire`
        selects the fabric leg, `wire_intra` the intra-node legs (defaults
        to bf16 when the fabric is lossy, fp32 otherwise).
        """
        if not self.hierarchical:
            return allreduce(x, self.data_axes, wire=wire, mean=mean)
        from repro.core import hier as hier_lib
        if wire_intra is None:
            wire_intra = hier_lib.default_wire_intra(wire)
        spec = self.hier_spec(wire_intra=wire_intra, wire_inter=wire)
        return hier_lib.hier_allreduce(x, spec, mean=mean)
