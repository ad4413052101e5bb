"""Hierarchical two-level collectives (machine-hierarchy-aware scale-out).

MLSL's core scale-out insight (paper §3, and You et al. 1708.02983) is that
communication must be organized around the machine's hierarchy: chips inside
a node share a cheap high-bandwidth link, nodes talk over an expensive
fabric. A flat ring allreduce over p = nodes x local ranks pushes the full
gradient volume through the slow fabric; the two-level decomposition

    intra-node reduce-scatter  (local axis, fast link, full volume)
    inter-node allreduce       (node axis, slow fabric, volume / local_size)
    intra-node all-gather      (local axis, fast link, full volume)

moves only 1/local_size of the bytes across the fabric, and lets the
DL-specific optimizations be chosen PER LEVEL: the intra legs run at bf16 (or
fp32 for bit-exactness) while the fabric leg can run the int8 block-quantized
wire with optional error feedback (repro.kernels.quant8 via
repro.core.collectives).

Everything here runs INSIDE a shard_map manual region over both axes, same
contract as repro.core.collectives. The cost model the planner/simulator use
to choose flat vs hierarchical lives in repro.core.hw
(``hier_allreduce_time``) and repro.core.planner (``choose_allreduce_algo``).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import collectives as cl
from repro.kernels import ops as kops

NODE_AXIS = "node"      # inter-node (fabric) mesh axis
LOCAL_AXIS = "local"    # intra-node (high-bandwidth) mesh axis

# Intra-node legs must REDUCE in transit, so only real float wire formats are
# legal there; the lossy int8 wire is gather-only and belongs on the fabric.
INTRA_WIRES = (cl.WIRE_FP32, cl.WIRE_BF16)


@dataclasses.dataclass(frozen=True)
class HierSpec:
    """Axis factoring + per-leg wire precision of a two-level allreduce."""

    node_axis: str = NODE_AXIS
    local_axis: str = LOCAL_AXIS
    wire_intra: str = cl.WIRE_FP32     # reduce-scatter / all-gather legs
    wire_inter: str = cl.WIRE_FP32     # fabric allreduce leg
    error_feedback: bool = False       # int8 fabric leg only
    # quantization-kernel dispatch for the int8 fabric leg: resolved through
    # the single kernels/ops.py policy (kops.wire_backend) -- the CommEngine
    # resolves "auto" once at plan-build time and records the choice.
    backend: str = "auto"
    fused: bool = True                 # single-pass kernels (False: composed)

    def __post_init__(self):
        if self.wire_intra not in INTRA_WIRES:
            raise ValueError(
                f"intra-node wire must be one of {INTRA_WIRES}, got "
                f"{self.wire_intra!r} (int8 is gather-only; use it on the "
                f"inter-node leg)")
        if self.wire_inter not in cl.WIRES:
            raise ValueError(self.wire_inter)
        if self.error_feedback and self.wire_inter != cl.WIRE_INT8:
            raise ValueError("error feedback requires the int8 fabric leg")
        if self.backend != "auto" and self.backend not in kops.BACKENDS:
            raise ValueError(
                f"unknown quantization backend {self.backend!r}")


def default_wire_intra(wire_inter: str) -> str:
    """Intra-node legs default to fp32 for a lossless fabric (bit-exactness)
    and bf16 once the fabric leg is lossy anyway. The single source of this
    policy for Comm.allreduce and trainer.CommConfig."""
    return cl.WIRE_FP32 if wire_inter == cl.WIRE_FP32 else cl.WIRE_BF16


def _pad_quantum(local: int, node: int, wire_inter: str) -> int:
    """Flat-message padding so both legs tile evenly.

    The intra scatter needs local | n; the int8 fabric leg additionally needs
    the per-rank shard to be whole (TILE_ROWS x QUANT_BLOCK) quantization
    rows per node rank (see collectives._allreduce_int8), so pad once here
    and the inner allreduce never re-pads.
    """
    if wire_inter == cl.WIRE_INT8:
        return local * node * cl.QUANT_BLOCK * 8
    return local


def hier_allreduce(x: jax.Array, spec: HierSpec = HierSpec(), *,
                   mean: bool = False,
                   acc: jax.Array | None = None) -> jax.Array:
    """Two-level allreduce; shape- and dtype-preserving.

    Equivalent to ``collectives.allreduce(x, (node_axis, local_axis))`` but
    with the fabric leg carrying 1/local_size of the volume and each leg's
    wire precision independently selectable. The int8 fabric leg consumes
    the wire-dtype shard directly (cast folded into the quantize tile --
    no materialized cast copy between the legs). `acc` (f32, x's shape)
    accumulates the reduced result into an existing buffer.
    """
    orig_dtype = x.dtype
    local = cl.axis_size(spec.local_axis)
    node = cl.axis_size(spec.node_axis)
    p = local * node

    wire_dtype = jnp.bfloat16 if spec.wire_intra == cl.WIRE_BF16 \
        else jnp.float32
    flat = x.reshape(-1).astype(wire_dtype)
    flat = cl._pad_flat(flat, _pad_quantum(local, node, spec.wire_inter))

    # leg 1: intra-node reduce-scatter over the fast link
    with jax.named_scope(f"hier/intra_rs_{spec.wire_intra}"):
        shard = lax.psum_scatter(flat, spec.local_axis, scatter_dimension=0,
                                 tiled=True)
    # leg 2: inter-node allreduce over the fabric, 1/local of the volume
    with jax.named_scope(f"hier/inter_allreduce_{spec.wire_inter}"):
        shard = cl.allreduce(shard, (spec.node_axis,), wire=spec.wire_inter,
                             backend=spec.backend, fused=spec.fused)
    # leg 3: intra-node all-gather over the fast link
    with jax.named_scope(f"hier/intra_ag_{spec.wire_intra}"):
        out = lax.all_gather(shard, spec.local_axis, axis=0, tiled=True)

    out = out[: x.size].reshape(x.shape).astype(orig_dtype)
    if mean:
        out = out / p
    if acc is not None:
        out = acc.reshape(x.shape) + out
    return out


def hier_allreduce_ef(x: jax.Array, residual: jax.Array,
                      spec: HierSpec = HierSpec(wire_inter=cl.WIRE_INT8,
                                                error_feedback=True), *,
                      mean: bool = False, acc: jax.Array | None = None):
    """Two-level allreduce with error feedback on the int8 fabric leg.

    ``residual`` has shape ``ef_residual_shape(x.size, local, node)`` -- the
    per-rank quantization error of this rank's fabric shard, carried into the
    next call (1-bit-SGD style unbiasing, applied only where the lossy wire
    is: the fabric). The fabric leg runs the fused quantize+error-feedback
    kernel per `spec.backend`/`spec.fused`. Returns (reduced, new_residual).
    """
    assert spec.wire_inter == cl.WIRE_INT8, spec
    orig_dtype = x.dtype
    local = cl.axis_size(spec.local_axis)
    node = cl.axis_size(spec.node_axis)
    p = local * node

    wire_dtype = jnp.bfloat16 if spec.wire_intra == cl.WIRE_BF16 \
        else jnp.float32
    flat = x.reshape(-1).astype(wire_dtype)
    flat = cl._pad_flat(flat, _pad_quantum(local, node, spec.wire_inter))

    with jax.named_scope(f"hier/intra_rs_{spec.wire_intra}"):
        shard = lax.psum_scatter(flat, spec.local_axis, scatter_dimension=0,
                                 tiled=True)
    with jax.named_scope("hier/inter_allreduce_int8_ef"):
        shard, new_residual = cl.allreduce_ef(shard, residual,
                                              (spec.node_axis,),
                                              backend=spec.backend,
                                              fused=spec.fused)
    with jax.named_scope(f"hier/intra_ag_{spec.wire_intra}"):
        out = lax.all_gather(shard, spec.local_axis, axis=0, tiled=True)

    out = out[: x.size].reshape(x.shape).astype(orig_dtype)
    if mean:
        out = out / p
    if acc is not None:
        out = acc.reshape(x.shape) + out
    return out, new_residual


def ef_residual_shape(n_elems: int, local: int, node: int) -> tuple:
    """Residual shape for an n_elems bucket on a (node, local) factoring.

    The residual lives on the fabric shard: n padded to the two-level
    quantum, divided by local (intra scatter) and by node (fabric scatter).
    """
    quantum = _pad_quantum(local, node, cl.WIRE_INT8)
    padded = ((n_elems + quantum - 1) // quantum) * quantum
    return (padded // (local * node),)


# --------------------------------------------------------------------------
# Wire-byte accounting (what the fabric actually carries)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class WireBytes:
    """Amortized bytes one gradient element occupies, split by level."""

    intra: float        # bytes/elem over the intra-node link
    inter: float        # bytes/elem over the inter-node fabric
    total: float


def hier_wire_bytes_per_elem(spec: HierSpec, local: int,
                             node: int) -> WireBytes:
    """Per-element wire bytes of the two-level path, by level.

    Uses the same amortized convention as ``collectives.wire_bytes_per_elem``
    (bytes of the full message per leg, averaged over the two intra legs).
    The fabric leg only carries n/local elements, so its per-element cost is
    the flat wire cost divided by local -- the hierarchy's headline saving.
    """
    isz = 2.0 if spec.wire_intra == cl.WIRE_BF16 else 4.0
    intra = (isz + isz) / 2.0 if local > 1 else 0.0   # RS leg + AG leg
    inter = (cl.wire_bytes_per_elem(spec.wire_inter) / local
             if node > 1 else 0.0)
    return WireBytes(intra=intra, inter=inter, total=intra + inter)


def flat_wire_bytes_per_elem(wire: str) -> WireBytes:
    """Flat single-level allreduce in the same accounting: every byte of the
    message crosses the fabric (the ring spans all p ranks)."""
    b = cl.wire_bytes_per_elem(wire)
    return WireBytes(intra=0.0, inter=b, total=b)
