"""Public, shape-polymorphic wrappers over the quantization kernels.

`quantize`/`quantize_ef`/`dequantize` accept arbitrary-shaped tensors: they
flatten, pad to the kernel's (TILE_ROWS x block) tiling, and restore shape on
the way back. The wire cast is folded into the kernels (both backends cast
on the tile/oracle side), so bf16 wire buffers are consumed directly without
a materialized f32 copy.

Backend policy (`wire_backend`, the single policy every comm call site
resolves through -- repro.core.collectives/hier take a ``backend`` argument
and the CommEngine resolves it once, against the platform of its mesh, and
records the choice in its EnginePlan):

  * "pallas"    -- pl.pallas_call compiled by Mosaic (TPU targets only).
  * "interpret" -- the same kernels under the Pallas interpreter; validates
                   them on CPU but is far slower than XLA.
  * "jnp"       -- the pure-jnp oracle (identical math; used inside GSPMD-
                   partitioned regions and as the CPU default).
  * "auto"      -- resolved by `wire_backend`: the compiled kernels on a TPU
                   target; elsewhere the REPRO_QUANT_BACKEND env var
                   ("pallas" runs the interpreted kernels) or jnp.

A TPU target always gets the compiled kernels: neither the oracle nor the
interpreter is ever chosen for it.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels import quant8, ref


@dataclasses.dataclass(frozen=True)
class QuantMeta:
    """Static metadata needed to invert `quantize`."""

    shape: tuple
    dtype: Any
    n: int                # true element count before padding
    block: int


@dataclasses.dataclass(frozen=True)
class PadInfo:
    """Padding a flat buffer pays to reach the (TILE_ROWS x block) tiling.

    `waste_frac` is large only for tiny buckets (n < TILE_ROWS * block): the
    engine records it per bucket (EnginePlan.quant_pad) so undersized int8
    buckets are visible in the plan instead of silently shipping padding."""

    n: int                # true element count
    padded: int           # elements after padding
    waste_elems: int

    @property
    def waste_frac(self) -> float:
        return self.waste_elems / max(self.padded, 1)


def pad_info(n: int, block: int = quant8.DEFAULT_BLOCK) -> PadInfo:
    row_quantum = block * quant8.TILE_ROWS
    padded = ((n + row_quantum - 1) // row_quantum) * row_quantum
    return PadInfo(n=n, padded=padded, waste_elems=padded - n)


BACKENDS = ("pallas", "interpret", "jnp")


def wire_backend(requested: str = "auto", platform: str | None = None) -> str:
    """Resolve a requested backend for computations on `platform` devices
    (default: the platform of JAX's default device).

    A TPU target runs the compiled kernels ("auto" and "pallas" resolve to
    "pallas"; asking for the oracle or the interpreter there is an error).
    Elsewhere "pallas" means the interpreted kernels, and "auto" follows
    REPRO_QUANT_BACKEND (default jnp)."""
    if requested != "auto" and requested not in BACKENDS:
        raise ValueError(
            f"unknown quantization backend {requested!r}; expected "
            f"'auto' or one of {BACKENDS}")
    if platform is None:
        platform = jax.devices()[0].platform
    if platform == "tpu":
        if requested in ("jnp", "interpret"):
            raise ValueError(
                f"quantization backend {requested!r} on a TPU target: the "
                f"int8 wire there runs the compiled kernels")
        return "pallas"
    if requested == "auto":
        requested = os.environ.get("REPRO_QUANT_BACKEND", "jnp")
        if requested not in ("pallas", "jnp"):
            requested = "jnp"
    return "interpret" if requested == "pallas" else requested


def _resolve(backend: str) -> str:
    """Direct callers may pass "auto"; a resolved name is taken as given so
    a plan resolved for a TPU mesh keeps the compiled kernels even where
    the host's default device is a CPU (a compile for a described chip)."""
    return backend if backend in BACKENDS else wire_backend(backend)


def _to_blocks(x: jax.Array, block: int, *, pad_to: int | None = None):
    """Flatten + zero-pad to (n_blocks, block) with n_blocks % TILE_ROWS == 0.

    Keeps the input dtype (the kernels cast in-tile; see quantize_cast_blocks)
    so a bf16 wire buffer never materializes an f32 copy here. `pad_to`
    overrides the padded length when the buffer must match a mate that was
    padded to a larger collective quantum. Pad waste is reported via
    `pad_info` (the returned count is the true element count `n`)."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    padded = pad_to if pad_to is not None else pad_info(n, block).padded
    flat = jnp.pad(flat, (0, padded - n))
    return flat.reshape(-1, block), n


def quantize(x: jax.Array, *, block: int = quant8.DEFAULT_BLOCK,
             backend: str = "auto"):
    """x (any shape, any float dtype) -> (q int8 (n_blocks, block), scales
    f32, QuantMeta). The cast to f32 happens inside the kernel/oracle."""
    be = _resolve(backend)
    x2d, n = _to_blocks(x, block)
    if be != "jnp":
        q, s = quant8.quantize_cast_blocks(x2d,
                                           interpret=be == "interpret")
    else:
        q, s = ref.quantize_blocks(x2d)
    meta = QuantMeta(shape=tuple(x.shape), dtype=x.dtype, n=n, block=block)
    return q, s, meta


def quantize_ef(x: jax.Array, residual: jax.Array, *,
                block: int = quant8.DEFAULT_BLOCK, backend: str = "auto"):
    """Fused error-feedback quantize: one pass computing

        y = f32(x) + residual;  (q, s) = quantize(y);  new_res = y - q * s

    `residual` must have x's element count (any shape; flattened alongside).
    Returns (q, scales, QuantMeta, new_residual) with new_residual in
    residual's shape. Both backends run the identical expression graph, so
    jnp and (interpret-mode) pallas stay aligned and the jnp path is bitwise
    equal to composing quantize + dequantize_accumulate by hand.
    """
    be = _resolve(backend)
    x2d, n = _to_blocks(x, block)
    r2d, rn = _to_blocks(residual.astype(jnp.float32), block,
                         pad_to=x2d.size)
    if rn != n:
        raise ValueError(
            f"residual has {rn} elements but the input has {n}")
    if be != "jnp":
        q, s, nr = quant8.quantize_ef_blocks(x2d, r2d,
                                             interpret=be == "interpret")
    else:
        q, s, nr = ref.quantize_ef_blocks(x2d, r2d)
    meta = QuantMeta(shape=tuple(x.shape), dtype=x.dtype, n=n, block=block)
    new_residual = nr.reshape(-1)[:n].reshape(residual.shape)
    return q, s, meta, new_residual


def dequantize(q: jax.Array, scales: jax.Array, meta: QuantMeta, *,
               backend: str = "auto") -> jax.Array:
    be = _resolve(backend)
    if be != "jnp":
        x2d = quant8.dequantize_blocks(q, scales, out_dtype=jnp.float32,
                                       interpret=be == "interpret")
    else:
        x2d = ref.dequantize_blocks(q, scales, out_dtype=jnp.float32)
    flat = x2d.reshape(-1)[: meta.n]
    return flat.reshape(meta.shape).astype(meta.dtype)


def dequantize_accumulate(q: jax.Array, scales: jax.Array, acc: jax.Array,
                          meta: QuantMeta, *,
                          backend: str = "auto") -> jax.Array:
    """acc (same logical element count as the original tensor) + dequant(q),
    one fused pass on the gather side. `acc` is padded to q's (possibly
    collective-quantum) blocked size, so callers may hand in the unpadded
    accumulator. The result keeps ACC's dtype (accumulators stay f32 even
    when the quantized tensor was a bf16 wire buffer), reshaped to
    meta.shape."""
    be = _resolve(backend)
    acc2d, _ = _to_blocks(acc, meta.block, pad_to=q.size)
    if be != "jnp":
        x2d = quant8.dequantize_accumulate_blocks(
            q, scales, acc2d, out_dtype=jnp.float32,
            interpret=be == "interpret")
    else:
        x2d = ref.dequantize_accumulate_blocks(q, scales, acc2d,
                                               out_dtype=jnp.float32)
    flat = x2d.reshape(-1)[: meta.n]
    return flat.reshape(meta.shape).astype(acc.dtype)


def quantization_rmse(x: jax.Array, *, block: int = quant8.DEFAULT_BLOCK,
                      backend: str = "jnp") -> jax.Array:
    """Convenience: RMS error of a quantize/dequantize round trip."""
    q, s, meta = quantize(x, block=block, backend=backend)
    xr = dequantize(q, s, meta, backend=backend)
    return jnp.sqrt(jnp.mean((x.astype(jnp.float32) - xr.astype(jnp.float32)) ** 2))
