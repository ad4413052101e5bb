"""Pallas TPU kernels for block-wise symmetric int8 quantization.

This is the performance-critical data-path operation of the paper's
low-precision communication feature (C6): gradients are quantized to int8
with one fp32 scale per block before hitting the wire, and dequantized (and
optionally accumulated) after the collective.

TPU mapping: the gradient bucket is viewed as (n_blocks, block) with
block a multiple of 128 (lane width) so each VMEM tile is MXU/VPU aligned.
Callers pad n_blocks to a multiple of TILE_ROWS; the grid walks row tiles of
`tile_rows(n_blocks)` blocks (up to MAX_TILE_ROWS, the last tile ragged), so
a grid step moves a few MB and its fixed cost stays small against its
bytes. Every row is independent: the rows of a ragged tile past the end are
never written back. Abs-max reduction, scaling and rounding all happen
inside VMEM, one HBM round-trip total -- on CPU the same kernels run under
interpret=True and are validated against ref.py.

Fused wire hot path (one HBM read + one write of the gradient per leg):

  * ``quantize_cast_blocks``   -- bf16/f32 input cast in-tile, so the wire
    cast never materializes an intermediate copy in HBM;
  * ``quantize_ef_blocks``     -- x + residual -> (q, scales, new_residual)
    in a single VMEM pass (the error-feedback add, the quantization, and the
    residual update that used to be 3-4 separate passes);
  * ``dequantize_accumulate_blocks`` -- acc + q * s on the gather side, so
    microbatch gradient accumulation consumes the int8 message directly.

One grid layout serves every input dtype: a tile of MAX_TILE_ROWS rows is
a whole number of native sublane tiles for f32, bf16 and int8 alike, and a
buffer smaller than that is one tile spanning the whole array.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Lane width on TPU is 128; sublane granularity for fp32 is 8.
LANE = 128
DEFAULT_BLOCK = 512          # elements per quantization block (multiple of 128)
TILE_ROWS = 8                # padding quantum: n_blocks is a multiple of it
# Most quantization blocks one grid step takes. The per-block scales are a
# lane-dense (n_blocks,) f32 vector, which XLA tiles by 1024 elements, and a
# rank-1 block must span whole tiles of it (or the whole vector).
MAX_TILE_ROWS = 1024


def tile_rows(n_blocks: int) -> int:
    """Quantization blocks per grid step for an (n_blocks, block) buffer:
    all of a small buffer, else MAX_TILE_ROWS with a ragged last tile."""
    return min(n_blocks, MAX_TILE_ROWS)


def _specs(n_blocks: int, block: int) -> tuple:
    """(row-tile spec, scale-vector spec) of one grid step."""
    tile = tile_rows(n_blocks)
    return (pl.BlockSpec((tile, block), lambda i: (i, 0)),
            pl.BlockSpec((tile,), lambda i: (i,)))


def _quantize_kernel(x_ref, q_ref, s_ref):
    """One tile: (rows, block) float -> int8 + per-row scale.

    The input cast to f32 happens on the VMEM tile, so a bf16 wire buffer is
    consumed directly (no materialized f32 copy in HBM)."""
    x = x_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)    # (rows, 1)
    scale = amax / 127.0
    safe = jnp.where(scale > 0.0, scale, 1.0)
    q = jnp.clip(jnp.round(x / safe), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale.reshape(s_ref.shape)


def _quantize_ef_kernel(x_ref, r_ref, q_ref, s_ref, nr_ref):
    """Fused error-feedback quantize, one tile in VMEM:

        y = f32(x) + residual
        q, scale = blockwise int8 quantization of y
        new_residual = y - q * scale

    What used to be the add / quantize / dequantize-to-get-the-error triple
    (3-4 HBM round-trips in collectives.allreduce_ef) reads x and residual
    once and writes q, scale, new_residual once."""
    y = x_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(y), axis=1, keepdims=True)
    scale = amax / 127.0
    safe = jnp.where(scale > 0.0, scale, 1.0)
    q = jnp.clip(jnp.round(y / safe), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale.reshape(s_ref.shape)
    nr_ref[...] = y - q * scale


def _column(s_ref):
    """The tile's lane-dense scales as a (rows, 1) column."""
    return s_ref[...].reshape(s_ref.shape[0], 1)


def _dequantize_kernel(q_ref, s_ref, o_ref, *, out_dtype):
    q = q_ref[...].astype(jnp.float32)
    o_ref[...] = (q * _column(s_ref)).astype(out_dtype)


def _dequant_accum_kernel(q_ref, s_ref, acc_ref, o_ref, *, out_dtype):
    """Fused dequantize + accumulate: o = acc + q * s (error-feedback path)."""
    q = q_ref[...].astype(jnp.float32)
    acc = acc_ref[...].astype(jnp.float32)
    o_ref[...] = (acc + q * _column(s_ref)).astype(out_dtype)


def _grid(n_blocks: int) -> tuple:
    # ValueError (not assert): the message survives `python -O` and names the
    # offending shape plus the tiling quantum the caller must pad to.
    if n_blocks % TILE_ROWS != 0:
        raise ValueError(
            f"n_blocks={n_blocks} is not a multiple of the row-tile quantum "
            f"TILE_ROWS={TILE_ROWS}; pad the flat buffer to a multiple of "
            f"TILE_ROWS * block elements (see repro.kernels.ops._to_blocks)")
    return (pl.cdiv(n_blocks, tile_rows(n_blocks)),)


def _check_block(shape: tuple) -> None:
    n_blocks, block = shape
    if block % LANE != 0:
        raise ValueError(
            f"block size {block} of a ({n_blocks}, {block}) buffer is not a "
            f"multiple of the TPU lane width LANE={LANE}; quantization "
            f"blocks must tile the 128-lane vector registers")


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_blocks(x2d: jax.Array, *, interpret: bool = False):
    """x2d: (n_blocks, block) f32 -> (int8 (n_blocks, block), f32 (n_blocks,)).

    n_blocks must be a multiple of TILE_ROWS and block a multiple of LANE
    (callers pad; see repro.kernels.ops).
    """
    n_blocks, block = x2d.shape
    _check_block(x2d.shape)
    row_spec, scale_spec = _specs(n_blocks, block)
    return pl.pallas_call(
        _quantize_kernel,
        name="quantize_blocks",
        grid=_grid(n_blocks),
        in_specs=[row_spec],
        out_specs=(row_spec, scale_spec),
        out_shape=(
            jax.ShapeDtypeStruct((n_blocks, block), jnp.int8),
            jax.ShapeDtypeStruct((n_blocks,), jnp.float32),
        ),
        interpret=interpret,
    )(x2d)


# The wire cast is folded into the quantize tile (`_quantize_kernel` casts on
# the VMEM block), so any float input quantizes without a materialized f32
# copy; the separate name documents the contract for bf16 wire buffers.
quantize_cast_blocks = quantize_blocks


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_ef_blocks(x2d: jax.Array, res2d: jax.Array, *,
                       interpret: bool = False):
    """Fused error-feedback quantize (one HBM round-trip).

    x2d: (n_blocks, block) float (bf16 wire buffers welcome -- cast in-tile);
    res2d: (n_blocks, block) f32 residual carried from the previous step.
    Returns (q int8, scales f32 (n_blocks,), new_residual f32) where
    q/scales quantize ``x + res`` and ``new_residual = x + res - q * s``.
    The new residual takes res2d's buffer (updated in place when the caller
    donates it).
    """
    n_blocks, block = x2d.shape
    _check_block(x2d.shape)
    if res2d.shape != x2d.shape:
        raise ValueError(
            f"residual shape {res2d.shape} must match the blocked input "
            f"shape {x2d.shape}")
    row_spec, scale_spec = _specs(n_blocks, block)
    return pl.pallas_call(
        _quantize_ef_kernel,
        name="quantize_ef_blocks",
        grid=_grid(n_blocks),
        in_specs=[row_spec, row_spec],
        out_specs=(row_spec, scale_spec, row_spec),
        out_shape=(
            jax.ShapeDtypeStruct((n_blocks, block), jnp.int8),
            jax.ShapeDtypeStruct((n_blocks,), jnp.float32),
            jax.ShapeDtypeStruct((n_blocks, block), jnp.float32),
        ),
        input_output_aliases={1: 2},
        interpret=interpret,
    )(x2d, res2d)


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def dequantize_blocks(q2d: jax.Array, scales: jax.Array, *,
                      out_dtype=jnp.float32, interpret: bool = False):
    n_blocks, block = q2d.shape
    _check_block(q2d.shape)
    row_spec, scale_spec = _specs(n_blocks, block)
    return pl.pallas_call(
        functools.partial(_dequantize_kernel, out_dtype=out_dtype),
        name="dequantize_blocks",
        grid=_grid(n_blocks),
        in_specs=[row_spec, scale_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((n_blocks, block), out_dtype),
        interpret=interpret,
    )(q2d, scales.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def dequantize_accumulate_blocks(q2d: jax.Array, scales: jax.Array,
                                 acc: jax.Array, *, out_dtype=jnp.float32,
                                 interpret: bool = False):
    n_blocks, block = q2d.shape
    _check_block(q2d.shape)
    row_spec, scale_spec = _specs(n_blocks, block)
    return pl.pallas_call(
        functools.partial(_dequant_accum_kernel, out_dtype=out_dtype),
        name="dequantize_accumulate_blocks",
        grid=_grid(n_blocks),
        in_specs=[row_spec, scale_spec, row_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((n_blocks, block), out_dtype),
        interpret=interpret,
    )(q2d, scales.astype(jnp.float32), acc)
