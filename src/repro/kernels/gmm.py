"""Grouped matrix products over the experts a chip holds (Pallas, TPU).

The rows of `lhs` are sorted by group (expert): group i owns rows
offsets[i]:offsets[i+1], offsets = [0, cumsum(group_sizes)]. Rows past
sum(group_sizes) are padding: a static buffer sized for the worst case.

  gmm(lhs (m, k), rhs (g, k, n), sizes)  -> (m, n)    out[rows of i] = lhs @ rhs[i]
  tgmm(lhs (m, k), rhs (m, n), sizes)    -> (g, k, n) out[i] = lhs[rows of i]^T rhs[rows of i]

Both kernels walk only the m-tiles that hold a group's rows: the grid's
row dimension is the count of active tiles, known at run time, so the
work follows the routed rows and not the buffer. A tile that two groups
share is visited once per group and each visit stores its own rows. The
tile metadata is that of JAX's megablox kernels
(jax.experimental.pallas.ops.tpu.megablox), whose design these follow.

`grouped_matmul` is the differentiable product (custom_vjp): forward
`moe_gmm`, backward `moe_gmm` against the transposed weights for the rows
and `moe_tgmm` for the weights. Its padding rows come out as zero, in both
directions. Each pallas_call carries its name, which is what a chip trace
calls the custom call.

Backends: "pallas" (compiled, TPU), "interpret" (the same kernels under
the Pallas interpreter, for CPU tests) and "ragged" (jax.lax.ragged_dot, the
CPU path of the model).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

GMM_NAME = "moe_gmm"
TGMM_NAME = "moe_tgmm"
BACKENDS = ("pallas", "interpret", "ragged")
TILE_M = 256                 # rows per tile: a few partial tiles per group
MAX_TILE = 2048              # a k or n dim up to this is one tile
VMEM_LIMIT = 96 * 1024 * 1024


@dataclasses.dataclass(frozen=True)
class Spec:
    """How a grouped product runs: backend and the row tile."""

    backend: str = "pallas"
    tile_m: int = TILE_M

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(f"unknown grouped-matmul backend "
                             f"{self.backend!r}; expected one of {BACKENDS}")


def _tile(dim: int, want: int = MAX_TILE) -> int:
    """The whole dim if it fits a tile, else its largest divisor that is a
    multiple of 128 and at most `want`."""
    if dim <= want:
        return dim
    for t in range(want - want % 128, 0, -128):
        if dim % t == 0:
            return t
    raise ValueError(f"no tile of at most {want} divides {dim}")


def _row_tile(m: int, tile_m: int) -> int:
    return min(tile_m, -(-m // 8) * 8)


def _pad_rows(x, tm: int):
    pad = (-x.shape[0]) % tm
    return jnp.pad(x, ((0, pad), (0, 0))) if pad else x


def _group_rows(meta, grid_id, tm: int, shape):
    """Mask of the tile's rows that belong to grid step `grid_id`'s group."""
    offsets, group_ids, m_tile_ids = meta
    gid = group_ids[grid_id]
    row = m_tile_ids[grid_id] * tm + jax.lax.broadcasted_iota(
        jnp.int32, shape, 0)
    return (row >= offsets[gid]) & (row < offsets[gid + 1])


def _gmm(lhs, rhs, sizes, *, transpose_rhs: bool, interpret: bool,
         tile_m: int):
    """(m, k) x (g, k, n) [or (g, n, k) transposed] -> (m, n) in lhs's
    dtype; rows of no group are left unwritten."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tk, tn = _row_tile(m, tile_m), _tile(k), _tile(n)
    lhs = _pad_rows(lhs, tm)
    mp = lhs.shape[0]
    tiles_k, tiles_n = k // tk, n // tn
    meta, active = make_group_metadata(
        group_sizes=sizes, m=mp, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=rhs.shape[0], visit_empty_groups=False)
    dims = (((1,), (1,)), ((), ())) if transpose_rhs else \
        (((1,), (0,)), ((), ()))

    def kernel(meta_ref, lhs_ref, rhs_ref, out_ref, acc_ref):
        grid_id, k_i = pl.program_id(1), pl.program_id(2)

        @pl.when(k_i == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jax.lax.dot_general(
            lhs_ref[...], rhs_ref[...], dims,
            preferred_element_type=jnp.float32)

        @pl.when(k_i == tiles_k - 1)
        def _():
            mine = _group_rows(meta_ref, grid_id, tm, (tm, tn))
            out_ref[...] = jnp.where(
                mine, acc_ref[...], out_ref[...].astype(jnp.float32)
            ).astype(out_ref.dtype)

    def lhs_map(n_i, grid_id, k_i, meta):
        return meta[2][grid_id], k_i

    def rhs_map(n_i, grid_id, k_i, meta):
        g = meta[1][grid_id]
        return (g, n_i, k_i) if transpose_rhs else (g, k_i, n_i)

    def out_map(n_i, grid_id, k_i, meta):
        return meta[2][grid_id], n_i

    rhs_block = (None, tn, tk) if transpose_rhs else (None, tk, tn)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((mp, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec(rhs_block, rhs_map)],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            grid=(tiles_n, active, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=GMM_NAME,
    )(meta, lhs, rhs)
    return out[:m]


def _tgmm(lhs, rhs, sizes, num_groups: int, *, interpret: bool,
          tile_m: int, out_dtype):
    """(m, k), (m, n) -> (g, k, n): per group, its rows of lhs transposed
    times its rows of rhs; a group with no rows gets zeros."""
    m, k = lhs.shape
    n = rhs.shape[1]
    tm, tk, tn = _row_tile(m, tile_m), _tile(k, 1024), _tile(n)
    lhs, rhs = _pad_rows(lhs, tm), _pad_rows(rhs, tm)
    meta, active = make_group_metadata(
        group_sizes=sizes, m=lhs.shape[0], tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=num_groups, visit_empty_groups=True)

    def kernel(meta_ref, lhs_ref, rhs_ref, out_ref, acc_ref):
        grid_id = pl.program_id(2)
        offsets, group_ids = meta_ref[0], meta_ref[1]
        group = group_ids[grid_id]
        first = jnp.logical_or(
            grid_id == 0, group_ids[jnp.maximum(grid_id - 1, 0)] != group)
        last_step = grid_id == pl.num_programs(2) - 1
        last = jnp.logical_or(
            last_step,
            group_ids[jnp.where(last_step, grid_id, grid_id + 1)] != group)

        @pl.when(first)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(offsets[group + 1] > offsets[group])
        def _():
            a = jnp.where(_group_rows(meta_ref, grid_id, tm, (tm, tk)),
                          lhs_ref[...].astype(jnp.float32), 0.0)
            b = jnp.where(_group_rows(meta_ref, grid_id, tm, (tm, tn)),
                          rhs_ref[...].astype(jnp.float32), 0.0)
            acc_ref[...] += jax.lax.dot(
                a.T.astype(lhs_ref.dtype), b.astype(rhs_ref.dtype),
                preferred_element_type=jnp.float32)

        @pl.when(last)
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    def lhs_map(n_i, k_i, grid_id, meta):
        return meta[2][grid_id], k_i

    def rhs_map(n_i, k_i, grid_id, meta):
        return meta[2][grid_id], n_i

    def out_map(n_i, k_i, grid_id, meta):
        return meta[1][grid_id], k_i, n_i

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((num_groups, k, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec((tm, tn), rhs_map)],
            out_specs=pl.BlockSpec((None, tk, tn), out_map),
            grid=(n // tn, k // tk, active),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=TGMM_NAME,
    )(meta, lhs, rhs)


def _zero_padding(out, sizes):
    rows = jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
    return jnp.where(rows < jnp.sum(sizes), out, jnp.zeros((), out.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped(lhs, rhs, sizes, spec: Spec):
    return _zero_padding(
        _gmm(lhs, rhs, sizes, transpose_rhs=False,
             interpret=spec.backend == "interpret", tile_m=spec.tile_m),
        sizes)


def _grouped_fwd(lhs, rhs, sizes, spec):
    return _grouped(lhs, rhs, sizes, spec), (lhs, rhs, sizes)


def _grouped_bwd(spec, res, g):
    lhs, rhs, sizes = res
    interpret = spec.backend == "interpret"
    d_lhs = _zero_padding(
        _gmm(g, rhs, sizes, transpose_rhs=True, interpret=interpret,
             tile_m=spec.tile_m), sizes)
    d_rhs = _tgmm(lhs, g, sizes, rhs.shape[0], interpret=interpret,
                  tile_m=spec.tile_m, out_dtype=rhs.dtype)
    return d_lhs, d_rhs, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(lhs, rhs, sizes, spec: Spec = Spec()):
    """lhs (m, k) rows sorted by group, rhs (g, k, n), sizes (g,) int32 ->
    (m, n) in lhs's dtype (f32 accumulation); the rows past sum(sizes)
    are zero. Differentiable in lhs and rhs."""
    sizes = sizes.astype(jnp.int32)
    if spec.backend == "ragged":
        return jax.lax.ragged_dot(lhs, rhs, sizes,
                                  preferred_element_type=jnp.float32
                                  ).astype(lhs.dtype)
    return _grouped(lhs, rhs, sizes, spec)


def backend_for(platform: str) -> str:
    """The compiled kernels on a TPU, jax.lax.ragged_dot elsewhere."""
    return "pallas" if platform == "tpu" else "ragged"
