"""Per-kernel validation: Pallas (interpret mode) vs the pure-jnp oracle,
swept over shapes and dtypes, plus the fused-vs-composed contracts of the
single-pass error-feedback hot path (quantize_ef / dequantize_accumulate)."""

import functools
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import PartitionSpec as P

from repro.kernels import ops, quant8, ref

SHAPES = [(8, 512), (16, 128), (64, 640), (8, 1024)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_quantize_blocks_matches_ref(shape, dtype):
    x = (jax.random.normal(jax.random.PRNGKey(42), shape) * 3).astype(dtype)
    x2 = x.astype(jnp.float32)
    q_p, s_p = quant8.quantize_blocks(x2, interpret=True)
    q_r, s_r = ref.quantize_blocks(x2)
    # interpret-mode XLA may fuse the divide differently; allow 1-LSB
    # rounding-tie differences on a tiny fraction of elements
    diff = np.abs(np.asarray(q_p, np.int32) - np.asarray(q_r, np.int32))
    assert diff.max() <= 1, diff.max()
    assert (diff > 0).mean() < 0.01
    np.testing.assert_allclose(np.asarray(s_p), np.asarray(s_r), rtol=1e-6)


@pytest.mark.parametrize("shape", SHAPES)
def test_dequantize_blocks_matches_ref(shape):
    x = jax.random.normal(jax.random.PRNGKey(1), shape)
    q, s = ref.quantize_blocks(x)
    d_p = quant8.dequantize_blocks(q, s, interpret=True)
    d_r = ref.dequantize_blocks(q, s)
    np.testing.assert_allclose(np.asarray(d_p), np.asarray(d_r), rtol=1e-6)


@pytest.mark.parametrize("shape", SHAPES[:2])
def test_dequant_accumulate_matches_ref(shape):
    x = jax.random.normal(jax.random.PRNGKey(2), shape)
    acc = jax.random.normal(jax.random.PRNGKey(3), shape)
    q, s = ref.quantize_blocks(x)
    a_p = quant8.dequantize_accumulate_blocks(q, s, acc, interpret=True)
    a_r = ref.dequantize_accumulate_blocks(q, s, acc)
    np.testing.assert_allclose(np.asarray(a_p), np.asarray(a_r), rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("n", [1, 100, 511, 512, 4097, 70000])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_ops_roundtrip_arbitrary_sizes(n, backend):
    backend = ops.wire_backend(backend)
    x = jax.random.normal(jax.random.PRNGKey(n), (n,)) * 0.01
    q, s, meta = ops.quantize(x, backend=backend)
    xr = ops.dequantize(q, s, meta, backend=backend)
    assert xr.shape == x.shape
    # per-block error bound: |x - xr| <= scale/2 <= amax/(2*127)
    amax = float(jnp.max(jnp.abs(x)))
    assert float(jnp.max(jnp.abs(x - xr))) <= amax / 127.0 + 1e-8


def test_roundtrip_zeros_and_extremes():
    for backend in (ops.wire_backend("jnp"), ops.wire_backend("pallas")):
        z = jnp.zeros((1000,))
        q, s, meta = ops.quantize(z, backend=backend)
        assert float(jnp.max(jnp.abs(ops.dequantize(q, s, meta,
                                                    backend=backend)))) == 0.0
        big = jnp.full((1000,), 1e20)
        q, s, meta = ops.quantize(big, backend=backend)
        np.testing.assert_allclose(
            np.asarray(ops.dequantize(q, s, meta, backend=backend)),
            np.asarray(big), rtol=1e-2)


# ---------------------------------------------------------------------------
# Fused single-pass EF hot path: quantize_ef vs the composed three-pass data
# path (cast+add, quantize, dequantize_accumulate residual update)
# ---------------------------------------------------------------------------

def _compose_ef(x2d, res2d):
    """The unfused reference decomposition of quantize_ef_blocks."""
    y = x2d.astype(jnp.float32) + res2d.astype(jnp.float32)
    q, s = ref.quantize_blocks(y)
    new_res = ref.dequantize_accumulate_blocks(q, -s, y)
    return q, s, new_res


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_quantize_ef_blocks_jnp_bitwise_vs_composed(shape, dtype):
    """The fused jnp oracle and the hand-composed passes run the identical
    expression graph eagerly, so they must agree BITWISE — fp32 and bf16
    wire dtypes alike (the cast is exact, the negated-scale residual
    update is an IEEE sign flip)."""
    x = (jax.random.normal(jax.random.PRNGKey(7), shape) * 3).astype(dtype)
    res = jax.random.normal(jax.random.PRNGKey(8), shape) * 0.01
    q_f, s_f, r_f = ref.quantize_ef_blocks(x, res)
    q_c, s_c, r_c = _compose_ef(x, res)
    np.testing.assert_array_equal(np.asarray(q_f), np.asarray(q_c))
    np.testing.assert_array_equal(np.asarray(s_f), np.asarray(s_c))
    np.testing.assert_array_equal(np.asarray(r_f), np.asarray(r_c))


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_quantize_ef_blocks_pallas_vs_composed(shape, dtype):
    """Pallas (interpret) vs the composed oracle: interpret-mode XLA may
    fuse the divide differently, so q gets the same 1-LSB rounding-tie
    policy as plain quantize; the residual error is then bounded by the
    per-row scale for flipped elements (plus float slack elsewhere)."""
    x = (jax.random.normal(jax.random.PRNGKey(9), shape) * 3).astype(dtype)
    res = jax.random.normal(jax.random.PRNGKey(10), shape) * 0.01
    q_p, s_p, r_p = quant8.quantize_ef_blocks(x, res, interpret=True)
    q_c, s_c, r_c = _compose_ef(x, res)
    qdiff = np.abs(np.asarray(q_p, np.int32) - np.asarray(q_c, np.int32))
    assert qdiff.max() <= 1, qdiff.max()
    assert (qdiff > 0).mean() < 0.01
    np.testing.assert_allclose(np.asarray(s_p), np.asarray(s_c), rtol=1e-6)
    # |r_p - r_c| <= scale where q flipped by 1 LSB, ~0 elsewhere
    bound = np.asarray(s_c)[:, None] * (qdiff + 1e-3) + 1e-7
    assert (np.abs(np.asarray(r_p) - np.asarray(r_c)) <= bound).all()


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_quantize_cast_blocks_folds_wire_cast(backend):
    """quantize(bf16 buffer) == quantize(f32 copy of it): the wire cast is
    inside the tile/oracle, so no separate cast pass is ever needed."""
    backend = ops.wire_backend(backend)
    x16 = (jax.random.normal(jax.random.PRNGKey(11), (3000,)) * 2
           ).astype(jnp.bfloat16)
    q_a, s_a, _ = ops.quantize(x16, backend=backend)
    q_b, s_b, _ = ops.quantize(x16.astype(jnp.float32), backend=backend)
    np.testing.assert_array_equal(np.asarray(q_a), np.asarray(q_b))
    np.testing.assert_array_equal(np.asarray(s_a), np.asarray(s_b))


@pytest.mark.parametrize("n", [1, 100, 511, 4097, 70000])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_ops_quantize_ef_odd_sizes(n, backend):
    """Shape-polymorphic fused EF: padding round-trips and the residual
    comes back in the caller's (odd) shape with the invariant
    y = dequant(q) + new_residual holding per element."""
    backend = ops.wire_backend(backend)
    x = jax.random.normal(jax.random.PRNGKey(n), (n,)).astype(jnp.bfloat16)
    res = jax.random.normal(jax.random.PRNGKey(n + 1), (n,)) * 0.01
    q, s, meta, new_res = ops.quantize_ef(x, res, backend=backend)
    assert new_res.shape == (n,) and new_res.dtype == jnp.float32
    y = x.astype(jnp.float32) + res
    deq = ops.dequantize(q, s,
                         ops.QuantMeta(shape=(n,), dtype=jnp.float32, n=n,
                                       block=meta.block), backend=backend)
    np.testing.assert_allclose(np.asarray(deq + new_res), np.asarray(y),
                               rtol=1e-5, atol=1e-7)


def test_quantize_ef_rejects_mismatched_residual():
    x = jnp.zeros((100,))
    with pytest.raises(ValueError, match="residual"):
        ops.quantize_ef(x, jnp.zeros((99,)))


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_quantize_ef_all_zero_blocks(backend):
    """amax == 0 rows: scale 0, q 0, and the residual carries y through
    unchanged (the safe-divide guard, same policy as plain quantize)."""
    x = jnp.zeros((8, 512))
    res = jnp.zeros((8, 512))
    if backend == "pallas":
        q, s, r = quant8.quantize_ef_blocks(x, res, interpret=True)
    else:
        q, s, r = ref.quantize_ef_blocks(x, res)
    assert not np.asarray(q).any()
    assert not np.asarray(s).any()
    assert not np.asarray(r).any()


def test_quantize_ef_inf_amax_rows_agree_across_backends():
    """A row containing inf drives amax (and the scale) to inf; whatever
    the resulting q/residual policy is, both backends must agree on it.
    q and scales are exact; the residual allows FMA-contraction slack on
    the finite rows (interpret-mode XLA fuses y - q*s) and compares the
    inf row's nans as equal (assert_allclose is nan-aware)."""
    x = jnp.ones((8, 512)).at[0, 3].set(jnp.inf)
    res = jnp.zeros((8, 512))
    q_p, s_p, r_p = quant8.quantize_ef_blocks(x, res, interpret=True)
    q_r, s_r, r_r = ref.quantize_ef_blocks(x, res)
    np.testing.assert_array_equal(np.asarray(q_p), np.asarray(q_r))
    np.testing.assert_array_equal(np.asarray(s_p), np.asarray(s_r))
    assert np.isnan(np.asarray(r_p)[0]).all()       # the inf row
    np.testing.assert_allclose(np.asarray(r_p), np.asarray(r_r), atol=1e-7)


def test_dequantize_accumulate_keeps_acc_dtype():
    """Accumulating into an f32 buffer stays f32 even when the quantized
    tensor was a bf16 wire buffer (meta.dtype must not leak in)."""
    x = jax.random.normal(jax.random.PRNGKey(12), (700,)).astype(jnp.bfloat16)
    q, s, meta = ops.quantize(x)
    acc = jax.random.normal(jax.random.PRNGKey(13), (700,))
    out = ops.dequantize_accumulate(q, s, acc, meta)
    assert out.dtype == jnp.float32


# ---------------------------------------------------------------------------
# The grid: up to MAX_TILE_ROWS blocks per step, the last tile ragged
# ---------------------------------------------------------------------------

_TILE = quant8.MAX_TILE_ROWS
# the padding quantum, 8 x an odd number below one tile, one tile, one tile
# + 8, several tiles + a ragged edge
GRID_ROWS = [8, 8 * 13, _TILE, _TILE + 8, 3 * _TILE + 24]


def _kernel_args(kernel, dtype, n_blocks):
    """(args, static kwargs) of one kernel call; `dtype` is the float
    input's (quantize) or the output's and accumulator's (dequantize)."""
    kx, kr, ka = jax.random.split(jax.random.PRNGKey(n_blocks), 3)
    shape = (n_blocks, quant8.DEFAULT_BLOCK)
    x = (jax.random.normal(kx, shape) * 3).astype(dtype)
    if kernel == "quantize_blocks":
        return (x,), {}
    if kernel == "quantize_ef_blocks":
        return (x, jax.random.normal(kr, shape) * 0.01), {}
    q, s = ref.quantize_blocks(x)
    if kernel == "dequantize_blocks":
        return (q, s), {"out_dtype": dtype}
    acc = jax.random.normal(ka, shape).astype(dtype)
    return (q, s, acc), {"out_dtype": dtype}


def _bits(a):
    a = np.asarray(a)
    return a.dtype, a.shape, a.view(np.uint8).tobytes()


@pytest.mark.parametrize("n_blocks", GRID_ROWS)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("kernel", ["quantize_blocks", "quantize_ef_blocks",
                                    "dequantize_blocks",
                                    "dequantize_accumulate_blocks"])
def test_reblocked_kernel_bitwise_vs_oracle(kernel, dtype, n_blocks):
    """Every row of every tile, the ragged last one included, equals the
    ref.py oracle bit for bit: q, scales, residual and dequantized output.
    The interpreted kernel body is compiled by XLA, so it is compared with
    the oracle compiled the same way (eager op-by-op evaluation may round
    differently, which the older tests above allow for)."""
    args, kw = _kernel_args(kernel, dtype, n_blocks)
    got = getattr(quant8, kernel)(*args, interpret=True, **kw)
    want = jax.jit(functools.partial(getattr(ref, kernel), **kw))(*args)
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert _bits(g) == _bits(w)


@pytest.mark.parametrize("n_blocks", [8, 16, 8 * 127, _TILE - 8, _TILE,
                                      _TILE + 8, 8 * 1543, 12_808, 512_000])
def test_grid_steps_follow_the_shape(n_blocks):
    """A tile of up to MAX_TILE_ROWS rows, ceil(n_blocks / tile) grid steps,
    never more steps than the 8-row grid took and never an empty tile."""
    tile = quant8.tile_rows(n_blocks)
    (steps,) = quant8._grid(n_blocks)
    assert quant8.MAX_TILE_ROWS % 32 == 0       # int8 tiles natively
    assert tile == min(n_blocks, quant8.MAX_TILE_ROWS)
    assert steps == -(-n_blocks // tile)
    assert steps <= n_blocks // quant8.TILE_ROWS
    assert (steps - 1) * tile < n_blocks


# ---------------------------------------------------------------------------
# Shape-contract errors + pad-waste accounting
# ---------------------------------------------------------------------------

def test_grid_rejects_ragged_rows_with_shape():
    with pytest.raises(ValueError) as ei:
        quant8.quantize_blocks(jnp.zeros((3, 512)), interpret=True)
    assert "3" in str(ei.value) and "TILE_ROWS" in str(ei.value)


def test_block_rejects_non_lane_multiple_with_shape():
    with pytest.raises(ValueError) as ei:
        quant8.quantize_blocks(jnp.zeros((8, 100)), interpret=True)
    assert "100" in str(ei.value) and "128" in str(ei.value)


def test_pad_info_reports_tiny_bucket_waste():
    quantum = quant8.TILE_ROWS * quant8.DEFAULT_BLOCK
    info = ops.pad_info(100)
    assert info.padded == quantum
    assert info.waste_elems == quantum - 100
    assert info.waste_frac == pytest.approx((quantum - 100) / quantum)
    assert ops.pad_info(quantum).waste_frac == 0.0


def test_backend_policy_is_single_sourced():
    """No comm call site hardcodes the kernel backend: core/collectives.py
    resolves it via the kernels/ops.py policy only."""
    import repro.core.collectives as cl
    src = inspect.getsource(cl)
    assert 'backend="jnp"' not in src and "backend='jnp'" not in src
    assert 'backend="pallas"' not in src and "backend='pallas'" not in src
    with pytest.raises(ValueError, match="unknown quantization backend"):
        ops.wire_backend("cuda")
    assert ops.wire_backend("pallas", "cpu") == "interpret"
    assert ops.wire_backend("jnp", "cpu") == "jnp"
    assert ops.wire_backend(platform="cpu") in ("interpret", "jnp")


@pytest.mark.parametrize("requested", ["auto", "pallas"])
def test_tpu_target_always_gets_compiled_kernels(requested, monkeypatch):
    """A TPU target resolves to the compiled kernels whatever the host's
    default device is; the oracle and the interpreter are refused there."""
    monkeypatch.setenv("REPRO_QUANT_BACKEND", "jnp")
    assert ops.wire_backend(requested, "tpu") == "pallas"
    for refused in ("jnp", "interpret"):
        with pytest.raises(ValueError, match="TPU"):
            ops.wire_backend(refused, "tpu")


# ---------------------------------------------------------------------------
# End-to-end on the factored mesh: the fused wire is a drop-in
# ---------------------------------------------------------------------------

def test_allreduce_ef_fused_matches_unfused_mesh8(mesh8):
    """Fused and composed EF data paths produce bitwise-identical reduced
    gradients AND residuals through a real 8-rank exchange."""
    from repro.core import collectives as cl

    ax = ("node", "local")
    p, n = 8, 70000
    x = jax.random.normal(jax.random.PRNGKey(21), (n,))
    res = jax.random.normal(jax.random.PRNGKey(22),
                            (cl.ef_residual_shape(n, p)[0] * p,)) * 0.01

    def run(fused):
        def f(xs, rs):
            return cl.allreduce_ef(xs, rs, ax, mean=True, backend="jnp",
                                   fused=fused)
        w = jax.shard_map(f, mesh=mesh8, in_specs=(P(), P(ax)),
                          out_specs=(P(), P(ax)), axis_names=set(ax),
                          check_vma=False)
        return w(x, res)

    o_f, r_f = run(True)
    o_u, r_u = run(False)
    np.testing.assert_array_equal(np.asarray(o_f), np.asarray(o_u))
    np.testing.assert_array_equal(np.asarray(r_f), np.asarray(r_u))


def test_hier_allreduce_ef_fused_matches_unfused_mesh8(mesh8):
    """Same drop-in contract through the two-level path (the fabric leg is
    where the fused kernels actually run in production plans)."""
    from repro.core import collectives as cl
    from repro.core import hier as hier_lib

    ax = ("node", "local")
    n = 70000
    x = jax.random.normal(jax.random.PRNGKey(23), (n,))
    res = jax.random.normal(
        jax.random.PRNGKey(24),
        (hier_lib.ef_residual_shape(n, 4, 2)[0] * 8,)) * 0.01

    def run(fused):
        spec = hier_lib.HierSpec(wire_inter=cl.WIRE_INT8,
                                 error_feedback=True, backend="jnp",
                                 fused=fused)

        def f(xs, rs):
            return hier_lib.hier_allreduce_ef(xs, rs, spec, mean=True)
        w = jax.shard_map(f, mesh=mesh8, in_specs=(P(), P(ax)),
                          out_specs=(P(), P(ax)), axis_names=set(ax),
                          check_vma=False)
        return w(x, res)

    o_f, r_f = run(True)
    o_u, r_u = run(False)
    np.testing.assert_array_equal(np.asarray(o_f), np.asarray(o_u))
    np.testing.assert_array_equal(np.asarray(r_f), np.asarray(r_u))


def test_allreduce_int8_acc_folds_accumulate_mesh8(mesh8):
    """The gather-side `acc` path (dequantize_accumulate) equals reducing
    then adding — bitwise, since q * s + acc is evaluated identically."""
    from repro.core import collectives as cl

    ax = ("node", "local")
    n = 5000
    x = jax.random.normal(jax.random.PRNGKey(25), (n,))
    acc = jax.random.normal(jax.random.PRNGKey(26), (n,))

    def run(use_acc):
        def f(xs, accs):
            return cl.allreduce(xs, ax, wire=cl.WIRE_INT8, mean=True,
                                backend="jnp",
                                acc=accs if use_acc else None)
        w = jax.shard_map(f, mesh=mesh8, in_specs=(P(), P()),
                          out_specs=P(), axis_names=set(ax),
                          check_vma=False)
        return w(x, acc)

    fused_out = run(True)
    plain = run(False)
    np.testing.assert_array_equal(np.asarray(fused_out),
                                  np.asarray(acc) + np.asarray(plain))


# ---------------------------------------------------------------------------
# Property tests (hypothesis): the fused kernel is total over its domain
# ---------------------------------------------------------------------------

try:
    import hypothesis
    import hypothesis.strategies as st
    from hypothesis import given, settings
except ImportError:                                     # pragma: no cover
    hypothesis = None

needs_hypothesis = pytest.mark.skipif(
    hypothesis is None, reason="property tests need hypothesis")


@needs_hypothesis
@settings(max_examples=30, deadline=None) if hypothesis else (lambda f: f)
@given(n=st.integers(min_value=1, max_value=9000),
       seed=st.integers(min_value=0, max_value=2**31 - 1),
       scale_exp=st.integers(min_value=-20, max_value=20)) \
    if hypothesis else (lambda f: f)
def test_property_fused_ef_bitwise_vs_composed(n, seed, scale_exp):
    """For arbitrary sizes and magnitudes the fused jnp path is bitwise
    equal to composing quantize + dequantize_accumulate by hand."""
    key = jax.random.PRNGKey(seed)
    kx, kr = jax.random.split(key)
    x = (jax.random.normal(kx, (n,)) * (2.0 ** scale_exp)
         ).astype(jnp.bfloat16)
    res = jax.random.normal(kr, (n,)) * (2.0 ** (scale_exp - 7))
    q_f, s_f, meta, r_f = ops.quantize_ef(x, res, backend="jnp")
    y = x.astype(jnp.float32) + res
    q_c, s_c, meta_c = ops.quantize(y, backend="jnp")
    r_c = ops.dequantize_accumulate(q_c, -s_c, y, meta_c, backend="jnp")
    np.testing.assert_array_equal(np.asarray(q_f), np.asarray(q_c))
    np.testing.assert_array_equal(np.asarray(s_f), np.asarray(s_c))
    np.testing.assert_array_equal(np.asarray(r_f), np.asarray(r_c))
