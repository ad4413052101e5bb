"""MLSL collectives API: wire formats and single-rank semantics (multi-rank
equivalence is covered by tests/test_multidevice.py in a subprocess)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import collectives as cl


def _run1(fn, x, mesh11):
    # jit-wrapped, as in the trainer: inside jit the partial-manual shard_map
    # accepts replicated specs with check_vma=False.
    return jax.jit(jax.shard_map(fn, mesh=mesh11, in_specs=P(),
                                 out_specs=P(), axis_names={"data"},
                                 check_vma=False))(x)


@pytest.mark.parametrize("wire", cl.WIRES)
def test_allreduce_identity_on_one_rank(wire, mesh11):
    x = jax.random.normal(jax.random.PRNGKey(0), (1000,)) * 0.01
    y = _run1(lambda u: cl.allreduce(u, ("data",), wire=wire), x, mesh11)
    # int8 error = bf16 reduce-scatter leg (~2^-8 rel) + int8 block
    # quantization (~amax/254)
    tol = {"fp32": 1e-7, "bf16": 1e-2, "int8": 1e-2}[wire]
    np.testing.assert_allclose(np.asarray(y), np.asarray(x),
                               rtol=tol, atol=tol * float(jnp.max(jnp.abs(x))))


def test_allreduce_ef_residual_tracks_error(mesh11):
    x = jax.random.normal(jax.random.PRNGKey(1), (2048,)) * 1e-3
    res0 = jnp.zeros(cl.ef_residual_shape(x.size, 1), jnp.float32)

    def f(u, r):
        return cl.allreduce_ef(u, r, ("data",))

    y, res = jax.jit(jax.shard_map(f, mesh=mesh11, in_specs=(P(), P()),
                                   out_specs=(P(), P()),
                                   axis_names={"data"},
                                   check_vma=False))(x, res0)
    # y + residual == bf16(x): the residual holds exactly the quantization
    # error of the bf16-wire reduce-scatter shard
    xb = np.asarray(x.astype(jnp.bfloat16).astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(y[: x.size]) + np.asarray(
        res[: x.size]), xb, rtol=1e-5, atol=1e-8)


def test_wire_bytes_ordering():
    assert cl.wire_bytes_per_elem("fp32") > cl.wire_bytes_per_elem("bf16") \
        > cl.wire_bytes_per_elem("int8")


def test_broadcast_root_semantics(mesh11):
    x = jnp.arange(8.0)
    y = _run1(lambda u: cl.broadcast(u, ("data",), root=0), x, mesh11)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


def test_comm_facade(mesh11):
    comm = cl.Comm(mesh=mesh11, data_axes=("data",))
    assert comm.data_parallel_size == 1
    assert comm.model_parallel_size == 1
    y = jax.jit(lambda v: comm.run(lambda u: cl.allreduce(u, ("data",)),
                                   P(), P(), v))(jnp.ones((4,)))
    np.testing.assert_array_equal(np.asarray(y), np.ones(4))
