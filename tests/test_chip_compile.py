"""Compile-only checks against a described TPU v5e (no chip attached).

The TPU compiler refuses what interpret mode accepts: misaligned block
shapes, too much VMEM. These tests lower the int8-wire kernels at real
bucket sizes for v5e and assert the compiled program carries the Mosaic
kernel (``tpu_custom_call``). The topology is described inside a fixture,
never while a module is imported: only one process may load the TPU
library, and the suite runs under several workers.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import quant8

# one 25 MB f32 gradient bucket (the engine's default bucket_bytes) in
# (n_blocks, block) layout
N_BLOCKS, BLOCK = 12_800, quant8.DEFAULT_BLOCK


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                    # noqa: BLE001 - any failure
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _arg(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args, **static):
    return fn.lower(*args, **static).compile().as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quantize_blocks_compiles_for_v5e(one_chip, dtype):
    x = _arg((N_BLOCKS, BLOCK), dtype, one_chip)
    assert "tpu_custom_call" in _compiled_text(quant8.quantize_blocks, x)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quantize_ef_blocks_compiles_for_v5e(one_chip, dtype):
    x = _arg((N_BLOCKS, BLOCK), dtype, one_chip)
    res = _arg((N_BLOCKS, BLOCK), jnp.float32, one_chip)
    assert "tpu_custom_call" in _compiled_text(quant8.quantize_ef_blocks,
                                               x, res)


@pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
def test_dequantize_blocks_compiles_for_v5e(one_chip, out_dtype):
    q = _arg((N_BLOCKS, BLOCK), jnp.int8, one_chip)
    s = _arg((N_BLOCKS,), jnp.float32, one_chip)
    assert "tpu_custom_call" in _compiled_text(quant8.dequantize_blocks, q,
                                               s, out_dtype=out_dtype)


@pytest.mark.parametrize("acc_dtype", [jnp.float32, jnp.bfloat16])
def test_dequantize_accumulate_blocks_compiles_for_v5e(one_chip, acc_dtype):
    q = _arg((N_BLOCKS, BLOCK), jnp.int8, one_chip)
    s = _arg((N_BLOCKS,), jnp.float32, one_chip)
    acc = _arg((N_BLOCKS, BLOCK), acc_dtype, one_chip)
    assert "tpu_custom_call" in _compiled_text(
        quant8.dequantize_accumulate_blocks, q, s, acc)


def test_engine_on_v5e_mesh_plans_compiled_kernels(topo, monkeypatch):
    """The engine resolves the wire backend from its mesh's devices, not
    from the host's default (CPU) backend: a step built for the chip
    carries the compiled kernels, never the oracle or the interpreter."""
    import numpy as np
    from jax.sharding import AxisType, Mesh

    from repro.core import engine as eng
    monkeypatch.setenv("REPRO_QUANT_BACKEND", "jnp")
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("node", "local"),
                axis_types=(AxisType.Auto,) * 2)
    comm = eng.CommConfig(mode="mlsl", wire="int8", error_feedback=True,
                          hier=True)
    grads = {"w": jax.ShapeDtypeStruct((N_BLOCKS, BLOCK), jnp.float32)}
    engine = eng.CommEngine.create(grads, comm, mesh, ("node", "local"))
    assert engine.plan.quant_backend == "pallas"
    assert engine.plan.hier_spec.backend == "pallas"


# each kernel's operand dtypes ("scales": the f32 (n_blocks,) scale vector)
KERNEL_ARGS = {
    "quantize_blocks": (jnp.float32,),
    "quantize_ef_blocks": (jnp.float32, jnp.float32),
    "dequantize_blocks": (jnp.int8, "scales"),
    "dequantize_accumulate_blocks": (jnp.int8, "scales", jnp.float32),
}


@pytest.mark.parametrize("kernel", list(KERNEL_ARGS))
def test_kernel_keeps_its_name_under_any_caller(one_chip, kernel):
    """The compiled custom call, which is what the chip's trace names, is
    called after the kernel however the function around it is named: a
    trace reader that matches the four names keeps finding the kernels."""
    import re
    n = 64
    args = [_arg((n,), jnp.float32, one_chip) if d == "scales"
            else _arg((n, BLOCK), d, one_chip) for d in KERNEL_ARGS[kernel]]
    raw = getattr(quant8, kernel).__wrapped__

    @jax.jit
    def some_caller(*xs):
        return raw(*xs)

    text = _compiled_text(some_caller, *args)
    calls = re.findall(
        r'%([\w.-]+) = [^\n]*custom_call_target="tpu_custom_call"', text)
    assert [re.sub(r"\.\d+$", "", c) for c in calls] == [kernel], calls


# a ragged last tile, the 262M-element embedding bucket, one 8-row tile
BUCKET_BLOCKS = [12_808, 512_000, 8]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n_blocks", BUCKET_BLOCKS)
@pytest.mark.parametrize("kernel", list(KERNEL_ARGS))
def test_kernel_compiles_at_bucket_sizes(one_chip, kernel, n_blocks, dtype):
    """Mosaic takes the ragged tile and the VMEM footprint of a full one,
    and the scales need no relayout around the kernel: the compiled program
    is the custom call alone, with no temporary buffer. `dtype` is the
    float operand's: the wire buffer quantized, or the accumulator and
    output dequantized into."""
    args = [_arg((n_blocks,), jnp.float32, one_chip) if d == "scales"
            else _arg((n_blocks, BLOCK), dtype if d == jnp.float32 else d,
                      one_chip)
            for d in KERNEL_ARGS[kernel]]
    if kernel == "quantize_ef_blocks":      # the residual stays f32
        args[1] = _arg((n_blocks, BLOCK), jnp.float32, one_chip)
    static = {"out_dtype": dtype} if kernel == "dequantize_blocks" else {}
    compiled = getattr(quant8, kernel).lower(*args, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_dsv2lite_cell_step_compiles_for_v5e(topo):
    """The dsv2lite-l7e8.moe-bf16.1chip cell's train step (2 x 4096 tokens,
    flat mlsl, bf16 wire, kv_chunk 1024) compiled for one described v5e
    chip: its buffers fit the chip's 16 GB, and the grouped-matmul kernels
    of the dropless MoE are Mosaic custom calls in it (forward, the rows'
    gradient and the weights' gradient)."""
    import json
    import pathlib
    import re

    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.configs import registry
    from repro.core import planner as pl
    from repro.models.transformer import Batch, Model
    from repro.optim import optimizers as opt_lib
    from repro.train import trainer as tr

    root = pathlib.Path(__file__).resolve().parents[1]
    cfg = json.loads((root / "benchmarks/chip/configs/dsv2lite-l7e8.json")
                     .read_text())
    traffic = json.loads((root / "benchmarks/chip/traffic/"
                          "moe-bf16-b2s4k.json").read_text())
    rep = dict(cfg["program"]["replace"])
    moe = dataclasses.replace(registry.get_config(cfg["program"]["arch"]).moe,
                              held=tuple(rep.pop("moe")["held"]))
    mc = dataclasses.replace(registry.get_config(cfg["program"]["arch"]),
                             moe=moe, **rep)
    model = Model(mc)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    planner = pl.Planner(mesh=mesh)
    opt = cfg["optimizer"]
    optimizer = opt_lib.make_optimizer(
        opt["name"], opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
        weight_decay=opt["weight_decay"])
    comm = tr.CommConfig(**traffic["comm"])
    engine = tr.make_comm_engine(model, mesh, planner, comm)
    state_sh = tr.state_shardings(planner, model, optimizer, engine)
    step = jax.jit(tr.make_train_step(model, optimizer, mesh, planner, comm,
                                      grad_clip=opt["clip"], engine=engine),
                   out_shardings=(state_sh, NamedSharding(mesh, P())),
                   donate_argnums=0)
    shapes = jax.eval_shape(lambda: tr.make_train_state(
        model, optimizer, jax.random.PRNGKey(0), engine=engine))
    state = jax.tree_util.tree_map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        shapes, state_sh)
    B, S = traffic["batch"], traffic["seq"]
    tok = jax.ShapeDtypeStruct((B, S), jnp.int32,
                               sharding=NamedSharding(mesh, P("data")))
    with jax.set_mesh(mesh):
        compiled = step.lower(state, Batch(tokens=tok, labels=tok)).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < 16e9, (total, str(mem))
    calls = re.findall(
        r'%([\w.-]+) = [^\n]*custom_call_target="tpu_custom_call"',
        compiled.as_text())
    names = [re.sub(r"\.\d+$", "", c) for c in calls]
    # moe_gmm: the three forward products, recomputed, and the rows'
    # gradients; moe_tgmm: the weights' gradients
    assert names.count("moe_gmm") >= 2 and "moe_tgmm" in names, names
