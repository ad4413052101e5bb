"""Every layer of the training step is named on the device.

The mlsl step's compiled HLO keeps each `jax.named_scope` in its ops'
`metadata={op_name=...}`, which is what a profiler trace attributes device
time by. Checked here on the tiny config (bf16, rematerialized layers, as
the benchmark's cells run) for a flat int8 + error-feedback exchange, the
two-level route on `mesh8`, and two microbatches with overlap off and on:

  * every dot, fusion, loop, custom call and collective sits under one of
    the layer scopes `model` (forward `jvp(model)`, backward
    `transpose(jvp(model))`, the forward recomputed under remat
    `rematted_computation`), `optim/` or `comm/`, apart from a short list
    of ops that belong to no layer;
  * each layer's scopes are there: `optim/cast|clip|update`,
    `comm/bucket{i}/pack|unpack` and the bucket's route, with `hier.py`'s
    legs nested in it.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import registry
from repro.core.planner import Planner
from repro.launch import mesh as mesh_lib
from repro.models.transformer import Batch, Model
from repro.optim import optimizers as opt_lib
from repro.train import trainer as tr

LAYERS = ("model", "optim/", "comm/")
# ops that belong to no layer, by the primitive that names them: the loss's
# mean over the chips (psum, then the scale), the step counter, and the
# tables JAX and XLA hoist out of the model (positions, causal masks, RoPE
# angles, zero fills, a weight's f32 copy for the backward loop)
OUTSIDE = {"psum", "div", "mul", "add", "iota", "cos", "sin", "le",
           "broadcast", "broadcast_in_dim", "convert_element_type"}
OPS = ("dot", "convolution", "fusion", "while", "custom-call", "all-reduce",
       "all-gather", "reduce-scatter", "all-to-all", "collective-permute")

INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.-]+) = .*? ([a-z][a-z0-9-]*)\(")
COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.-]+) .*\{\s*$")
OP_NAME = re.compile(r'op_name="([^"]*)"')

CASES = {
    "flat-int8ef": ("flat", dict(wire="int8", error_feedback=True)),
    "hier-int8ef": ("mesh8", dict(wire="int8", error_feedback=True,
                                  hier=True, wire_intra="bf16")),
    "accum2-blocking": ("mesh8", dict(wire="fp32", accum_steps=2,
                                      overlap=False)),
    "accum2-overlap": ("mesh8", dict(wire="fp32", accum_steps=2,
                                     overlap=True)),
}


def _compiled_hlo(mesh, comm) -> str:
    cfg = dataclasses.replace(registry.get_smoke_config("yi-6b"), remat=True,
                              dtype=jnp.bfloat16)
    model, opt = Model(cfg), opt_lib.adamw(1e-3)
    planner = Planner(mesh=mesh)
    engine = tr.make_comm_engine(model, mesh, planner, comm)
    step = jax.jit(tr.make_train_step(model, opt, mesh, planner, comm,
                                      engine=engine))
    with jax.set_mesh(mesh):
        state = jax.eval_shape(lambda: tr.make_train_state(
            model, opt, jax.random.PRNGKey(0), engine=engine))
        tokens = jax.ShapeDtypeStruct((16, 32), jnp.int32)
        return step.lower(state, Batch(tokens=tokens, labels=tokens)) \
            .compile().as_text()


def device_ops(hlo: str):
    """(instruction, opcode, op_names) of each op the device runs: the
    instructions of every computation that is not a fusion's body. A
    fusion's op_names are its own and those of the ops fused into it (XLA
    leaves a fusion's own empty where its root is a layout op)."""
    bodies, cur = {}, None
    for line in hlo.splitlines():
        m = COMPUTATION.match(line)
        if m:
            cur = bodies.setdefault(m.group(1), [])
        elif cur is not None:
            cur.append(line)
    fused = set(re.findall(r"calls=%([\w.-]+)", hlo))
    for comp, lines in bodies.items():
        if comp in fused:
            continue
        for line in lines:
            m = INSTR.match(line)
            if not m or m.group(2) not in OPS:
                continue
            names = OP_NAME.findall(line)
            called = re.search(r"calls=%([\w.-]+)", line)
            if m.group(2) == "fusion" and called:
                for inner in bodies.get(called.group(1), []):
                    names += OP_NAME.findall(inner)
            yield m.group(1), m.group(2), names


def _outside(name: str) -> bool:
    """An op_name with no scope at all below the step, whose primitive is
    one of OUTSIDE."""
    parts = [p for p in name.split("/")[1:]
             if p not in ("shard_map", "jit(_where)")]
    return not parts or (len(parts) == 1
                         and re.sub(r"\.\d+$", "", parts[0]) in OUTSIDE)


@pytest.fixture(scope="module")
def hlos(mesh8):
    meshes = {"flat": mesh_lib.make_host_mesh(8), "mesh8": mesh8}
    return {case: _compiled_hlo(meshes[mesh],
                                tr.CommConfig(mode="mlsl", **kw))
            for case, (mesh, kw) in CASES.items()}


@pytest.mark.parametrize("case", list(CASES))
def test_every_device_op_has_a_layer(hlos, case):
    stray = {}
    for instr, opcode, names in device_ops(hlos[case]):
        # ops XLA adds itself (layout copies) carry no op_name at all
        if not names or any(k in n for n in names for k in LAYERS):
            continue
        if not all(_outside(n) for n in names):
            stray[instr] = (opcode, sorted(set(names)))
    assert not stray, stray


@pytest.mark.parametrize("case", list(CASES))
def test_each_layer_scope_is_there(hlos, case):
    names = "\n".join(n for _, _, ns in device_ops(hlos[case]) for n in ns)
    _, kw = CASES[case]
    route = f"{'hier' if kw.get('hier') else 'flat'}_allreduce_{kw['wire']}"
    want = [r"/jvp\(model\)/", r"/transpose\(jvp\(model\)\)/",
            "rematted_computation", "optim/cast/", "optim/clip/",
            "optim/update/", r"comm/bucket\d+/pack/", r"comm/bucket\d+/unpack/",
            rf"comm/bucket\d+/{route}/"]
    if kw.get("hier"):
        want += [rf"comm/bucket\d+/{route}/hier/{leg}/" for leg in
                 ("intra_rs_bf16", "inter_allreduce_int8_ef",
                  "intra_ag_bf16")]
    if kw.get("accum_steps", 1) > 1:
        want.append(r"microbatch/fwd_bwd/.*model")
    missing = [w for w in want if not re.search(w, names)]
    assert not missing, missing
