"""Mixture-of-Experts layers: top-k token-choice routing.

Two dispatch implementations, both capacity-based (GShard semantics, overflow
tokens dropped from the expert path but preserved by the residual):

  * `moe_apply` (baseline, pure GSPMD): sort-based dispatch with static
    shapes — argsort tokens by expert, scatter into an (E, C, d) buffer,
    batched expert matmuls, scatter-add back. Expert weights shard over the
    `model` axis on the expert dim when E divides it, else on d_ff (tensor
    parallel experts). The cross-device token movement is whatever GSPMD
    infers from the gather/scatter — this is the baseline the paper-style
    optimization improves on.

  * `moe_apply_ep` (optimized, shard_map): explicit expert parallelism with
    all_to_all over the model axis — the MLSL-flavored hand-scheduled
    collective data path (see EXPERIMENTS.md §Perf). Requires
    E % model_axis_size == 0 and runs fully manual over the model axis.

Routing math is shared, so both paths are numerically comparable up to token
drop ordering (tests assert equivalence where capacities are loose).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.core import planner as pl
from repro.models import common, mlp


def moe_defs(d_model: int, m: MoEConfig, dtype) -> dict:
    d = {
        "router": pl.ParamDef((d_model, m.n_experts), pl.K_REPLICATED,
                              jnp.float32),
        "w1": pl.ParamDef((m.n_experts, d_model, m.d_ff), pl.K_EXPERT_IN, dtype),
        "w2": pl.ParamDef((m.n_experts, m.d_ff, d_model), pl.K_EXPERT_OUT, dtype),
        "w3": pl.ParamDef((m.n_experts, d_model, m.d_ff), pl.K_EXPERT_IN, dtype),
    }
    if m.dense_residual_ff:
        d["dense"] = mlp.mlp_defs(d_model, m.dense_residual_ff, dtype)
    return d


def capacity(n_tokens: int, m: MoEConfig) -> int:
    c = int(math.ceil(n_tokens * m.top_k * m.capacity_factor / m.n_experts))
    return max(8, ((c + 7) // 8) * 8)     # sublane-aligned


def route(xf: jax.Array, router_w: jax.Array, m: MoEConfig):
    """xf (T, d) -> (weights (T, k), ids (T, k), aux_loss scalar)."""
    logits = (xf.astype(jnp.float32) @ router_w).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, m.top_k)
    weights = weights / jnp.maximum(jnp.sum(weights, axis=-1, keepdims=True),
                                    1e-9)
    # load-balance auxiliary loss (Switch/GShard): E * sum_e f_e * p_e
    T = xf.shape[0]
    me = jnp.mean(probs, axis=0)
    one_hot = jax.nn.one_hot(ids[:, 0], m.n_experts, dtype=jnp.float32)
    ce = jnp.sum(one_hot, axis=0) / T
    aux = m.n_experts * jnp.sum(me * ce)
    return weights, ids, aux


def _expert_ffn(w1, w2, w3, xe, act: str):
    """xe (E, C, d) -> (E, C, d) with per-expert SwiGLU."""
    f = common.act_fn(act)
    h = f(jnp.einsum("ecd,edf->ecf", xe, w1))
    h = h * jnp.einsum("ecd,edf->ecf", xe, w3)
    return jnp.einsum("ecf,efd->ecd", h, w2)


def _dispatch_indices(ids: jax.Array, m: MoEConfig, cap: int):
    """Sort-based capacity dispatch with static shapes.

    Returns (slot_token (E*C,) token index feeding each expert slot,
             slot_valid (E*C,) bool,
             slot_weight_src (E*C,) index into the flat (T*k,) weight vector).
    """
    T = ids.shape[0]
    flat_e = ids.reshape(-1)                           # (T*k,) expert of slot
    order = jnp.argsort(flat_e, stable=True)           # group by expert
    sorted_e = flat_e[order]
    arange = jnp.arange(T * m.top_k)
    group_start = jnp.searchsorted(sorted_e, jnp.arange(m.n_experts),
                                   side="left")
    pos_in_group = arange - group_start[sorted_e]
    ok = pos_in_group < cap
    dest = jnp.where(ok, sorted_e * cap + pos_in_group, m.n_experts * cap)
    slot_token = jnp.full((m.n_experts * cap + 1,), 0, jnp.int32)
    slot_valid = jnp.zeros((m.n_experts * cap + 1,), bool)
    slot_wsrc = jnp.zeros((m.n_experts * cap + 1,), jnp.int32)
    slot_token = slot_token.at[dest].set((order // m.top_k).astype(jnp.int32))
    slot_valid = slot_valid.at[dest].set(True)
    slot_wsrc = slot_wsrc.at[dest].set(order.astype(jnp.int32))
    return slot_token[:-1], slot_valid[:-1], slot_wsrc[:-1]


def moe_apply(p: dict, x: jax.Array, m: MoEConfig, *, act: str = "silu"):
    """Baseline GSPMD MoE. x (B, S, d) -> (y, aux_loss)."""
    B, S, d = x.shape
    xf = x.reshape(B * S, d)
    T = B * S
    cap = capacity(T, m)
    weights, ids, aux = route(xf, p["router"], m)
    slot_token, slot_valid, slot_wsrc = _dispatch_indices(ids, m, cap)
    xe = xf[slot_token] * slot_valid[:, None].astype(x.dtype)   # (E*C, d)
    xe = xe.reshape(m.n_experts, cap, d)
    ye = _expert_ffn(p["w1"], p["w2"], p["w3"], xe, act)        # (E, C, d)
    yf = ye.reshape(m.n_experts * cap, d)
    w_slot = weights.reshape(-1)[slot_wsrc] * slot_valid.astype(jnp.float32)
    contrib = yf * w_slot[:, None].astype(x.dtype)
    y = jnp.zeros((T, d), x.dtype).at[slot_token].add(contrib)
    y = y.reshape(B, S, d)
    if "dense" in p:
        y = y + mlp.mlp_apply(p["dense"], x, act=act)
    return y, aux


# --- optimized path: explicit expert parallelism over the model axis ---------

def _quantized_gather(w: jax.Array, axis_name: str, concat_axis: int,
                      p_size: int) -> jax.Array:
    """ZeRO weight all-gather with an int8 wire (paper C6 applied to the
    FSDP data path): quantize the local shard blockwise, gather int8 +
    scales, dequantize and reassemble. Halves the dominant collective of
    giant-MoE training (EXPERIMENTS.md §Perf, arctic-480b).

    Gradients use the straight-through estimator: the backward pass is the
    exact vjp of an (unquantized) all-gather — a reduce-scatter of the
    cotangent — because d(round)/dx = 0 would otherwise zero the expert
    weight gradients."""
    from repro.kernels import ops as kops

    def impl(w):
        q, s, meta = kops.quantize(w, block=512, backend="jnp")
        qg = jax.lax.all_gather(q, axis_name, axis=0, tiled=False)
        sg = jax.lax.all_gather(s, axis_name, axis=0, tiled=False)
        parts = [kops.dequantize(qg[i], sg[i], meta).astype(w.dtype)
                 for i in range(p_size)]
        return jnp.concatenate(parts, axis=concat_axis)

    @jax.custom_vjp
    def qg(w):
        return impl(w)

    def fwd(w):
        return impl(w), None

    def bwd(_, g):
        return (jax.lax.psum_scatter(g, axis_name,
                                     scatter_dimension=concat_axis,
                                     tiled=True),)

    qg.defvjp(fwd, bwd)
    return qg(w)


def moe_apply_ep(p: dict, x: jax.Array, m: MoEConfig, *, act: str,
                 mesh: jax.sharding.Mesh, model_axis: str = "model",
                 batch_axes: tuple = ("data",), fsdp_axes: tuple = (),
                 wire_bf16_a2a: bool = False, wgather_wire: str = "bf16"):
    """shard_map all-to-all expert parallelism (paper-style hand scheduling).

    Layout: tokens are batch-sharded over `batch_axes` and replicated over
    the model axis; each model rank takes a 1/ep slice of its local tokens,
    routes them, exchanges token slots with the expert owners via all_to_all,
    runs its local experts, and reverses the exchange. Router weights are
    replicated; expert weights are sharded on the expert dim.
    """
    ep = mesh.shape[model_axis]
    assert m.n_experts % ep == 0, (m.n_experts, ep)
    e_local = m.n_experts // ep
    P = jax.sharding.PartitionSpec
    bspec = batch_axes if len(batch_axes) > 1 else batch_axes[0]

    def local_fn(xl, router_w, w1, w2, w3):
        # xl (b_loc, S, d) replicated over model; w* lead dim e_local.
        if fsdp_axes:
            # ZeRO-3 style: expert weights arrive sharded on d over the batch
            # axes; gather just-in-time before use (int8 wire optional).
            for a in reversed(fsdp_axes):
                if wgather_wire == "int8":
                    psz = jax.lax.axis_size(a)
                    w1 = _quantized_gather(w1, a, 1, psz)
                    w3 = _quantized_gather(w3, a, 1, psz)
                    w2 = _quantized_gather(w2, a, 2, psz)
                else:
                    w1 = jax.lax.all_gather(w1, a, axis=1, tiled=True)
                    w3 = jax.lax.all_gather(w3, a, axis=1, tiled=True)
                    w2 = jax.lax.all_gather(w2, a, axis=2, tiled=True)
        b, S, d = xl.shape
        r = jax.lax.axis_index(model_axis)
        T = b * S
        assert T % ep == 0, (T, ep)
        t_loc = T // ep
        xf = xl.reshape(T, d)
        my = jax.lax.dynamic_slice_in_dim(xf, r * t_loc, t_loc, axis=0)
        weights, ids, aux = route(my, router_w, m)
        cap = capacity(t_loc, m)         # per-source-rank, per-expert capacity
        slot_token, slot_valid, slot_wsrc = _dispatch_indices(ids, m, cap)
        xe = my[slot_token] * slot_valid[:, None].astype(xl.dtype)
        # (E, C, d) -> (ep, e_local*C, d): block j goes to expert-owner rank j
        send = xe.reshape(ep, e_local * cap, d)
        if wire_bf16_a2a:
            send = send.astype(jnp.bfloat16)
        recv = jax.lax.all_to_all(send, model_axis, split_axis=0,
                                  concat_axis=0, tiled=True)
        recv = recv.astype(xl.dtype)
        # recv: (ep * e_local * C, d) == tokens from every source for my experts
        xe_mine = recv.reshape(ep, e_local, cap, d).transpose(1, 0, 2, 3)
        xe_mine = xe_mine.reshape(e_local, ep * cap, d)
        ye = _expert_ffn(w1, w2, w3, xe_mine, act)
        ye = ye.reshape(e_local, ep, cap, d).transpose(1, 0, 2, 3)
        back = ye.reshape(ep, e_local * cap, d)
        if wire_bf16_a2a:
            back = back.astype(jnp.bfloat16)
        got = jax.lax.all_to_all(back, model_axis, split_axis=0,
                                 concat_axis=0, tiled=True)
        got = got.astype(xl.dtype).reshape(m.n_experts * cap, d)
        w_slot = weights.reshape(-1)[slot_wsrc] * slot_valid.astype(jnp.float32)
        contrib = got * w_slot[:, None].astype(xl.dtype)
        y_my = jnp.zeros((t_loc, d), xl.dtype).at[slot_token].add(contrib)
        # reassemble the full local token set across model ranks
        y = jax.lax.all_gather(y_my, model_axis, axis=0, tiled=True)
        aux = jax.lax.pmean(aux, (model_axis,) + tuple(batch_axes))
        return y.reshape(b, S, d), aux

    wspec_in = P(model_axis, fsdp_axes if len(fsdp_axes) > 1 else
                 (fsdp_axes[0] if fsdp_axes else None), None)
    wspec_out = P(model_axis, None,
                  fsdp_axes if len(fsdp_axes) > 1 else
                  (fsdp_axes[0] if fsdp_axes else None))
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(bspec, None, None), P(None, None), wspec_in,
                  wspec_out, wspec_in),
        out_specs=(P(bspec, None, None), P()),
        axis_names={model_axis} | set(batch_axes), check_vma=False)
    y, aux = fn(x, p["router"], p["w1"], p["w2"], p["w3"])
    if "dense" in p:
        y = y + mlp.mlp_apply(p["dense"], x, act=act)
    return y, aux
