"""Mixture-of-Experts layers: top-k token-choice routing.

Three dispatch implementations. `moe_dropless` computes every routed row:

  * `moe_dropless` (DeepSeek-style, dropless): the (token, k) assignments
    that land on the experts this chip holds (MoEConfig.held) are sorted by
    expert and run through grouped matmuls (kernels/gmm.py) over the real
    group sizes, then gate-weighted and summed back per token, plus the
    shared experts. The router stays n_experts wide. The dispatch, expert
    and combine buffers are static at R rows (`held_row_bound`: twice the
    held experts' uniform share of the T * top_k assignments): the held
    ones are the first rows of the sorted order, and where they outrun R
    the further ones run in more chunks of R rows, so no routing drops a
    row. Only combine's forward gather and dispatch's way back read all
    T * top_k assignments, from the R rows. Where R would not be less
    than T * top_k (all experts held) the buffers are T * top_k rows. The
    grouped matmuls' work follows the routed rows either way. Its scopes
    name the stages: moe/route, moe/dispatch, moe/experts, moe/combine,
    moe/shared.

The other two are capacity-based (GShard semantics, overflow tokens dropped
from the expert path but preserved by the residual):

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
from repro.kernels import gmm as gmm_lib
from repro.models import common, mlp


def moe_defs(d_model: int, m: MoEConfig, dtype) -> dict:
    if m.held is not None and not m.dropless:
        raise ValueError("MoEConfig.held needs the dropless path")
    e = m.held_range[1]
    d = {
        "router": pl.ParamDef((d_model, m.n_experts), pl.K_REPLICATED,
                              jnp.float32 if m.router_fp32 else dtype),
        "w1": pl.ParamDef((e, d_model, m.d_ff), pl.K_EXPERT_IN, dtype),
        "w2": pl.ParamDef((e, m.d_ff, d_model), pl.K_EXPERT_OUT, dtype),
        "w3": pl.ParamDef((e, d_model, m.d_ff), pl.K_EXPERT_IN, dtype),
    }
    if m.dense_residual_ff:
        d["dense"] = mlp.mlp_defs(d_model, m.dense_residual_ff, dtype)
    if m.shared_ff:
        d["shared"] = mlp.mlp_defs(d_model, m.shared_ff, dtype)
    return d


def zero_stats(m: MoEConfig | None) -> dict:
    """The per-layer statistics a block reports, at zero: the aux loss,
    and on the dropless path the rows routed to held experts, the largest
    held group and whether the layer ran on R rows (bounded_layers)."""
    z = jnp.zeros((), jnp.float32)
    if m is not None and m.dropless:
        return {"aux": z, "held_rows": z, "max_expert_rows": z,
                "bounded_layers": z}
    return {"aux": z}


def merge_stats(total: dict, layer: dict) -> dict:
    """Running statistics over layers: sums, and maxima for max_*."""
    out = dict(total)
    for k, v in layer.items():
        out[k] = jnp.maximum(out[k], v) if k.startswith("max_") \
            else out[k] + v
    return out


# the dropless layer's bounded buffers hold this many times the held
# experts' share of the assignments under uniform routing
HELD_HEADROOM = 2


def held_row_bound(n_tokens: int, m: MoEConfig) -> int:
    """R, the rows of the dropless layer's bounded buffers for n_tokens
    tokens: HELD_HEADROOM times the held experts' uniform share of the
    n_tokens * top_k assignments, in whole grouped-matmul row tiles, and
    never more than n_tokens * top_k."""
    tk = n_tokens * m.top_k
    share = -(-HELD_HEADROOM * tk * m.held_range[1] // m.n_experts)
    tile = gmm_lib.TILE_M
    return min(tk, -(-share // tile) * tile)


def capacity(n_tokens: int, m: MoEConfig) -> int:
    c = int(math.ceil(n_tokens * m.top_k * m.capacity_factor / m.n_experts))
    return max(8, ((c + 7) // 8) * 8)     # sublane-aligned


def route(xf: jax.Array, router_w: jax.Array, m: MoEConfig, *,
          batch: int = 1):
    """xf (T, d), the rows of `batch` sequences -> (weights (T, k), ids
    (T, k), aux_loss scalar). Logits in float32 whatever the router's
    dtype; softmax scores, greedy top-k, renormalised if
    m.norm_topk_prob, times m.routed_scaling."""
    logits = (xf.astype(jnp.float32) @ router_w.astype(jnp.float32)
              ).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        weights = weights / jnp.maximum(
            jnp.sum(weights, axis=-1, keepdims=True), 1e-9)
    if m.routed_scaling != 1.0:
        weights = weights * m.routed_scaling
    if m.aux == "seq":
        return weights, ids, seq_aux(probs, ids, m, batch)
    # load-balance auxiliary loss (Switch/GShard): E * sum_e f_e * p_e
    T = xf.shape[0]
    me = jnp.mean(probs, axis=0)
    one_hot = jax.nn.one_hot(ids[:, 0], m.n_experts, dtype=jnp.float32)
    ce = jnp.sum(one_hot, axis=0) / T
    aux = m.n_experts * jnp.sum(me * ce)
    return weights, ids, aux


def seq_aux(probs, ids, m: MoEConfig, batch: int):
    """DeepSeek's sequence-wise balance loss (unweighted): per sequence b,
    ce[b, e] = its top-k slots on expert e / (S k / E), and the loss is
    mean_b sum_e ce[b, e] * mean_t probs[b, t, e]."""
    E, k = m.n_experts, m.top_k
    S = probs.shape[0] // batch
    slots = jnp.sum(jax.nn.one_hot(ids, E, dtype=jnp.float32), axis=1)
    ce = jnp.sum(slots.reshape(batch, S, E), axis=1) / (S * k / E)
    p_mean = jnp.mean(probs.reshape(batch, S, E), axis=1)
    return jnp.mean(jnp.sum(ce * p_mean, axis=-1))


def _take_or_zero(a, idx):
    """a[idx] where 0 <= idx < len(a), else zero: the rows of a chunk of
    the sorted assignments, read back by all T * k of them."""
    n = a.shape[0]
    zero = jnp.zeros((1,) + a.shape[1:], a.dtype)
    return jnp.concatenate([a, zero])[
        jnp.where((idx >= 0) & (idx < n), idx, n)]


@jax.custom_vjp
def _dispatch(xf, token, inv):
    """Rows xf[token] (token: the row of each sorted assignment, all of
    them or a chunk's); the way back gathers each assignment's cotangent
    through the inverse permutation `inv` (shifted to the chunk's first
    row: zero outside it) and sums a token's k of them (no scatter)."""
    return xf[token]


def _dispatch_fwd(xf, token, inv):
    return xf[token], (inv, xf.shape[0])


def _dispatch_bwd(res, g):
    inv, T = res
    k = inv.shape[0] // T
    g = g[inv] if g.shape[0] == inv.shape[0] else _take_or_zero(g, inv)
    dx = jnp.sum(g.reshape(T, k, -1).astype(jnp.float32), axis=1)
    return dx.astype(g.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _permute(y, idx, inv):
    """y[idx] for a permutation idx whose inverse is inv: its transpose is
    the gather y[inv], not a scatter."""
    return y[idx]


def _permute_fwd(y, idx, inv):
    return y[idx], (idx, inv)


def _permute_bwd(res, g):
    return g[res[1]], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


@jax.custom_vjp
def _combine(ys, gate, order, inv):
    """sum_k gate[t, k] * ys[row of (t, k)] in float32, (T, d), for the
    rows ys of a chunk `order` of the sorted assignments, `inv` their
    inverse permutation shifted to the chunk's first row; an assignment
    outside the chunk reads zero. The way back builds ys's cotangent on
    the chunk's rows alone, and the gate's from them through `inv`."""
    T, K = gate.shape
    yf = _take_or_zero(ys, inv).reshape(T, K, -1)
    return jnp.sum(yf.astype(jnp.float32) * gate[..., None], axis=1)


def _combine_fwd(ys, gate, order, inv):
    return _combine(ys, gate, order, inv), (ys, gate, order, inv)


def _combine_bwd(res, g):
    ys, gate, order, inv = res
    T, K = gate.shape
    g_rows = g[order // K]                                  # (R, d) f32
    d_ys = (g_rows * gate.reshape(-1)[order][:, None]).astype(ys.dtype)
    d_gate = _take_or_zero(
        jnp.sum(g_rows * ys.astype(jnp.float32), axis=-1), inv)
    return d_ys, d_gate.reshape(T, K), None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _routed(rows, act, spec, lo, xf, w1, w2, w3, weights, held, order, inv,
            sizes):
    """The routed experts' part of the layer, float32 (T, d), on buffers
    of the sorted assignments lo .. lo + rows: all T * k of them (lo 0),
    or a chunk of R (`order` padded to whole chunks)."""
    T, K = held.shape
    with jax.named_scope("moe/dispatch"):
        if rows < T * K:
            order = jax.lax.dynamic_slice_in_dim(order, lo, rows)
            ends = jnp.clip(jnp.cumsum(sizes) - lo, 0, rows)
            sizes = jnp.diff(ends, prepend=0)               # the chunk's
            inv = inv - lo
        xs = _dispatch(xf, order // K, inv)                 # (rows, d)
    with jax.named_scope("moe/experts"):
        f = common.act_fn(act)
        h = f(gmm_lib.grouped_matmul(xs, w1, sizes, spec)) \
            * gmm_lib.grouped_matmul(xs, w3, sizes, spec)
        ys = gmm_lib.grouped_matmul(h, w2, sizes, spec)   # pad rows 0
    with jax.named_scope("moe/combine"):
        gate = jnp.where(held, weights, 0.0)
        if rows < T * K:
            return _combine(ys, gate, order, inv)
        yf = _permute(ys, inv, order).reshape(T, K, -1)
        return jnp.sum(yf.astype(jnp.float32) * gate[..., None], axis=1)


def _chunks(sizes, rows):
    """How many chunks of `rows` sorted assignments hold the held ones."""
    return -(-jnp.sum(sizes) // rows)


def _padded(order, rows):
    pad = -order.shape[0] % rows
    return jnp.pad(order, (0, pad)) if pad else order


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _chunked(rows, act, spec, xf, w1, w2, w3, weights, held, order, inv,
             sizes):
    """`_routed` over the sorted assignments in chunks of R = rows: the
    first chunk, then a loop over any further chunks the held assignments
    fill, which takes no step where they fit in R. Every buffer is R rows
    and no routing drops a row. Its residuals are its inputs: the way back
    recomputes each chunk, adding a further chunk's gradients in float32
    to the first's, rounded to their dtype once per chunk."""
    run = functools.partial(_routed, rows, act, spec)
    diff, rest = (xf, w1, w2, w3, weights), (held, _padded(order, rows),
                                             inv, sizes)
    return jax.lax.fori_loop(
        1, _chunks(sizes, rows),
        lambda c, y: y + run(c * rows, *diff, *rest), run(0, *diff, *rest))


def _chunked_fwd(rows, act, spec, *args):
    return _chunked(rows, act, spec, *args), args


def _chunked_bwd(rows, act, spec, res, g):
    xf, w1, w2, w3, weights, held, order, inv, sizes = res
    diff, rest = (xf, w1, w2, w3, weights), (held, _padded(order, rows),
                                             inv, sizes)

    def grads(lo):
        def f(*d):
            return _routed(rows, act, spec, lo, *d, *rest)
        return jax.vjp(f, *diff)[1](g)

    def add(c, acc):
        return tuple((a.astype(jnp.float32) + b.astype(jnp.float32)).astype(
            a.dtype) for a, b in zip(acc, grads(c * rows)))

    return (*jax.lax.fori_loop(1, _chunks(sizes, rows), add, grads(0)),
            None, None, None, None)


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def moe_dropless(p: dict, x: jax.Array, m: MoEConfig, *, act: str = "silu",
                 gmm_backend: str = "ragged"):
    """Dropless MoE over the held experts. x (B, S, d) -> (y, stats):
    stats = {aux, held_rows, max_expert_rows, bounded_layers} of this
    layer. Its buffers hold R = held_row_bound(B * S, m) rows; the held
    assignments fit in one such chunk (bounded_layers 1) or run over more
    (bounded_layers 0). Where R = T * top_k the buffers hold every
    assignment and bounded_layers is 0. gmm_backend: kernels/gmm.py's
    (the train step picks the compiled kernels on a TPU:
    trainer.make_train_step)."""
    B, S, d = x.shape
    T, K = B * S, m.top_k
    first, count = m.held_range
    spec = gmm_lib.Spec(backend=gmm_backend)
    xf = x.reshape(T, d)
    with jax.named_scope("moe/route"):
        weights, ids, aux = route(xf, p["router"], m, batch=B)
    with jax.named_scope("moe/dispatch"):
        local = ids - first
        held = (local >= 0) & (local < count)               # (T, K)
        key = jnp.where(held, local, count).reshape(-1)     # (T*K,)
        # sorts and a dense count, no scatter: their cost is the same for
        # any routing; the sort is stable and non-held keys sort last, so
        # the held assignments are order[:sum(sizes)]
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        inv = jnp.argsort(order).astype(jnp.int32)
        sizes = jnp.sum(key[:, None] == jnp.arange(count, dtype=key.dtype),
                        axis=0, dtype=jnp.int32)
    rows = held_row_bound(T, m)
    args = (xf, p["w1"], p["w2"], p["w3"], weights, held, order, inv, sizes)
    if rows == T * K:
        y = _routed(rows, act, spec, 0, *args)
        bounded = jnp.zeros((), jnp.float32)
    else:
        y = _chunked(rows, act, spec, *args)
        bounded = (_chunks(sizes, rows) <= 1).astype(jnp.float32)
    with jax.named_scope("moe/combine"):
        y = y.astype(x.dtype).reshape(B, S, d)
    if "shared" in p:
        with jax.named_scope("moe/shared"):
            y = y + mlp.mlp_apply(p["shared"], x, act=act)
    stats = {"aux": aux, "held_rows": jnp.sum(sizes).astype(jnp.float32),
             "max_expert_rows": jnp.max(sizes).astype(jnp.float32),
             "bounded_layers": bounded}
    return y, stats


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
