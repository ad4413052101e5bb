"""The plain reference: layers, loss and AdamW in straightforward float32.

Nothing here imports the program. A configuration's reference
(`configs/<name>.py`) builds its loss from these layers; `train` runs the
three reference steps that the comparison follows. Every product goes
through a `Matmul`: float32 at full precision for the reference, or
rounded to fp8 for the control (the precision below the bfloat16 that the
configurations state). The exchange's effect on the mean gradient, as the
job's `comm` states it, is a `Wire`: nothing on a float wire, block-wise
int8 rounding (with its error carried to the next step under error
feedback) on the int8 wire.

The parameters are held in the dtype the configuration states (bfloat16),
as the program holds them. Every step computes in float32 from them; the
gradient comes back in the parameters' dtype, as the program's does (its
rounding, 2**-9 relative, is far below every limit), and AdamW and the clip
run in float32.

Memory: the reference holds the parameters, their gradient, both
float32 AdamW moments and, under error feedback, the float32 residual (12
or 16 bytes a parameter, ~10.5 or ~13.9 GB for the ~0.87B-parameter
configurations), so everything else is computed in blocks: the layers are scanned and each rematerialized, attention runs one
block of queries at a time, and the loss one block of positions at a time.
The arithmetic is the unblocked one's; only what is kept for the backward
pass changes.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import weights

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK = 512              # queries / positions per block


class Matmul:
    """Every product of the reference. `fp8=True` is the control: fp8
    training as its usual recipe has it, each operand rounded to
    float8_e4m3fn in the forward pass and each operand's cotangent to
    float8_e5m2 in the backward pass, one scale per tensor (its largest
    magnitude to the format's largest finite value)."""

    def __init__(self, fp8: bool = False):
        self.fp8 = fp8

    def einsum(self, spec: str, a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        if self.fp8:
            a, b = _fp8(a), _fp8(b)
        return jnp.einsum(spec, a, b, precision=HIGHEST,
                          preferred_element_type=jnp.float32)


def _round(x, dtype):
    """x rounded to `dtype` with one scale per tensor, back in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(jnp.finfo(dtype).max)
    return (x / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def _fp8(x):
    return _round(x, jnp.float8_e4m3fn)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, ct):
    return (_round(ct, jnp.float8_e5m2),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


class Wire:
    """What the gradient exchange does to the mean gradient, from a job's
    `comm` (traffic/<traffic>.json).

    A float wire (`fp32`, `bf16`) hands the mean on as it is: the gradient
    already comes back in the parameters' dtype, and a bf16 sum of a few
    chips' bf16 gradients differs from it by rounding alone.

    The int8 wire sends each leaf's gradient in blocks of INT8_BLOCK
    elements of its row-major flattening (the last block padded with
    zeros), each block as int8 codes and one float32 scale: scale = the
    block's largest magnitude / 127, code = round(y / scale) clipped to
    +-127, received value = code * scale, in the parameters' dtype. Under
    `error_feedback` y is the gradient plus the residual of the step
    before, and the residual becomes y - code * scale, in float32. The
    rounding does not depend on the gradient's scale, so rounding the mean
    of the chips' gradients is rounding their sum and dividing after."""

    INT8_BLOCK = 512

    def __init__(self, comm: dict):
        self.int8 = comm.get("wire") == "int8"
        self.error_feedback = self.int8 and bool(comm.get("error_feedback"))

    def residuals(self, params: dict):
        """Zero residuals on the host, one float32 (blocks, INT8_BLOCK)
        array per leaf (None without error feedback)."""
        if not self.error_feedback:
            return None
        return {k: np.zeros((_blocks(x.size), self.INT8_BLOCK), np.float32)
                for k, x in params.items()}

    def __call__(self, g: dict, residual):
        """(mean gradient, residual) -> (what the optimizer receives, the
        next residual)."""
        if not self.int8:
            return g, residual
        out, new_res = {}, {}
        for k, x in g.items():
            flat = x.astype(jnp.float32).reshape(-1)
            pad = _blocks(x.size) * self.INT8_BLOCK - x.size
            y = (jnp.pad(flat, (0, pad)) if pad else flat).reshape(
                -1, self.INT8_BLOCK)
            if residual is not None:
                y = y + residual[k]
            scale = jnp.max(jnp.abs(y), axis=1, keepdims=True) / 127.0
            code = jnp.clip(jnp.round(y / jnp.where(scale > 0, scale, 1.0)),
                            -127, 127)
            got = code * scale
            out[k] = got.reshape(-1)[: x.size].reshape(x.shape) \
                .astype(x.dtype)
            if residual is not None:
                new_res[k] = y - got
        return out, (new_res if residual is not None else None)


def _blocks(n: int) -> int:
    return -(-n // Wire.INT8_BLOCK)


# --- layers --------------------------------------------------------------------

def rmsnorm(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale.astype(jnp.float32)


def rope(x, theta: float):
    """Rotary embedding over the whole last dim of x (B, S, H, D), the
    halves convention: (x1, x2) -> (x1 cos - x2 sin, x1 sin + x2 cos)."""
    S, D = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, D, 2, dtype=np.float32) / D)
    ang = np.arange(S, dtype=np.float32)[:, None] * inv      # (S, D/2)
    cos = jnp.asarray(np.cos(ang))[:, None, :]
    sin = jnp.asarray(np.sin(ang))[:, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1)


def causal_attention(mm: Matmul, q, k, v):
    """softmax(q k^T / sqrt(Dq), causal) v, one block of queries at a time.
    q, k (B, S, H, Dq); v (B, S, H, Dv)."""
    B, S, H, Dq = q.shape
    qb = BLOCK if S % BLOCK == 0 else S
    scale = 1.0 / math.sqrt(Dq)

    @jax.checkpoint
    def one(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
        s = mm.einsum("bqhd,bkhd->bhqk", qi, k) * scale
        q_pos = i * qb + jnp.arange(qb)
        causal = jnp.arange(S)[None, :] <= q_pos[:, None]
        w = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
        return mm.einsum("bhqk,bkhd->bqhd", w, v)

    out = jax.lax.map(one, jnp.arange(S // qb))      # (n, B, qb, H, Dv)
    return out.transpose(1, 0, 2, 3, 4).reshape(B, S, H, v.shape[-1])


def swiglu(mm: Matmul, x, w1, w3, w2):
    h = jax.nn.silu(mm.einsum("bsd,df->bsf", x, w1)) \
        * mm.einsum("bsd,df->bsf", x, w3)
    return mm.einsum("bsf,fd->bsd", h, w2)


def next_token_xent(mm: Matmul, h, w_head, tokens):
    """Mean cross-entropy of predicting tokens[:, 1:] from h[:, :-1],
    over blocks of positions. h (B, S, d) is the final normed state."""
    B, S, _ = h.shape
    pb = BLOCK if S % BLOCK == 0 else S
    w_head = w_head.astype(jnp.float32)    # its gradient summed in float32
    labels = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    counted = (jnp.arange(S) < S - 1).astype(jnp.float32)

    @jax.checkpoint
    def one(i):
        hi = jax.lax.dynamic_slice_in_dim(h, i * pb, pb, axis=1)
        li = jax.lax.dynamic_slice_in_dim(labels, i * pb, pb, axis=1)
        ci = jax.lax.dynamic_slice_in_dim(counted, i * pb, pb)
        logits = mm.einsum("bsd,dv->bsv", hi, w_head)
        gold = jnp.take_along_axis(logits, li[..., None], axis=-1)[..., 0]
        nll = jax.nn.logsumexp(logits, axis=-1) - gold
        return jnp.sum(nll * ci[None, :])

    total = jnp.sum(jax.lax.map(one, jnp.arange(S // pb)))
    return total / (B * (S - 1))


def layers(layer, h, params: dict, prefix: str):
    """h through every layer of the stacked leaves under `prefix` (leading
    dim = layer), each layer rematerialized in the backward pass:
    layer(p, h) -> h, with p keyed by the rest of each leaf's path."""
    stacked = {path[len(prefix):]: x for path, x in params.items()
               if path.startswith(prefix)}
    h, _ = jax.lax.scan(jax.checkpoint(lambda h, p: (layer(p, h), None)),
                        h, stacked)
    return h


# --- training reference -----------------------------------------------------------

def leaf_norms(tree: dict, n_layers: int) -> dict:
    """L2 norm of each leaf, a stacked leaf ("blocks/...") split into its
    layers: {"path" or "path[i]": norm}. Each is one reduction, so an
    operand made on the fly (weights regenerated from the seed) fuses into
    it and is never held whole."""
    out = {}
    for path, x in tree.items():
        x = x.astype(jnp.float32)
        if path.startswith("blocks/") and x.shape[0] == n_layers:
            per = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
            out.update({f"{path}[{i}]": per[i] for i in range(n_layers)})
        else:
            out[path] = jnp.sqrt(jnp.sum(x * x))
    return out


def change_fn(specs: dict, dtype, n_layers: int):
    """(params, lo, hi) -> norms of each leaf's change from the seed's
    weights, which are regenerated inside the reduction."""
    def change(params: dict, lo, hi):
        return leaf_norms({k: params[k].astype(jnp.float32)
                           - weights.leaf(lo, hi, k, *specs[k], dtype)
                           .astype(jnp.float32) for k in specs}, n_layers)
    return change


def train(ref, cfg: dict, seed: int, batches: list, mm: Matmul,
          wire: Wire, devices) -> dict:
    """Three AdamW steps of the reference from the seed's weights, on the
    batches the program was fed, each mean gradient sent through `wire`.
    Rows are split over `devices`.

    Returns the loss of each step, the norms of the first gradient as
    AdamW takes it (after clipping), the norms of the parameters' change
    after the last step and, under error feedback, of the wire's residual
    after it (else None)."""
    opt = cfg["optimizer"]
    n_layers = cfg["num_hidden_layers"]
    specs = ref.param_specs(cfg)
    stored = jnp.dtype(cfg["param_dtype"])
    mesh = Mesh(np.asarray(devices), ("rows",))
    rep = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P("rows"))
    lo, hi = weights.seed_words(seed)
    gen = weights.generator(specs, stored)

    with jax.default_matmul_precision("highest"):
        params = jax.jit(gen, out_shardings=rep)(lo, hi)

        value_grad = jax.jit(
            jax.value_and_grad(lambda p, t: ref.loss(p, t, cfg, mm)),
            in_shardings=(rep, rows), out_shardings=(rep, rep))

        def update(p, g, m, v, step):
            gn = jnp.sqrt(sum(jnp.sum(x.astype(jnp.float32) ** 2)
                              for x in g.values()))
            scale = jnp.minimum(1.0, opt["clip"] / jnp.maximum(gn, 1e-9))
            c1 = 1 - opt["b1"] ** (step + 1.0)
            c2 = 1 - opt["b2"] ** (step + 1.0)
            new_p, new_m, new_v, used = {}, {}, {}, {}
            for k in p:
                gk = g[k].astype(jnp.float32) * scale
                mk = opt["b1"] * m[k] + (1 - opt["b1"]) * gk
                vk = opt["b2"] * v[k] + (1 - opt["b2"]) * gk * gk
                pk = p[k].astype(jnp.float32)
                u = (mk / c1) / (jnp.sqrt(vk / c2) + opt["eps"]) \
                    + opt["weight_decay"] * pk
                new_p[k] = (pk - opt["lr"] * u).astype(stored)
                new_m[k], new_v[k], used[k] = mk, vk, gk
            return new_p, new_m, new_v, leaf_norms(used, n_layers)

        step_fn = jax.jit(update, donate_argnums=(0, 2, 3),
                          out_shardings=rep)
        send = jax.jit(wire, donate_argnums=(0, 1), out_shardings=rep)
        zeros = jax.jit(lambda p: {k: jnp.zeros(x.shape, jnp.float32)
                                   for k, x in p.items()},
                        out_shardings=rep)
        m, v = zeros(params), zeros(params)
        residual = wire.residuals(params)   # on the host between steps
        losses, grad_norms = [], None
        for t, tokens in enumerate(batches):
            loss, g = value_grad(params, jax.device_put(tokens, rows))
            g, residual = send(g, jax.device_put(residual, rep))
            residual = jax.device_get(residual)
            params, m, v, norms = step_fn(params, g, m, v, np.float32(t))
            del g           # before the next gradient is made beside it
            losses.append(float(loss))
            if grad_norms is None:
                grad_norms = {k: float(x) for k, x in norms.items()}
        del m, v
        res_norms = None
        if residual is not None:
            residual = jax.device_put(residual, rep)
            res_norms = jax.jit(lambda r: leaf_norms(
                {k: r[k].reshape(-1)[: x.size].reshape(x.shape)
                 for k, x in params.items()}, n_layers))(residual)
            res_norms = {k: float(x) for k, x in res_norms.items()}
        del residual
        norms = jax.jit(change_fn(specs, stored, n_layers))(params, lo, hi)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": {k: float(x) for k, x in norms.items()},
            "residual_norms": res_norms}
