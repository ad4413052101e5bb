"""The Model facade: embeddings + block pattern (scanned) + head, with
train / prefill / decode entry points for every assigned architecture.

Layer stacking: the repeating block pattern is scanned (`lax.scan`) over
`pattern_repeats` with parameters stacked on a leading dim — this keeps the
HLO small enough to compile 480B-parameter configs against a 512-device mesh
in seconds (see DESIGN.md §6). Leading layers of their own kind
(`lead_layers`, DeepSeek's dense first layer) run once before the scan, a
non-divisible remainder ("tail") after it; both are unrolled. Smoke tests
run the same code with 1-2 repeats on CPU.

Modality frontends are stubs per the assignment: VLMs consume precomputed
patch embeddings (projected into d_model), audio models consume precomputed
frame embeddings; everything from there on is real.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import planner as pl
from repro.models import blocks, common, moe


@dataclasses.dataclass(frozen=True)
class Batch:
    """Model inputs. `tokens` (B, S) int32; labels/mask same shape (train).
    img_embeds (B, n_img, d_vision) for VLMs; frame_embeds (B, n_frames,
    d_input) for audio enc-dec."""

    tokens: jax.Array
    labels: Optional[jax.Array] = None
    mask: Optional[jax.Array] = None
    img_embeds: Optional[jax.Array] = None
    frame_embeds: Optional[jax.Array] = None


jax.tree_util.register_dataclass(
    Batch, data_fields=["tokens", "labels", "mask", "img_embeds",
                        "frame_embeds"], meta_fields=[])


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ---------------- parameter definitions ----------------

    def param_defs(self) -> dict:
        cfg = self.cfg
        d = cfg.d_model
        defs: dict = {
            "embed": pl.ParamDef((cfg.vocab, d), pl.K_EMBED, cfg.dtype,
                                 init="scaled", init_scale=0.02),
            "ln_f": blocks.norm_defs(d, cfg),
        }
        if not cfg.tie_embeddings:
            defs["head"] = pl.ParamDef((d, cfg.vocab), pl.K_HEAD, cfg.dtype)
        if cfg.vlm_img_tokens:
            defs["img_proj"] = pl.ParamDef((cfg.vlm_d_vision, d),
                                           pl.K_REPLICATED, cfg.dtype)
        if cfg.learned_positions:
            defs["pos_emb"] = pl.ParamDef((cfg.learned_positions, d),
                                          pl.K_REPLICATED, cfg.dtype,
                                          init="scaled", init_scale=0.02)
        if cfg.encoder is not None:
            enc: dict = {
                "blocks": common.stack_defs(blocks.block_defs("enc", cfg),
                                            cfg.encoder.n_layers),
                "pos": pl.ParamDef((cfg.encoder.n_frames, d), pl.K_REPLICATED,
                                   cfg.dtype, init="scaled", init_scale=0.02),
                "ln_f": blocks.norm_defs(d, cfg),
            }
            if cfg.encoder.d_input != d:
                enc["in_proj"] = pl.ParamDef((cfg.encoder.d_input, d),
                                             pl.K_REPLICATED, cfg.dtype)
            defs["encoder"] = enc
        if cfg.lead_layers:
            defs["lead"] = {
                f"l{i}_{kind}": blocks.block_defs(kind, cfg)
                for i, kind in enumerate(cfg.lead_layers)
            }
        reps = cfg.pattern_repeats
        if reps > 0:
            defs["blocks"] = {
                f"p{i}_{kind}": common.stack_defs(blocks.block_defs(kind, cfg),
                                                  reps)
                for i, kind in enumerate(cfg.block_pattern)
            }
        if cfg.tail_layers:
            defs["tail"] = {
                f"t{i}_{kind}": blocks.block_defs(kind, cfg)
                for i, kind in enumerate(cfg.tail_layers)
            }
        return defs

    def init(self, key: jax.Array) -> dict:
        return common.init_tree(key, self.param_defs())

    def n_params(self) -> int:
        return common.count_params(self.param_defs())

    # paths whose leaves have a leading stacked (scan) dimension
    @staticmethod
    def stacked_path(path: tuple) -> bool:
        for p in path:
            key = getattr(p, "key", None)
            if key in ("blocks",):
                return True
        return False

    # ---------------- helpers ----------------

    def _ctx(self, enc_out=None, window_override=None, moe_impl="gather",
             kv_chunk=None, kv_dtype="native", mesh=None,
             batch_axes=("data",), fsdp_axes=(),
             wgather_wire="bf16", tp_axis=None,
             gmm_backend="ragged") -> blocks.BlockCtx:
        return blocks.BlockCtx(cfg=self.cfg, window_override=window_override,
                               enc_out=enc_out, moe_impl=moe_impl,
                               kv_chunk=kv_chunk, kv_dtype=kv_dtype,
                               mesh=mesh, batch_axes=batch_axes,
                               fsdp_axes=fsdp_axes,
                               wgather_wire=wgather_wire, tp_axis=tp_axis,
                               gmm_backend=gmm_backend)

    def _embed(self, params: dict, batch: Batch, *, pos0: int = 0) -> jax.Array:
        cfg = self.cfg
        h = jnp.take(params["embed"], batch.tokens, axis=0)
        if cfg.embed_scale:
            h = h * jnp.sqrt(jnp.array(cfg.d_model, h.dtype))
        if cfg.vlm_img_tokens and batch.img_embeds is not None:
            img = batch.img_embeds.astype(cfg.dtype) @ params["img_proj"]
            h = jnp.concatenate([img, h], axis=1)
        if cfg.learned_positions:
            S = h.shape[1]
            h = h + jax.lax.dynamic_slice_in_dim(params["pos_emb"], pos0, S,
                                                 axis=0)[None]
        return h

    def _encode(self, params: dict, frame_embeds: jax.Array) -> jax.Array:
        cfg = self.cfg
        p = params["encoder"]
        h = frame_embeds.astype(cfg.dtype)
        if "in_proj" in p:
            h = h @ p["in_proj"]
        h = h + p["pos"][None]
        ctx = self._ctx()

        def body(carry, pslice):
            hh, _ = blocks.block_apply("enc", pslice, carry, ctx)
            return hh, None

        h, _ = jax.lax.scan(body, h, p["blocks"])
        return blocks.norm_apply(p["ln_f"], h, cfg)

    def _run_blocks(self, params: dict, h: jax.Array, ctx: blocks.BlockCtx):
        """The lead layers, the scanned pattern repeats, then the tail.
        Returns (h, stats summed over layers: moe.merge_stats)."""
        cfg = self.cfg
        stats0 = moe.zero_stats(cfg.moe)

        for i, kind in enumerate(cfg.lead_layers):
            def lead(ps, hh, kind=kind):
                return blocks.block_apply(kind, ps, hh, ctx)
            if cfg.remat:
                lead = jax.checkpoint(lead)
            h, st = lead(params["lead"][f"l{i}_{kind}"], h)
            stats0 = moe.merge_stats(stats0, st)

        if cfg.pattern_repeats > 0:
            stacked = tuple(params["blocks"][f"p{i}_{k}"]
                            for i, k in enumerate(cfg.block_pattern))

            def body(carry, pslices):
                hh, stats = carry
                for kind, ps in zip(cfg.block_pattern, pslices):
                    hh, st = blocks.block_apply(kind, ps, hh, ctx)
                    stats = moe.merge_stats(stats, st)
                return (hh, stats), None

            if cfg.remat:
                body = jax.checkpoint(body)
            (h, stats0), _ = jax.lax.scan(body, (h, stats0), stacked)

        for i, kind in enumerate(cfg.tail_layers):
            h, st = blocks.block_apply(kind, params["tail"][f"t{i}_{kind}"],
                                       h, ctx)
            stats0 = moe.merge_stats(stats0, st)
        return h, stats0

    def _head(self, params: dict, h: jax.Array) -> jax.Array:
        cfg = self.cfg
        h = blocks.norm_apply(params["ln_f"], h, cfg)
        w = (params["embed"].T if cfg.tie_embeddings else params["head"])
        logits = h @ w
        if cfg.logit_softcap:
            c = cfg.logit_softcap
            logits = jnp.tanh(logits / c) * c
        return logits

    # ---------------- entry points ----------------

    def forward(self, params: dict, batch: Batch, **ctx_kw) -> jax.Array:
        """Full-sequence logits (training / evaluation)."""
        enc_out = None
        if self.cfg.encoder is not None:
            enc_out = self._encode(params, batch.frame_embeds)
        ctx = self._ctx(enc_out=enc_out, **ctx_kw)
        h = self._embed(params, batch)
        h, self._last_stats = self._run_blocks(params, h, ctx)
        return self._head(params, h)

    def loss(self, params: dict, batch: Batch, *, with_stats: bool = False,
             **ctx_kw):
        """Mean next-token cross-entropy, plus the weighted MoE aux loss.
        with_stats=True returns (loss, stats): for an MoE model
        {"moe/aux": the aux term added}, and on the dropless path
        "moe/held_rows" (assignments on held experts, over the layers) and
        "moe/max_expert_rows" (the largest held group of any layer)."""
        logits = self.forward(params, batch, **ctx_kw)
        cfg = self.cfg
        if cfg.vlm_img_tokens and batch.img_embeds is not None:
            logits = logits[:, batch.img_embeds.shape[1]:]
        loss = common.softmax_xent(logits[:, :-1], batch.labels[:, 1:],
                                   None if batch.mask is None
                                   else batch.mask[:, 1:])
        stats = {}
        if self.cfg.moe is not None:
            aux = self.cfg.moe.router_aux_weight * self._last_stats["aux"]
            loss = loss + aux
            stats = {f"moe/{k}": v for k, v in self._last_stats.items()
                     if k != "aux"}
            stats["moe/aux"] = aux
        return (loss, stats) if with_stats else loss

    # ---------------- serving ----------------

    def init_cache(self, batch: int, max_seq: int, **ctx_kw) -> dict:
        cfg = self.cfg
        ctx = self._ctx(**ctx_kw)
        cache: dict = {}
        if cfg.lead_layers:
            cache["lead"] = {
                f"l{i}_{kind}": blocks.block_init_cache(kind, cfg, batch,
                                                        max_seq, ctx)
                for i, kind in enumerate(cfg.lead_layers)
            }
        if cfg.pattern_repeats > 0:
            cache["blocks"] = {}
            for i, kind in enumerate(cfg.block_pattern):
                one = blocks.block_init_cache(kind, cfg, batch, max_seq, ctx)
                cache["blocks"][f"p{i}_{kind}"] = jax.tree.map(
                    lambda x: jnp.broadcast_to(
                        x[None], (cfg.pattern_repeats,) + x.shape), one)
        if cfg.tail_layers:
            cache["tail"] = {
                f"t{i}_{kind}": blocks.block_init_cache(kind, cfg, batch,
                                                        max_seq, ctx)
                for i, kind in enumerate(cfg.tail_layers)
            }
        return cache

    def prefill(self, params: dict, batch: Batch, max_seq: int, **ctx_kw):
        """Consume the prompt; return (last-token logits, cache, prompt_len).

        The cache is laid out for `decode_step`: windowed blocks get ring
        buffers, full-attention blocks get max_seq slots.
        """
        cfg = self.cfg
        enc_out = None
        if cfg.encoder is not None:
            enc_out = self._encode(params, batch.frame_embeds)
        ctx = self._ctx(enc_out=enc_out, **ctx_kw)
        h = self._embed(params, batch)
        S = h.shape[1]
        cache: dict = {}

        def pad_cache(kind, c):
            """Grow prompt-length K/V buffers to max_seq slots."""
            def grow(x):
                if x.ndim >= 2 and x.shape[1] == S and kind != "ssm":
                    pad = [(0, 0)] * x.ndim
                    pad[1] = (0, max(0, max_seq - S))
                    return jnp.pad(x, pad)
                return x
            if kind in ("attn", "local", "moe", "mla", "mla_moe", "cross"):
                w = (ctx.window_for(kind) if kind not in ("mla", "mla_moe")
                     else ctx.window_override)
                if kind == "cross":
                    return {"self": jax.tree.map(grow, c["self"]),
                            "cross": c["cross"]}
                if not w or w >= max_seq:
                    return jax.tree.map(grow, c)
            return c

        if cfg.lead_layers:
            cache["lead"] = {}
            for i, kind in enumerate(cfg.lead_layers):
                ps = params["lead"][f"l{i}_{kind}"]
                c = blocks.block_prefill_cache(kind, ps, h, cfg, ctx)
                cache["lead"][f"l{i}_{kind}"] = pad_cache(kind, c)
                h, _ = blocks.block_apply(kind, ps, h, ctx)
        if cfg.pattern_repeats > 0:
            stacked = tuple(params["blocks"][f"p{i}_{k}"]
                            for i, k in enumerate(cfg.block_pattern))

            def body(carry, pslices):
                hh = carry
                caches = []
                for kind, ps in zip(cfg.block_pattern, pslices):
                    c = blocks.block_prefill_cache(kind, ps, hh, cfg, ctx)
                    caches.append(pad_cache(kind, c))
                    hh, _ = blocks.block_apply(kind, ps, hh, ctx)
                return hh, tuple(caches)

            h, stacked_caches = jax.lax.scan(body, h, stacked)
            cache["blocks"] = {
                f"p{i}_{kind}": stacked_caches[i]
                for i, kind in enumerate(cfg.block_pattern)
            }
        if cfg.tail_layers:
            cache["tail"] = {}
            for i, kind in enumerate(cfg.tail_layers):
                ps = params["tail"][f"t{i}_{kind}"]
                c = blocks.block_prefill_cache(kind, ps, h, cfg, ctx)
                cache["tail"][f"t{i}_{kind}"] = pad_cache(kind, c)
                h, _ = blocks.block_apply(kind, ps, h, ctx)
        logits = self._head(params, h[:, -1:, :])
        return logits[:, 0, :], cache, S

    def decode_step(self, params: dict, cache: dict, token: jax.Array,
                    pos: jax.Array, **ctx_kw):
        """One-token decode. token (B, 1) int32, pos scalar int32 (number of
        tokens already in the cache). Returns (logits (B, V), new cache)."""
        cfg = self.cfg
        ctx = self._ctx(**ctx_kw)
        h = jnp.take(params["embed"], token, axis=0)
        if cfg.embed_scale:
            h = h * jnp.sqrt(jnp.array(cfg.d_model, h.dtype))
        if cfg.learned_positions:
            h = h + jax.lax.dynamic_slice_in_dim(
                params["pos_emb"], pos, 1, axis=0)[None]
        new_cache: dict = {"blocks": {}, "tail": {}}
        if cfg.lead_layers:
            new_cache["lead"] = {}
            for i, kind in enumerate(cfg.lead_layers):
                key = f"l{i}_{kind}"
                h, new_cache["lead"][key] = blocks.block_decode(
                    kind, params["lead"][key], h, cache["lead"][key], pos, ctx)

        if cfg.pattern_repeats > 0:
            stacked_p = tuple(params["blocks"][f"p{i}_{k}"]
                              for i, k in enumerate(cfg.block_pattern))
            stacked_c = tuple(cache["blocks"][f"p{i}_{k}"]
                              for i, k in enumerate(cfg.block_pattern))

            def body(carry, xs):
                hh = carry
                pslices, cslices = xs
                outs = []
                for kind, ps, cs in zip(cfg.block_pattern, pslices, cslices):
                    hh, c2 = blocks.block_decode(kind, ps, hh, cs, pos, ctx)
                    outs.append(c2)
                return hh, tuple(outs)

            h, new_stacked = jax.lax.scan(body, h, (stacked_p, stacked_c))
            new_cache["blocks"] = {
                f"p{i}_{kind}": new_stacked[i]
                for i, kind in enumerate(cfg.block_pattern)
            }
        if cfg.tail_layers:
            for i, kind in enumerate(cfg.tail_layers):
                key = f"t{i}_{kind}"
                h, c2 = blocks.block_decode(kind, params["tail"][key], h,
                                            cache["tail"][key], pos, ctx)
                new_cache["tail"][key] = c2
        logits = self._head(params, h)
        return logits[:, 0, :], new_cache


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
