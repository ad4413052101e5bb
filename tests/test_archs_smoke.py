"""Per-architecture smoke tests (deliverable f): a REDUCED variant of each
assigned architecture runs one train step and one decode step on CPU with
correct shapes and no NaNs."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import registry
from repro.configs.shapes import SHAPES
from repro.core.planner import Planner
from repro.launch import mesh as mesh_lib
from repro.models.transformer import Batch, Model
from repro.optim import optimizers as opt_lib
from repro.train import trainer as tr


@pytest.fixture(scope="module")
def mesh():
    return mesh_lib.make_host_mesh()


def _batch(cfg, key, B, S, with_labels=True):
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab)
    kw = {}
    if cfg.vlm_img_tokens:
        kw["img_embeds"] = jax.random.normal(
            key, (B, cfg.vlm_img_tokens, cfg.vlm_d_vision))
    if cfg.encoder is not None:
        kw["frame_embeds"] = jax.random.normal(
            key, (B, cfg.encoder.n_frames, cfg.encoder.d_input))
    return Batch(tokens=tokens, labels=tokens if with_labels else None, **kw)


@pytest.mark.parametrize("arch", registry.ARCH_IDS)
def test_smoke_train_step(arch, mesh):
    cfg = registry.get_smoke_config(arch)
    assert cfg.d_model <= 512 and cfg.n_layers <= 6
    if cfg.moe is not None:
        assert cfg.moe.n_experts <= 4
    model = Model(cfg)
    opt = opt_lib.adamw(1e-3)
    planner = Planner(mesh=mesh)
    with jax.set_mesh(mesh):
        state = tr.make_train_state(model, opt, jax.random.PRNGKey(0))
        step = jax.jit(tr.make_train_step(model, opt, mesh, planner,
                                          tr.CommConfig()))
        batch = _batch(cfg, jax.random.PRNGKey(1), B=2, S=24)
        new_state, metrics = step(state, batch)
    assert jnp.isfinite(metrics["loss"]), arch
    assert int(new_state.step) == 1
    # params changed and are finite
    leaves = jax.tree_util.tree_leaves(new_state.params)
    assert all(bool(jnp.all(jnp.isfinite(l))) for l in leaves), arch


@pytest.mark.parametrize("arch", registry.ARCH_IDS)
def test_smoke_decode_step(arch, mesh):
    cfg = registry.get_smoke_config(arch)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 12
    batch = _batch(cfg, jax.random.PRNGKey(1), B, S, with_labels=False)
    logits, cache, pos = model.prefill(params, batch, max_seq=S + 8)
    assert logits.shape == (B, cfg.vocab)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    logits2, cache2 = model.decode_step(params, cache, tok, jnp.int32(pos))
    assert logits2.shape == (B, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits2))), arch


@pytest.mark.parametrize("arch", registry.ARCH_IDS)
def test_full_config_matches_assignment(arch):
    """The FULL configs carry the exact assigned hyperparameters."""
    cfg = registry.get_config(arch)
    expected = {
        "yi-6b": (32, 4096, 64000), "llava-next-mistral-7b": (32, 4096, 32000),
        "minicpm3-4b": (62, 2560, 73448), "arctic-480b": (35, 7168, 32000),
        "chatglm3-6b": (28, 4096, 65024), "mamba2-2.7b": (64, 2560, 50280),
        "recurrentgemma-2b": (26, 2560, 256000),
        "grok-1-314b": (64, 6144, 131072),
        "whisper-small": (12, 768, 51865), "deepseek-7b": (30, 4096, 102400),
        "deepseek-v2-lite": (27, 2048, 102400),
    }[arch]
    assert (cfg.n_layers, cfg.d_model, cfg.vocab) == expected
    assert cfg.source
    if arch == "arctic-480b":
        assert cfg.moe.n_experts == 128 and cfg.moe.top_k == 2
        assert cfg.moe.dense_residual_ff > 0
    if arch == "grok-1-314b":
        assert cfg.moe.n_experts == 8 and cfg.moe.top_k == 2
    if arch == "deepseek-v2-lite":
        assert cfg.moe.n_experts == 64 and cfg.moe.top_k == 6
        assert cfg.moe.shared_ff == 2 * 1408 and cfg.moe.dropless
        assert cfg.lead_layers == ("mla",) and cfg.pattern_repeats == 26
        assert cfg.mla.q_lora_rank == 0 and cfg.mla.yarn.factor == 40
    if arch == "mamba2-2.7b":
        assert cfg.ssm.d_state == 128 and cfg.attn is None
    if arch == "recurrentgemma-2b":
        assert cfg.block_pattern == ("rglru", "rglru", "local")


def test_shapes_match_assignment():
    assert SHAPES["train_4k"].seq_len == 4096
    assert SHAPES["train_4k"].global_batch == 256
    assert SHAPES["prefill_32k"].seq_len == 32768
    assert SHAPES["prefill_32k"].global_batch == 32
    assert SHAPES["decode_32k"].global_batch == 128
    assert SHAPES["long_500k"].seq_len == 524288
    assert SHAPES["long_500k"].global_batch == 1
