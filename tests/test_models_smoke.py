"""Per-architecture smoke tests: reduced config, one forward + one train
step on CPU, asserting output shapes and finiteness. FULL configs are only
shape-checked (param counts vs nameplate) — never allocated."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get_arch
from repro.core import make_optimizer
from repro.data import make_dataset
from repro.launch.train import make_tx
from repro.models import (count_params, forward, init_params,
                          logits_from_hidden, param_shapes)
from repro.training import init_state, make_train_step

NAMEPLATE_B = {
    "deepseek-67b": (60, 75), "qwen2-7b": (7, 8.5), "granite-3-8b": (8.1, 8.3),
    "mistral-large-123b": (115, 130), "mamba2-370m": (0.3, 0.5),
    "llama-3.2-vision-11b": (9, 12), "dbrx-132b": (125, 140),
    "deepseek-v3-671b": (660, 685), "jamba-1.5-large-398b": (390, 405),
    "musicgen-medium": (1.2, 2.2),
}


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_param_count(arch):
    cfg = get_arch(arch)
    n = count_params(param_shapes(cfg)) / 1e9
    lo, hi = NAMEPLATE_B[arch]
    assert lo <= n <= hi, f"{arch}: {n:.2f}B outside [{lo},{hi}]"


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_train_step(arch):
    cfg = get_arch(arch, smoke=True)
    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    B, S = 2, 32
    ds = make_dataset(cfg, seq_len=S, global_batch=B)
    batch = ds.host_batch_at(0)

    hidden, _, aux = forward(params, cfg, batch["tokens"],
                             image_embeds=batch.get("image_embeds"))
    assert hidden.shape == (B, S, cfg.d_model)
    assert bool(jnp.all(jnp.isfinite(hidden)))

    logits = logits_from_hidden(params, cfg, hidden)
    if cfg.family == "audio":
        assert logits.shape == (B, cfg.n_codebooks, S, cfg.padded_vocab)
    else:
        assert logits.shape == (B, S, cfg.padded_vocab)
    # padded vocab entries are masked to -inf-ish
    if cfg.padded_vocab != cfg.vocab_size:
        assert float(jnp.max(logits[..., cfg.vocab_size:])) <= -1e8

    tx = make_tx(cfg, "scale", 1e-3)
    step = jax.jit(make_train_step(cfg, tx))
    state = init_state(params, tx)
    state, metrics = step(state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    state, m2 = step(state, batch)
    assert bool(jnp.isfinite(m2["loss"]))


def test_deepseek_v3_active_params():
    cfg = get_arch("deepseek-v3-671b")
    active = count_params(param_shapes(cfg), cfg=cfg, active_only=True) / 1e9
    assert 34 <= active <= 40  # official: 37B activated


def test_jamba_active_params():
    cfg = get_arch("jamba-1.5-large-398b")
    active = count_params(param_shapes(cfg), cfg=cfg, active_only=True) / 1e9
    assert 85 <= active <= 100  # official: 94B active


def test_granite_vocab_padding():
    cfg = get_arch("granite-3-8b")
    assert cfg.vocab_size == 49155 and cfg.padded_vocab % 128 == 0


@pytest.mark.parametrize("arch", ["gpt2-medium", "qwen2-500m", "gemma-2b"])
def test_appendix_f_archs_smoke(arch):
    """Paper Appendix F architectures (GPT2 / Qwen2-500M / Gemma-2B):
    reduced-width one-train-step smoke incl. learned-pos + GELU paths."""
    import dataclasses
    cfg = get_arch(arch)
    cfg = dataclasses.replace(
        cfg, n_layers=2, d_model=64,
        n_heads=4, n_kv_heads=min(cfg.n_kv_heads, 2), head_dim=16, d_ff=128,
        vocab_size=256, dtype="float32", max_position=64,
        attn_kv_block=16, attn_q_block=16, loss_chunk=16)
    params = init_params(jax.random.PRNGKey(0), cfg)
    ds = make_dataset(cfg, seq_len=32, global_batch=2)
    tx = make_optimizer("scale", 1e-3)
    step = jax.jit(make_train_step(cfg, tx))
    state = init_state(params, tx)
    state, metrics = step(state, ds.host_batch_at(0))
    assert bool(jnp.isfinite(metrics["loss"]))
    if cfg.pos_embed == "learned":
        assert state.opt_state.mu["pos_embed"]["w"].size == 0  # stateless
