"""Per-architecture smoke tests: reduced config, one forward + grad step and
one decode step on CPU; asserts shapes and finiteness (assignment brief)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.types import ServeConfig, replace
from repro.configs import ARCH_IDS, get_config, get_reduced
from repro.models import decode as D
from repro.models import transformer as T

B, S = 2, 64
KEY = jax.random.PRNGKey(0)


def _batch(cfg):
    tokens = jax.random.randint(KEY, (B, S), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    if cfg.frontend != "none":
        batch["embeds"] = jax.random.normal(
            KEY, (B, S, cfg.d_model)).astype(jnp.bfloat16) * 0.02
    return batch


# tier-1 keeps four cheap, family-diverse configs (dense/GQA, MLA, audio
# frontend, MLA + shared experts behind a dense layer); the rest are
# slow-marked and run with `pytest -m ""`
_FAST_ARCHS = {"llama3_8b", "minicpm3_4b", "musicgen_medium",
               "deepseek_v2_lite"}
_ARCH_PARAMS = [a if a in _FAST_ARCHS else
                pytest.param(a, marks=pytest.mark.slow) for a in ARCH_IDS]


@pytest.mark.parametrize("arch", _ARCH_PARAMS)
def test_forward_and_grad(arch):
    cfg = get_reduced(arch)
    params, axes = T.init_params(KEY, cfg)
    # axes tree mirrors params tree
    assert set(axes.keys()) == set(params.keys())
    batch = _batch(cfg)
    logits, aux = T.forward(params, batch, cfg)
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))

    loss, metrics = T.loss_fn(params, batch, cfg)
    assert bool(jnp.isfinite(loss))
    grads = jax.grad(lambda p: T.loss_fn(p, batch, cfg)[0])(params)
    gnorm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                         for g in jax.tree_util.tree_leaves(grads)))
    assert bool(jnp.isfinite(gnorm)) and float(gnorm) > 0


@pytest.mark.parametrize("arch", _ARCH_PARAMS)
def test_decode_step(arch):
    cfg = get_reduced(arch)
    scfg = ServeConfig(hot_window=16, attn_chunk=32, kv_rate_bits=8)
    max_len = 128
    params, _ = T.init_params(KEY, cfg)
    cache = D.init_cache(cfg, scfg, B, max_len)
    tokens = jax.random.randint(KEY, (B,), 0, cfg.vocab_size)
    pos = jnp.asarray([0, 5], jnp.int32)
    embeds = (jax.random.normal(KEY, (B, cfg.d_model)).astype(jnp.bfloat16)
              if cfg.frontend != "none" else None)
    logits, cache2 = D.decode_step(params, cache, tokens, pos, cfg, scfg,
                                   embeds=embeds)
    assert logits.shape == (B, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    # structure preserved
    assert jax.tree_util.tree_structure(cache) == \
        jax.tree_util.tree_structure(cache2)


def test_full_config_param_counts():
    """FULL configs instantiate only as metadata (no allocation) — sanity of
    the published sizes (loose bands; active counts for MoE)."""
    expect = {
        "chameleon_34b": (25e9, 45e9), "qwen3_moe_235b_a22b": (150e9, 300e9),
        "arctic_480b": (350e9, 560e9), "deepseek_7b": (5e9, 9e9),
        "minicpm3_4b": (2.5e9, 6e9), "codeqwen15_7b": (5e9, 9e9),
        "llama3_8b": (6e9, 10e9), "zamba2_2p7b": (2e9, 4.5e9),
        "musicgen_medium": (1e9, 2.5e9), "falcon_mamba_7b": (5e9, 9e9),
        "deepseek_v2_lite": (15e9, 16.5e9),
    }
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, f"{arch}: {n/1e9:.2f}B outside [{lo/1e9},{hi/1e9}]"
