"""Per-architecture smoke tests: reduced config, one forward/train step
and one decode step on CPU, asserting output shapes and no NaNs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import model as M

BATCH, SEQ = 2, 32


def make_batch(cfg, batch=BATCH, seq=SEQ):
    key = jax.random.PRNGKey(0)
    b = {
        "tokens": jax.random.randint(key, (batch, seq), 0, cfg.vocab),
        "labels": jax.random.randint(key, (batch, seq), 0, cfg.vocab),
    }
    if cfg.family == "encdec":
        b["frames"] = jax.random.normal(key, (batch, cfg.encoder_frames,
                                               cfg.d_model))
        b["tokens"] = b["tokens"][:, :cfg.decoder_len]
        b["labels"] = b["labels"][:, :cfg.decoder_len]
    if cfg.frontend == "vision_stub":
        b["patch_embeds"] = jax.random.normal(
            key, (batch, cfg.n_patches, cfg.d_model))
    return b


@pytest.fixture(scope="module", params=ARCH_IDS)
def arch_setup(request):
    cfg = get_config(request.param).reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(42))
    return request.param, cfg, params


class TestArchSmoke:
    def test_forward_shapes_and_finite(self, arch_setup):
        arch, cfg, params = arch_setup
        batch = make_batch(cfg)
        hidden, aux = M.forward(params, cfg, batch)
        n_expected = batch["tokens"].shape[1]
        if cfg.frontend == "vision_stub":
            n_expected += cfg.n_patches
        assert hidden.shape == (BATCH, n_expected, cfg.d_model)
        assert bool(jnp.all(jnp.isfinite(hidden.astype(jnp.float32)))), arch
        assert bool(jnp.isfinite(aux))

    @pytest.mark.slow
    def test_loss_and_grad_step(self, arch_setup):
        arch, cfg, params = arch_setup
        batch = make_batch(cfg)
        loss, grads = jax.value_and_grad(
            lambda p: M.loss_fn(p, cfg, batch))(params)
        assert bool(jnp.isfinite(loss)), arch
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                             for g in jax.tree.leaves(grads)))
        assert bool(jnp.isfinite(gnorm)), arch
        assert float(gnorm) > 0, f"{arch}: zero gradient"

    @pytest.mark.slow
    def test_decode_step(self, arch_setup):
        arch, cfg, params = arch_setup
        cache = M.init_decode_state(cfg, BATCH, cache_len=SEQ,
                                    cache_kind="taylor", dtype=jnp.float32)
        if cfg.family == "encdec":
            frames = jax.random.normal(
                jax.random.PRNGKey(1), (BATCH, cfg.encoder_frames, cfg.d_model))
            cache = M.encode_for_decode(params, cfg, frames, cache)
        tok = jnp.zeros((BATCH, 1), jnp.int32)
        for _ in range(3):
            logits, cache = M.decode_step(params, cfg, {"tokens": tok}, cache)
        assert logits.shape == (BATCH, 1, cfg.vocab)
        assert bool(jnp.all(jnp.isfinite(logits))), arch

    def test_param_count_positive(self, arch_setup):
        arch, cfg, params = arch_setup
        n = M.count_params(params)
        assert n > 0
        assert M.count_params_analytic(cfg) == n


class TestFullConfigMetadata:
    """Full (non-reduced) configs: analytic checks only — no allocation."""

    @pytest.mark.parametrize("arch", ARCH_IDS)
    def test_full_config_param_count(self, arch):
        cfg = get_config(arch)
        n = M.count_params_analytic(cfg)
        expected = {
            "whisper-large-v3": (1.2e9, 2.5e9),
            "gemma3-1b": (0.9e9, 1.7e9),
            "yi-9b": (8e9, 10e9),
            "stablelm-1.6b": (1.3e9, 2.1e9),
            "gemma2-27b": (24e9, 30e9),
            "llava-next-34b": (30e9, 38e9),
            "zamba2-7b": (6e9, 8.5e9),
            "granite-4.0-h-micro": (3.0e9, 3.4e9),
            "llama4-maverick-400b-a17b": (360e9, 440e9),
            "grok-1-314b": (290e9, 340e9),
            "xlstm-125m": (0.9e8, 1.6e8),
        }[arch]
        assert expected[0] <= n <= expected[1], f"{arch}: {n/1e9:.2f}B params"

    def test_moe_active_params(self):
        cfg = get_config("llama4-maverick-400b-a17b")
        active = M.count_params_analytic(cfg, active_only=True)
        assert 10e9 <= active <= 25e9, f"{active/1e9:.1f}B active"
