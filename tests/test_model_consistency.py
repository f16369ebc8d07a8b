"""Consistency of the two execution paths every serving system needs:
full-sequence forward (train/prefill) vs token-by-token decode, plus
sequential oracles for the SSD and mLSTM cells."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import mamba2 as M2
from repro.models import model as M
from repro.models import xlstm as XL

SEQ = 24
BATCH = 2


def _logits_forward(cfg, params, tokens):
    hidden, _ = M.forward(params, cfg, {"tokens": tokens})
    return M.logits_from_hidden(params, cfg, hidden)


def _logits_decode(cfg, params, tokens, cache_kind):
    cache = M.init_decode_state(cfg, tokens.shape[0], cache_len=SEQ,
                                cache_kind=cache_kind, dtype=jnp.float32)
    outs = []
    step = jax.jit(lambda b, c: M.decode_step(params, cfg, b, c))
    for t in range(tokens.shape[1]):
        lg, cache = step({"tokens": tokens[:, t:t+1]}, cache)
        outs.append(lg)
    return jnp.concatenate(outs, axis=1)


@pytest.mark.parametrize("arch,cache_kind", [
    ("stablelm-1.6b", "taylor"),
    ("stablelm-1.6b", "kv"),
    pytest.param("gemma3-1b", "taylor", marks=pytest.mark.slow),
    pytest.param("zamba2-7b", "taylor", marks=pytest.mark.slow),
    # cache_kind ignored for xlstm: state blocks
    pytest.param("xlstm-125m", "taylor", marks=pytest.mark.slow),
])
def test_decode_matches_forward(arch, cache_kind):
    cfg = get_config(arch).reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (BATCH, SEQ), 0,
                                cfg.vocab)
    lg_fwd = _logits_forward(cfg, params, tokens)
    lg_dec = _logits_decode(cfg, params, tokens, cache_kind)
    np.testing.assert_allclose(np.asarray(lg_fwd), np.asarray(lg_dec),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.slow
def test_whisper_decode_matches_forward():
    cfg = get_config("whisper-large-v3").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    frames = jax.random.normal(jax.random.PRNGKey(2),
                               (BATCH, cfg.encoder_frames, cfg.d_model))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (BATCH, cfg.decoder_len),
                                0, cfg.vocab)
    hidden, _ = M.forward(params, cfg, {"tokens": tokens, "frames": frames})
    lg_fwd = M.logits_from_hidden(params, cfg, hidden)

    cache = M.init_decode_state(cfg, BATCH, cache_len=cfg.decoder_len,
                                cache_kind="taylor", dtype=jnp.float32)
    cache = M.encode_for_decode(params, cfg, frames, cache)
    outs = []
    for t in range(tokens.shape[1]):
        lg, cache = M.decode_step(params, cfg, {"tokens": tokens[:, t:t+1]},
                                  cache)
        outs.append(lg)
    lg_dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(lg_fwd), np.asarray(lg_dec),
                               rtol=5e-3, atol=5e-3)


# ---------------------------------------------------------------------------
# Cell-level oracles
# ---------------------------------------------------------------------------

def _ssd_sequential(xh, dt, A, Bm, Cm, h0=None):
    """Naive O(N) recurrence: h_t = exp(-A dt_t) h_{t-1} + B_t (x_t dt_t).
    Returns (y, h after the last token)."""
    b, n, h, p = xh.shape
    g = Bm.shape[2]
    rep = h // g
    Bh = np.repeat(np.asarray(Bm), rep, axis=2)
    Ch = np.repeat(np.asarray(Cm), rep, axis=2)
    xh, dt, A = map(np.asarray, (xh, dt, A))
    S = Bh.shape[-1]
    hstate = np.zeros((b, h, S, p)) if h0 is None else np.asarray(h0)
    ys = np.zeros_like(xh)
    for t in range(n):
        dec = np.exp(-A[None] * dt[:, t])            # (b, h)
        hstate = hstate * dec[..., None, None] + np.einsum(
            "bhs,bhp->bhsp", Bh[:, t], xh[:, t] * dt[:, t][..., None])
        ys[:, t] = np.einsum("bhs,bhsp->bhp", Ch[:, t], hstate)
    return ys, hstate


def _ssd_inputs(b=2, n=16, h=4, p=8, s=8, g=2):
    key = jax.random.PRNGKey(3)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    xh = jax.random.normal(k1, (b, n, h, p))
    dt = jax.nn.softplus(jax.random.normal(k2, (b, n, h)))
    A = jnp.exp(jax.random.normal(k3, (h,)) * 0.5)
    Bm = jax.random.normal(k4, (b, n, g, s))
    Cm = jax.random.normal(jax.random.fold_in(key, 9), (b, n, g, s))
    return xh, dt, A, Bm, Cm


@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_ssd_chunked_matches_sequential(chunk):
    xh, dt, A, Bm, Cm = _ssd_inputs()
    y_chunked, _ = M2._ssd_chunked(xh, dt, A, Bm, Cm, chunk)
    y_seq, _ = _ssd_sequential(xh, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y_chunked), y_seq,
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("chunk", [1, 4, 16])
def test_ssd_chunked_from_state_matches_sequential(chunk):
    """Chunked prefill's core: SSD from a nonzero state returns the
    outputs and the final state of the per-token recurrence. float32 on
    both sides, summed in another order: 1e-4 holds them; a state dropped
    at the start (h0 ignored) misses by O(1)."""
    xh, dt, A, Bm, Cm = _ssd_inputs()
    h0 = jax.random.normal(jax.random.PRNGKey(11), (2, 4, 8, 8))
    y, h = M2._ssd_chunked(xh, dt, A, Bm, Cm, chunk, h0)
    y_seq, h_seq = _ssd_sequential(xh, dt, A, Bm, Cm, h0)
    np.testing.assert_allclose(np.asarray(y), y_seq, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), h_seq, rtol=1e-4, atol=1e-4)


def _tiny_granite(**kw):
    """A [mamba, mamba, global] granite-shaped model: MLP after every
    mixer, conv bias, GQA 4/2, no positions, the three muP multipliers."""
    from repro.configs.base import ModelConfig, SSMConfig
    cfg = ModelConfig(
        name="tiny-granite", family="hybrid", n_layers=3, d_model=32,
        n_heads=4, n_kv_heads=2, head_dim=8, d_ff=64, vocab=97, norm="rms",
        rms_norm_eps=1e-5, pos_embed="none", dtype="float32", remat=False,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        logits_divisor=8.0, layer_pattern=("mamba", "mamba", "global"),
        ssm=SSMConfig(state=8, head_dim=8, expansion=2, conv_width=4,
                      n_groups=1, chunk=8, conv_bias=True, mlp=True), **kw)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    # nonzero biases and norm scales, so each term reaches the logits
    params = jax.tree.map(
        lambda a: a + 0.3 * jax.random.normal(jax.random.PRNGKey(1), a.shape),
        params)
    return cfg, params


@pytest.mark.parametrize("chunk", [1, 8, 24])
def test_prompt_absorbed_in_chunks_matches_one_pass(chunk):
    """A 24-token prompt through chunked prefill in chunks of 1, 8 or all
    24 gives the logits of one full-sequence forward: the conv tail and
    the SSM state are handed from chunk to chunk. float32, the same
    arithmetic in another order: 1e-4 of the logits' scale."""
    cfg, params = _tiny_granite()
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 24), 0, cfg.vocab)
    want = np.asarray(_logits_forward(cfg, params, tokens))
    cache = M.init_decode_state(cfg, 1, cache_len=32, dtype=jnp.float32)
    step = jax.jit(lambda t, c: M.prefill_chunk(params, cfg, {"tokens": t},
                                                c))
    got = []
    for lo in range(0, 24, chunk):
        lg, cache = step(tokens[:, lo:lo + chunk], cache)
        got.append(lg)
    got = np.asarray(jnp.concatenate(got, axis=1))
    np.testing.assert_allclose(got, want, atol=1e-4 * np.abs(want).max())
    # and decoding on from the absorbed state continues the sequence
    nxt = jnp.asarray([[5]])
    lg, _ = M.decode_step(params, cfg, {"tokens": nxt}, cache)
    full = _logits_forward(cfg, params, jnp.concatenate([tokens, nxt], 1))
    np.testing.assert_allclose(np.asarray(lg[:, -1]),
                               np.asarray(full[:, -1]),
                               atol=1e-4 * np.abs(want).max())


def test_gated_norm_gates_before_the_norm():
    """Mamba2's gated RMSNorm is rmsnorm(y * silu(z)) * (1 + scale), as
    in Mamba2 and HF's MambaRMSNormGated; float32, one op order apart."""
    cfg, params = _tiny_granite()
    p = jax.tree.map(lambda a: a[0], params["groups"][0]["mamba"])
    y = np.asarray(jax.random.normal(jax.random.PRNGKey(3), (2, 5, 64)))
    z = np.asarray(jax.random.normal(jax.random.PRNGKey(4), (2, 5, 64)))
    g = y * z / (1 + np.exp(-z))
    want = (g / np.sqrt(np.mean(g * g, -1, keepdims=True) + 1e-5)
            * (1 + np.asarray(p["norm"]["scale"])))
    got = M2._gated_norm(p, cfg, jnp.asarray(y), jnp.asarray(z), jnp.float32)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_mamba2_decode_matches_prefill():
    cfg = get_config("zamba2-7b").reduced()
    params = M2.mamba2_init(jax.random.PRNGKey(4), cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (BATCH, 16, cfg.d_model))
    y_full = M2.mamba2_apply(params, cfg, x)
    cache = M2.mamba2_init_cache(cfg, BATCH)
    ys = []
    for t in range(16):
        y, cache = M2.mamba2_decode(params, cfg, x[:, t:t+1], cache)
        ys.append(y)
    y_dec = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_full), np.asarray(y_dec),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.slow
def test_mlstm_decode_matches_prefill():
    cfg = get_config("xlstm-125m").reduced()
    params = XL.mlstm_init(jax.random.PRNGKey(6), cfg)
    x = jax.random.normal(jax.random.PRNGKey(7), (BATCH, 16, cfg.d_model))
    y_full = XL.mlstm_apply(params, cfg, x)
    cache = XL.mlstm_init_cache(cfg, BATCH)
    ys = []
    for t in range(16):
        y, cache = XL.mlstm_decode(params, cfg, x[:, t:t+1], cache)
        ys.append(y)
    y_dec = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_full), np.asarray(y_dec),
                               rtol=2e-3, atol=2e-3)


def test_slstm_decode_matches_scan():
    cfg = get_config("xlstm-125m").reduced()
    params = XL.slstm_init(jax.random.PRNGKey(8), cfg)
    x = jax.random.normal(jax.random.PRNGKey(9), (BATCH, 12, cfg.d_model))
    y_full = XL.slstm_apply(params, cfg, x)
    cache = XL.slstm_init_cache(cfg, BATCH)
    ys = []
    for t in range(12):
        y, cache = XL.slstm_decode(params, cfg, x[:, t:t+1], cache)
        ys.append(y)
    np.testing.assert_allclose(np.asarray(y_full),
                               np.asarray(jnp.concatenate(ys, 1)),
                               rtol=1e-4, atol=1e-4)
