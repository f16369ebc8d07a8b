"""Mamba2 (SSD) block — the SSM half of zamba2-7b.

Chunked "state-space dual" algorithm (Dao & Gu, 2024) in pure JAX:
intra-chunk quadratic term + inter-chunk recurrent state carried with a
``lax.scan`` over chunks. TaylorShift is *inapplicable* here (no
attention); the block is implemented faithfully as the substrate the
hybrid architecture needs (docs/design.md §Arch-applicability).

Decode: constant-size per-layer state — causal-conv tail (width-1 window)
plus the SSM state h ∈ (B, H, S, P). Chunked prefill
(:func:`mamba2_prefill`) starts from that state and returns it, so a
prompt absorbed in chunks of any length reaches the state one pass over
the whole prompt would.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L

Params = dict[str, Any]


def _dims(cfg: ModelConfig):
    s = cfg.ssm
    d_inner = s.expansion * cfg.d_model
    n_heads = d_inner // s.head_dim
    return d_inner, n_heads, s.head_dim, s.state, s.n_groups


def mamba2_init(key, cfg: ModelConfig) -> Params:
    s = cfg.ssm
    d_inner, H, P, S, G = _dims(cfg)
    dt = cfg.param_dtype
    ks = jax.random.split(key, 5)
    # in_proj packs [z (gate), x, B, C, dt] like the reference impl.
    d_in_proj = 2 * d_inner + 2 * G * S + H
    p: Params = {
        "in_proj": L.dense_init(ks[0], cfg.d_model, d_in_proj, dt),
        "out_proj": L.dense_init(ks[1], d_inner, cfg.d_model, dt),
        "conv_w": (jax.random.normal(ks[2], (s.conv_width, d_inner + 2 * G * S),
                                     jnp.float32) * 0.1).astype(dt),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H, dtype=jnp.float32)),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "norm": L.rmsnorm_init(d_inner),
    }
    if s.conv_bias:
        p["conv_b"] = jnp.zeros((d_inner + 2 * G * S,), dt)
    return p


def _split_in_proj(cfg, zxbcdt):
    d_inner, H, P, S, G = _dims(cfg)
    z, xbc, dt_raw = jnp.split(
        zxbcdt, [d_inner, 2 * d_inner + 2 * G * S], axis=-1)
    return z, xbc, dt_raw


def _causal_conv(params, x, tail):
    """Depthwise causal conv of width W (plus its bias, where the block has
    one), then SiLU. x: (B, N, C); tail: (B, W-1, C), the inputs before
    x (zeros at a sequence's start). Returns (out, the new tail)."""
    w = params["conv_w"]
    W = w.shape[0]
    full = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    out = sum(full[:, i:i + x.shape[1]] * w[i][None, None] for i in range(W))
    if "conv_b" in params:
        out = out + params["conv_b"]
    return (jax.nn.silu(out.astype(jnp.float32)).astype(x.dtype),
            full[:, full.shape[1] - (W - 1):])


def _gated_norm(params, cfg: ModelConfig, y, z, dtype):
    """Mamba2's gated RMSNorm: the gate before the norm,
    rmsnorm(y · silu(z)), as in the reference Mamba2 and HF's
    ``MambaRMSNormGated``."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    return L.rmsnorm(params["norm"], g, cfg.rms_norm_eps).astype(dtype)


def _ssd_chunked(xh, dt, A, Bm, Cm, chunk, h0=None):
    """SSD core. xh: (B,N,H,P), dt: (B,N,H), A: (H,), Bm/Cm: (B,N,G,S);
    h0: (B,H,S,P) state before the first token (zeros where None).

    Returns (y (B,N,H,P), the state after the last token). G divides H
    (heads share B/C within a group).
    """
    b, n, h, p = xh.shape
    g = Bm.shape[2]
    assert n % chunk == 0
    nc = n // chunk
    rep = h // g
    Bh = jnp.repeat(Bm, rep, axis=2)                    # (B,N,H,S)
    Ch = jnp.repeat(Cm, rep, axis=2)

    # discretized log-decay per step: a_t = -A * dt_t  (A > 0)
    loga = (-A[None, None] * dt).astype(jnp.float32)    # (B,N,H) (<= 0)
    xdt = (xh * dt[..., None]).astype(jnp.float32)      # input scaled by dt

    def r(t, shape):  # reshape into chunks
        return t.reshape(b, nc, chunk, *shape)

    loga_c = r(loga, (h,))
    x_c = r(xdt, (h, p))
    B_c = r(Bh, (h, Bh.shape[-1]))
    C_c = r(Ch, (h, Ch.shape[-1]))

    cs = jnp.cumsum(loga_c, axis=2)                      # (B,nc,C,H)
    # --- intra-chunk (quadratic within chunk) ---
    # L[i,j] = exp(cs_i - cs_j + loga_j)   for i >= j  … standard SSD form:
    # decay from j..i inclusive of step j's own a? Convention: h_t = a_t h_{t-1} + B_t x_t
    # => y_i gets B_j x_j decayed by prod_{t=j+1..i} a_t = exp(cs_i - cs_j).
    scores = jnp.einsum("bzihs,bzjhs->bzhij", C_c, B_c)
    ci = cs.transpose(0, 1, 3, 2)                        # (B,nc,H,C)
    expo = ci[..., :, None] - ci[..., None, :]           # [i,j] = cs_i - cs_j
    mask = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(mask[None, None, None], expo, -jnp.inf))
    y_intra = jnp.einsum("bzhij,bzhij,bzjhp->bzihp", scores, decay, x_c)

    # --- chunk states & inter-chunk scan ---
    # state contribution of chunk z: sum_j exp(cs_end - cs_j) B_j ⊗ x_j
    end = cs[:, :, -1:, :]                               # (B,nc,1,H)
    w = jnp.exp(end - cs)                                # (B,nc,C,H)
    states = jnp.einsum("bzjh,bzjhs,bzjhp->bzhsp", w, B_c, x_c)
    chunk_decay = jnp.exp(end[:, :, 0])                  # (B,nc,H)

    def scan_fn(hprev, inp):
        st, dec = inp                                    # (B,H,S,P), (B,H)
        hnew = hprev * dec[..., None, None] + st
        return hnew, hprev

    if h0 is None:
        h0 = jnp.zeros((b, h, Bh.shape[-1], p), jnp.float32)
    h_last, h_prefix = jax.lax.scan(
        scan_fn, h0,
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)))
    h_prefix = h_prefix.transpose(1, 0, 2, 3, 4)         # (B,nc,H,S,P) excl.

    # y_inter[i] = C_i · (exp(cs_i) * h_prefix)
    y_inter = jnp.einsum("bzihs,bzih,bzhsp->bzihp",
                         C_c, jnp.exp(cs), h_prefix)
    y = (y_intra + y_inter).reshape(b, n, h, p)
    return y, h_last


def _mixer(params: Params, cfg: ModelConfig, x, conv_tail, h0):
    """The block over (B, N, d_model) from a conv tail and SSM state.
    Returns (y, new conv tail, new state)."""
    s = cfg.ssm
    d_inner, H, P, S, G = _dims(cfg)
    zxbcdt = L.dense(params["in_proj"], x)
    z, xbc, dt_raw = _split_in_proj(cfg, zxbcdt)
    xbc, tail = _causal_conv(params, xbc, conv_tail)
    xs, Bm, Cm = jnp.split(xbc, [d_inner, d_inner + G * S], axis=-1)
    b, n, _ = x.shape
    xh = xs.reshape(b, n, H, P)
    Bm = Bm.reshape(b, n, G, S)
    Cm = Cm.reshape(b, n, G, S)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + params["dt_bias"][None, None])
    A = jnp.exp(params["A_log"])
    chunk = min(s.chunk, n)
    while n % chunk:
        chunk //= 2
    y, h = _ssd_chunked(xh, dt, A, Bm, Cm, max(chunk, 1), h0)
    y = y + params["D"][None, None, :, None] * xh.astype(jnp.float32)
    y = _gated_norm(params, cfg, y.reshape(b, n, d_inner), z, x.dtype)
    return L.dense(params["out_proj"], y), tail, h


def mamba2_apply(params: Params, cfg: ModelConfig, x: jnp.ndarray
                 ) -> jnp.ndarray:
    """x: (B, N, d_model) -> (B, N, d_model), from the zero state."""
    d_inner, _, _, S, G = _dims(cfg)
    tail = jnp.zeros((x.shape[0], cfg.ssm.conv_width - 1,
                      d_inner + 2 * G * S), x.dtype)
    return _mixer(params, cfg, x, tail, None)[0]


def mamba2_prefill(params: Params, cfg: ModelConfig, x: jnp.ndarray, cache):
    """Absorb a (B, C, d_model) chunk into a decode cache (the conv tail
    and the SSM state every row carries): the chunked-prefill site.
    The SSD chunk is ``min(ssm.chunk, C)``. Returns (y, new cache)."""
    y, tail, h = _mixer(params, cfg, x, cache["conv"], cache["h"])
    return y, {"conv": tail.astype(cache["conv"].dtype), "h": h}


# ---------------------------------------------------------------------------
# Decode (constant state)
# ---------------------------------------------------------------------------

def mamba2_init_cache(cfg: ModelConfig, batch: int, dtype=jnp.float32):
    s = cfg.ssm
    d_inner, H, P, S, G = _dims(cfg)
    return {
        "conv": jnp.zeros((batch, s.conv_width - 1, d_inner + 2 * G * S), dtype),
        "h": jnp.zeros((batch, H, S, P), jnp.float32),
    }


def mamba2_decode(params: Params, cfg: ModelConfig, x: jnp.ndarray, cache):
    """x: (B, 1, d_model). Returns (y, cache)."""
    s = cfg.ssm
    d_inner, H, P, S, G = _dims(cfg)
    zxbcdt = L.dense(params["in_proj"], x)
    z, xbc, dt_raw = _split_in_proj(cfg, zxbcdt)
    conv_in = jnp.concatenate([cache["conv"], xbc.astype(cache["conv"].dtype)], axis=1)
    w = params["conv_w"]
    W = w.shape[0]
    out = jnp.sum(conv_in[:, -W:] * w[None], axis=1, keepdims=True)
    if "conv_b" in params:
        out = out + params["conv_b"]
    xbc = jax.nn.silu(out.astype(jnp.float32)).astype(x.dtype)
    new_conv = conv_in[:, 1:]

    xs, Bm, Cm = jnp.split(xbc, [d_inner, d_inner + G * S], axis=-1)
    b = x.shape[0]
    xh = xs.reshape(b, H, P)
    Bm = jnp.repeat(Bm.reshape(b, G, S), H // G, axis=1)
    Cm = jnp.repeat(Cm.reshape(b, G, S), H // G, axis=1)
    dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32) + params["dt_bias"])
    A = jnp.exp(params["A_log"])
    dec = jnp.exp(-A[None] * dt)                          # (B,H)
    hnew = (cache["h"] * dec[..., None, None]
            + jnp.einsum("bhs,bhp,bh->bhsp", Bm.astype(jnp.float32),
                         xh.astype(jnp.float32), dt))
    y = jnp.einsum("bhs,bhsp->bhp", Cm.astype(jnp.float32), hnew)
    y = y + params["D"][None, :, None] * xh.astype(jnp.float32)
    y = _gated_norm(params, cfg, y.reshape(b, 1, d_inner), z, x.dtype)
    return L.dense(params["out_proj"], y), {"conv": new_conv, "h": hnew}
