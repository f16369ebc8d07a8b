"""Plain float32 reference of granite-4.0-h's Mamba2 / attention hybrid.

Used by the configurations whose ``reference`` is ``granite_hybrid``
(granite-4.0-h-micro). Written from the source's description
(``granitemoehybrid`` in HF transformers: its config.json, Mamba2 after
Dao & Gu, arXiv:2405.21060) and the configuration file alone; it imports
nothing of the program, and takes the float8 control, the matrix product
and the l2 normalisation from ``taylor_lm.py`` beside it. It reads the
parameter tree by its key names (``embed/emb``,
``groups/<position>/mamba/in_proj/w``, ...), which the benchmark fills
from the seed; layer ``l`` is pattern position ``l % P`` of group
``l // P``.

Per layer, pre-norm, with the muP multipliers of the configuration::

    x = x + r . Mixer(RMSN1(x))          (Mamba2, or Taylor attention)
    x = x + r . Wdown (silu(Wgate z) * Wup z),  z = RMSN2(x)

    RMSN(x) = x / sqrt(mean(x^2) + eps) * (1 + scale)

The input is ``emb[token] * embedding_multiplier``, the output
``RMSN_f(x) . emb^T / logits_divisor`` (tied). The program stores a
norm's weight as ``1 + scale``; it is read the same way here.

Mamba2, one token at a time (the plain recurrence, not the chunked
form): ``[z, xBC, dt] = Win u``; xBC goes through a causal depthwise
conv of width W with its bias, then silu, and splits into x (H heads of
P), B and C (G groups of S); ``dt = softplus(dt + dt_bias)``,
``A = -exp(A_log)``::

    h_t = exp(A dt_t) h_{t-1} + dt_t B_t (x) x_t
    y_t = C_t . h_t + D x_t
    out = Wout (RMSN_gated(y * silu(z)))

Attention: TaylorShift (Taylor-softmax of order 2 over l2-normalised q
and k, a learned temperature per head, output scaled by
sqrt(keys / head_dim)), grouped kv heads, no positions; direct form, one
head at a time, in blocks of queries so that a 16k-token prompt fits.

Every matrix product of the projections, the attention and the read-out
runs at ``Precision.HIGHEST`` on float32 operands; the weights stay as
stored and are cast one layer at a time. ``rounding="fp8"`` is the
control: both operands of each of those products rounded to float8 e4m3
(``taylor_lm.py``), the precision below the configuration's bf16.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp


def _sibling(name: str):
    path = Path(__file__).with_name(f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_ref_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


TL = _sibling("taylor_lm")
QUERY_BLOCK = 512


def _rms(p, x, eps):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y * (1.0 + p["scale"].astype(jnp.float32))


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _mlp(p, z, rnd):
    up = TL._mm("nd,df->nf", z, p["up"]["w"], rnd)
    gate = TL._mm("nd,df->nf", z, p["gate"]["w"], rnd)
    return TL._mm("nf,fd->nd", up * jax.nn.silu(gate), p["down"]["w"], rnd)


def _mamba(p, u, m, rnd):
    """One Mamba2 mixer over a sequence u: (N, d_model)."""
    s = m["ssm"]
    d_inner = s["expansion"] * m["d_model"]
    H, P, S, G = d_inner // s["head_dim"], s["head_dim"], s["state"], \
        s["n_groups"]
    n = u.shape[0]
    zxbcdt = TL._mm("nd,de->ne", u, p["in_proj"]["w"], rnd)
    z = zxbcdt[:, :d_inner]
    xbc = zxbcdt[:, d_inner:2 * d_inner + 2 * G * S]
    dt = zxbcdt[:, 2 * d_inner + 2 * G * S:]
    w = p["conv_w"]                                   # (W, channels)
    W = w.shape[0]
    padded = jnp.concatenate([jnp.zeros((W - 1, xbc.shape[1])), xbc])
    conv = sum(padded[i:i + n] * w[i] for i in range(W))
    if "conv_b" in p:
        conv = conv + p["conv_b"]
    xbc = jax.nn.silu(conv)
    x = xbc[:, :d_inner].reshape(n, H, P)
    Bm = jnp.repeat(xbc[:, d_inner:d_inner + G * S].reshape(n, G, S),
                    H // G, 1)
    Cm = jnp.repeat(xbc[:, d_inner + G * S:].reshape(n, G, S), H // G, 1)
    dt = jax.nn.softplus(dt + p["dt_bias"])           # (N, H)
    A = -jnp.exp(p["A_log"])

    def step(h, t):
        x_t, B_t, C_t, dt_t = t
        h = (jnp.exp(A * dt_t)[:, None, None] * h
             + dt_t[:, None, None] * B_t[:, :, None] * x_t[:, None, :])
        return h, jnp.einsum("hs,hsp->hp", C_t, h, precision=TL.HIGHEST)

    _, y = jax.lax.scan(step, jnp.zeros((H, S, P)), (x, Bm, Cm, dt))
    y = (y + p["D"][:, None] * x).reshape(n, d_inner)
    y = _rms(p["norm"], y * jax.nn.silu(z), m["rms_norm_eps"])
    return TL._mm("ne,ed->nd", y, p["out_proj"]["w"], rnd)


def _taylor_head(q, k, v, tau, rnd):
    """One causal head, direct form, in blocks of queries. q, k, v:
    (N, d), N a multiple of the block."""
    n, d = q.shape
    q, k = TL._unit(q) * tau, TL._unit(k)
    blocks = q.reshape(n // QUERY_BLOCK, QUERY_BLOCK, d)

    def block(args):
        qb, start = args
        s = TL._mm("nd,md->nm", qb, k, rnd)
        rows = start + jnp.arange(QUERY_BLOCK)[:, None]
        a = jnp.where(jnp.arange(n)[None, :] <= rows, 1.0 + s + 0.5 * s * s,
                      0.0)
        y = TL._mm("nm,md->nd", a / jnp.sum(a, -1, keepdims=True), v, rnd)
        return y * jnp.sqrt((rows + 1.0) / d)

    starts = jnp.arange(0, n, QUERY_BLOCK)
    return jax.lax.map(block, (blocks, starts)).reshape(n, d)


def _attention(p, u, m, rnd):
    """Grouped Taylor attention with no positions over u: (N, d_model)."""
    n = u.shape[0]
    h, kv, dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    q = TL._mm("nd,de->ne", u, p["wq"]["w"], rnd).reshape(n, h, dh)
    k = TL._mm("nd,de->ne", u, p["wk"]["w"], rnd).reshape(n, kv, dh)
    v = TL._mm("nd,de->ne", u, p["wv"]["w"], rnd).reshape(n, kv, dh)
    q, k, v = (t.transpose(1, 0, 2) for t in (q, k, v))
    k, v = jnp.repeat(k, h // kv, 0), jnp.repeat(v, h // kv, 0)
    y = jax.lax.map(lambda args: _taylor_head(*args, rnd),
                    (q, k, v, p["tau"]))
    y = y.transpose(1, 0, 2).reshape(n, h * dh)
    return TL._mm("ne,ed->nd", y, p["wo"]["w"], rnd)


def _block(kind, p, x, m, rnd):
    """One layer of kind ``mamba`` or ``global`` over x: (N, d_model)."""
    p = _f32(p)
    r, eps = m["residual_multiplier"], m["rms_norm_eps"]
    z = _rms(p["norm1"], x, eps)
    mix = (_mamba(p["mamba"], z, m, rnd) if kind == "mamba"
           else _attention(p["attn"], z, m, rnd))
    x = x + r * mix
    if "mlp" in p:
        x = x + r * _mlp(p["mlp"], _rms(p["norm2"], x, eps), rnd)
    return x


def layers(params, m):
    """(kind, parameters) of each layer in order."""
    pattern = m["layer_pattern"]
    for i in range(m["n_layers"]):
        kind = pattern[i % len(pattern)]
        yield kind, jax.tree.map(lambda a, g=i // len(pattern): a[g],
                                 params["groups"][i % len(pattern)])


_SERVE_JITS: dict = {}


def _serve_fns(m, rounding):
    key = (id(m), rounding)
    if key not in _SERVE_JITS:
        rnd = TL.ROUNDINGS[rounding]
        sm = TL._Static(m)
        blk = {kind: jax.jit(lambda p, x, kind=kind: _block(kind, p, x, sm,
                                                            rnd))
               for kind in set(m["layer_pattern"])}

        def readout(p, h, idx):
            h = _rms(_f32(p["final_norm"]), h[idx], sm["rms_norm_eps"])
            lg = TL._mm("rd,vd->rv", h, p["embed"]["emb"], rnd)
            return lg / sm["logits_divisor"]

        def embed(p, t):
            x = p["embed"]["emb"][t].astype(jnp.float32)
            return x * sm["embedding_multiplier"]

        _SERVE_JITS[key] = (jax.jit(embed), blk, jax.jit(readout))
    return _SERVE_JITS[key]


def logits_at(params, m, tokens, positions, *, rounding="fp32", pad_to=1024):
    """Logits (len(positions), vocab) over ``tokens`` (one sequence), at
    ``positions``. The sequence is padded at its end to a multiple of
    ``pad_to``, which a causal model never reads back, so that few
    shapes compile."""
    n = len(tokens)
    padded = -(-n // pad_to) * pad_to
    toks = jnp.zeros((padded,), jnp.int32).at[:n].set(
        jnp.asarray(tokens, jnp.int32))
    emb, blk, out = _serve_fns(m, rounding)
    with jax.default_matmul_precision("highest"):
        x = emb(params, toks)
        for kind, p in layers(params, m):
            x = blk[kind](p, x)
        return out(params, x, jnp.asarray(positions, jnp.int32))


def layer_flops(m, kind, n_past, n_new):
    """Least forward FLOPs of one ``mamba`` layer for ``n_new`` tokens of
    one sequence (its state is constant, so ``n_past`` does not enter):
    the in- and out-projections, the depthwise conv, the recurrence's
    update ``dt B (x) x`` and read-out ``C . h`` (2 H S P each), and the
    gated MLP after the mixer where the block has one."""
    if kind != "mamba":
        raise ValueError(f"granite_hybrid counts only mamba layers, not "
                         f"{kind!r}")
    s, d = m["ssm"], m["d_model"]
    d_inner = s["expansion"] * d
    heads = d_inner // s["head_dim"]
    channels = d_inner + 2 * s["n_groups"] * s["state"]
    per_token = (2 * d * (d_inner + channels + heads)
                 + 2 * d_inner * d
                 + 2 * s["conv_width"] * channels
                 + 4 * heads * s["state"] * s["head_dim"])
    if s.get("mlp"):
        per_token += 2 * 3 * d * m["d_ff"]
    return n_new * per_token
