"""Plain float32 reference of the Taylor-softmax transformer.

Used by the configurations whose ``reference`` is ``taylor_lm``
(stablelm-1.6b, taylorshift-lra). Written from the paper (arXiv:2403.02920,
Eq. 1 and section 3.3) and the configuration files alone; it imports
nothing of the program. It reads the parameter tree by its key names
(``embed/emb``, ``groups/0/attn/wq/w``, ...), which the benchmark fills
from the seed.

Per block, pre-LayerNorm (eps 1e-5)::

    x = x + Wo . TaylorAttn(RoPE(Wq z), RoPE(Wk z), Wv z),  z = LN1(x)
    x = x + Wdown . MLP(z),                                 z = LN2(x)

    TaylorAttn: q <- tau q / |q|,  k <- k / |k|,  a_ij = 1 + s + s^2 / 2
                with s = q_i . k_j over the visible keys j,
                y_i = sum_j a_ij v_j / sum_j a_ij * sqrt(n_i / d)

with ``n_i`` the number of keys row i sees. The input is
``emb[token] * sqrt(d_model)`` (plus a learned position row), the output
``LN_f(x)`` read out through the tied embedding or a dense ``unembed``.
The attention is the direct form, one head at a time. Every matrix
product runs at ``Precision.HIGHEST`` on float32 operands.

``rounding="fp8"`` is the control: the same computation with both
operands of every matrix product first rounded to float8 e4m3, each
tensor scaled so that its largest magnitude maps to 448. It is the
precision below the bf16 the configurations state.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
LN_EPS = 1e-5
NORM_EPS = 1e-6
F8_MAX = 448.0


def _exact(x):
    return x.astype(jnp.float32)


def _fp8(x):
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


ROUNDINGS = {"fp32": _exact, "fp8": _fp8}


def _mm(spec, a, b, rnd):
    return jnp.einsum(spec, rnd(a), rnd(b), precision=HIGHEST)


def _ln(p, x):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return ((x - mu) / jnp.sqrt(var + LN_EPS) * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32))


def _rope(x, theta):
    """Rotary embedding of (..., N, d): the two halves of a head rotate
    as pairs (x_i, x_{i+d/2}) by angle pos * theta^(-2i/d)."""
    n, d = x.shape[-2], x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _unit(x):
    sq = jnp.sum(x * x, -1, keepdims=True)
    ok = sq > NORM_EPS * NORM_EPS
    return jnp.where(ok, x / jnp.sqrt(jnp.where(ok, sq, 1.0)), 0.0)


def _taylor_head(q, k, v, tau, causal, rnd):
    """One head, direct form. q, k, v: (N, d)."""
    n, d = q.shape
    s = _mm("nd,md->nm", _unit(q) * tau, _unit(k), rnd)
    a = 1.0 + s + 0.5 * s * s
    if causal:
        a = jnp.where(jnp.tril(jnp.ones((n, n), bool)), a, 0.0)
        counts = jnp.arange(1, n + 1, dtype=jnp.float32)[:, None]
    else:
        counts = jnp.full((n, 1), float(n))
    y = _mm("nm,md->nd", a / jnp.sum(a, -1, keepdims=True), v, rnd)
    return y * jnp.sqrt(counts / d)


def _block(p, x, m, rnd):
    """One pre-LN block over a single sequence x: (N, d_model)."""
    n = x.shape[0]
    h, kv, dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    z = _ln(p["norm1"], x)
    a = p["attn"]
    q = _mm("nd,de->ne", z, a["wq"]["w"], rnd).reshape(n, h, dh)
    k = _mm("nd,de->ne", z, a["wk"]["w"], rnd).reshape(n, kv, dh)
    v = _mm("nd,de->ne", z, a["wv"]["w"], rnd).reshape(n, kv, dh)
    q, k, v = (t.transpose(1, 0, 2) for t in (q, k, v))
    if m["pos_embed"] == "rope":
        q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    k, v = jnp.repeat(k, h // kv, 0), jnp.repeat(v, h // kv, 0)
    tau = a["tau"].astype(jnp.float32)
    head = jax.checkpoint(
        lambda args: _taylor_head(*args, m["causal"], rnd))
    y = jax.lax.map(head, (q, k, v, tau))
    y = y.transpose(1, 0, 2).reshape(n, h * dh)
    x = x + _mm("ne,ed->nd", y, a["wo"]["w"], rnd)
    z = _ln(p["norm2"], x)
    mp = p["mlp"]
    up = _mm("nd,df->nf", z, mp["up"]["w"], rnd)
    if "gate" in mp:
        u = up * jax.nn.silu(_mm("nd,df->nf", z, mp["gate"]["w"], rnd))
    else:
        u = jax.nn.gelu(up) if m["act"] == "gelu" else jax.nn.silu(up)
    return x + _mm("nf,fd->nd", u, mp["down"]["w"], rnd)


def layer(params, i: int):
    """The parameters of block ``i`` (blocks are stacked on axis 0)."""
    return jax.tree.map(lambda a: a[i], params["groups"][0])


def embed(params, m, tokens):
    x = params["embed"]["emb"][tokens].astype(jnp.float32)
    x = x * math.sqrt(m["d_model"])
    if m["pos_embed"] == "learned":
        x = x + params["pos"]["pos"][: tokens.shape[0]].astype(jnp.float32)
    return x


def readout(params, m, h, rnd):
    """Logits of final hidden rows h: (R, d_model)."""
    h = _ln(params["final_norm"], h)
    if m["tie_embeddings"]:
        return _mm("rd,vd->rv", h, params["embed"]["emb"], rnd)
    return _mm("rd,dv->rv", h, params["unembed"]["w"], rnd)


def forward(params, m, tokens, rnd):
    """Final hidden (pre-norm) of one sequence: (N, d_model)."""
    x = embed(params, m, tokens)
    for i in range(m["n_layers"]):
        x = jax.checkpoint(_block, static_argnums=(2, 3))(
            layer(params, i), x, _Static(m), rnd)
    return x


class _Static(dict):
    """A hashable view of the model dict, so it can be a static arg."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


# ---------------------------------------------------------------------------
# Serving: logits at chosen positions of one sequence, layer by layer
# ---------------------------------------------------------------------------

_SERVE_JITS: dict = {}


def _serve_fns(m, rounding):
    key = (id(m), rounding)
    if key not in _SERVE_JITS:
        rnd = ROUNDINGS[rounding]
        sm = _Static(m)
        blk = jax.jit(lambda p, x: _block(p, x, sm, rnd))
        out = jax.jit(lambda p, h, idx: readout(p, sm, h[idx], rnd))
        emb = jax.jit(lambda p, t: embed(p, sm, t))
        _SERVE_JITS[key] = (emb, blk, out)
    return _SERVE_JITS[key]


def logits_at(params, m, tokens, positions, *, rounding="fp32", pad_to=1024):
    """Logits (len(positions), vocab) of a causal model over ``tokens``
    (one sequence), at ``positions``. The sequence is padded at its end
    to a multiple of ``pad_to``, which a causal model never reads back,
    so that few shapes compile."""
    if not m["causal"]:
        raise ValueError("logits_at pads the sequence: causal models only")
    n = len(tokens)
    padded = -(-n // pad_to) * pad_to
    toks = jnp.zeros((padded,), jnp.int32).at[:n].set(
        jnp.asarray(tokens, jnp.int32))
    emb, blk, out = _serve_fns(m, rounding)
    with jax.default_matmul_precision("highest"):
        x = emb(params, toks)
        for i in range(m["n_layers"]):
            x = blk(layer(params, i), x)
        return out(params, x, jnp.asarray(positions, jnp.int32))


# ---------------------------------------------------------------------------
# Training: the loss, its gradient and AdamW, step by step
# ---------------------------------------------------------------------------

def sample_loss(params, m, tokens, labels, rnd):
    """Summed next-token cross-entropy of one sequence."""
    h = forward(params, m, tokens, rnd)
    lg = readout(params, m, h, rnd)
    lse = jax.nn.logsumexp(lg, -1)
    gold = jnp.take_along_axis(lg, labels[:, None], -1)[:, 0]
    return jnp.sum(lse - gold)


_TRAIN_JITS: dict = {}


def loss_and_grad(params, m, tokens, labels, *, rounding="fp32"):
    """Mean loss over the batch and its gradient, one sequence at a time
    (sums accumulated in float32)."""
    key = (id(m), rounding)
    if key not in _TRAIN_JITS:
        rnd = ROUNDINGS[rounding]
        sm = _Static(m)
        _TRAIN_JITS[key] = jax.jit(jax.value_and_grad(
            lambda p, t, y: sample_loss(p, sm, t, y, rnd)))
    fn = _TRAIN_JITS[key]
    total, grads = 0.0, None
    with jax.default_matmul_precision("highest"):
        for b in range(tokens.shape[0]):
            loss, g = fn(params, jnp.asarray(tokens[b]),
                         jnp.asarray(labels[b]))
            total = total + loss
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    count = tokens.shape[0] * tokens.shape[1]
    return total / count, jax.tree.map(lambda g: g / count, grads)


def adamw(params, grads, state, opt, step):
    """One AdamW step as configured: global-norm clipping, bias-corrected
    moments, linear warm-up then cosine decay, decoupled decay on every
    leaf of rank 2 or more as stored. ``step`` counts from 1."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    grads = jax.tree.map(
        lambda g: g * jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-9)),
        grads)
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    prog = min(max((step - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0),
               1.0)
    lr = opt["lr"] * warm * 0.5 * (1.0 + math.cos(math.pi * prog))
    b1, b2 = opt["b1"], opt["b2"]
    mu = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, state["mu"],
                      grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["nu"],
                      grads)

    def upd(p, m_, v):
        u = (m_ / (1 - b1 ** step)) / (jnp.sqrt(v / (1 - b2 ** step))
                                       + opt["eps"])
        if p.ndim >= 2:
            u = u + opt["weight_decay"] * p
        return p - lr * u

    return (jax.tree.map(upd, params, mu, nu), {"mu": mu, "nu": nu}, grads)


def train_steps(params, m, opt, batches, *, stored, rounding="fp32"):
    """Follow the program's first steps: returns the loss of each step,
    the clipped gradient of the first and the float32 master parameters
    after the last. ``batches`` are (tokens, labels) host arrays.

    The configuration keeps a float32 master copy and computes with the
    weights stored in ``stored`` (a tree of dtypes, bfloat16 for the
    matrices): each step's forward and gradient use the master rounded
    to that dtype, and the update goes to the master."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    state = {"mu": jax.tree.map(jnp.zeros_like, p),
             "nu": jax.tree.map(jnp.zeros_like, p)}
    losses, first_grad = [], None
    for step, (tokens, labels) in enumerate(batches, start=1):
        work = jax.tree.map(
            lambda a, dt: a.astype(dt).astype(jnp.float32), p, stored)
        loss, g = loss_and_grad(work, m, tokens, labels, rounding=rounding)
        p, state, clipped = adamw(p, g, state, opt, step)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = clipped
    return losses, first_grad, p
