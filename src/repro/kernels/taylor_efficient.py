"""Fused efficient-TaylorShift Pallas TPU kernels.

This is the IO-aware implementation the paper's Appendix D.2 calls for:
the N×d² expanded tensors K^⊠2 / Q^⊠2 are **never materialized in HBM**.

Phase A (``amod``):  A_mod = Σ_blocks (K_blk^⊠2)ᵀ V̂_blk
  grid (BH, d²-chunks, N-blocks); each step forms the (block_k, cf·d)
  slice of K^⊠2 in VMEM registers and accumulates a (cf·d, d+1) tile of
  A_mod in VMEM scratch. HBM traffic: read K,V̂ once per d²-chunk,
  write A_mod once — O(N·d·ceil(d/cf) + d²·(d+1)) instead of O(N·d²).

Phase B (``readout``): Ŷ = ½ Q^⊠2 A_mod + α² Q (KᵀV̂) + α⁴ ΣV̂
  grid (BH, N-blocks, d²-chunks); accumulates (block_q, d+1) in scratch,
  adds the linear/constant Taylor terms on the last chunk, divides
  nominator by denominator and writes Y.

MXU alignment: the contraction dims are cf·d and d+1 — cf is chosen so
cf·d is a multiple of 128 where possible; d+1 costs one lane of padding
(the paper's trick of gluing the denominator onto V as column 0).

Inputs are (BH, N, d) with q, k pre-normalized and α-scaled, and
v̂ = concat(1, v) built by ops.py. fp32 accumulation throughout.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pick_chunk_factor(d: int, vmem_budget: int = 8 * 1024 * 1024) -> int:
    """How many d-row groups of A_mod to hold per VMEM tile."""
    best = 1
    for cf in range(1, d + 1):
        if d % cf:
            continue
        tile_bytes = cf * d * (d + 1) * 4
        if tile_bytes <= vmem_budget:
            best = cf
    return best


# ---------------------------------------------------------------------------
# Phase A: accumulate A_mod
# ---------------------------------------------------------------------------

def _amod_kernel(k_ref, kc_ref, vh_ref, a_ref, acc, *, cf: int, d: int):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    k = k_ref[0].astype(jnp.float32)                     # (bk, d)
    vh = vh_ref[0].astype(jnp.float32)                   # (bk, d+1)
    kc = kc_ref[0].astype(jnp.float32)                   # (bk, cf) chunk cols
    # K^⊠2 chunk: rows π(a, b) with a in this cf-slice: k[:, a] * k[:, b]
    k2 = (kc[:, :, None] * k[:, None, :]).reshape(k.shape[0], cf * d)
    acc[...] += jax.lax.dot_general(k2, vh, (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finish():
        a_ref[0] = acc[...]


def _amod_call(k, vh, *, cf: int, block_k: int, interpret: bool, name: str):
    bh, n, d = k.shape
    nchunks = d // cf
    grid = (bh, nchunks, n // block_k)
    kernel = functools.partial(_amod_kernel, cf=cf, d=d)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, c, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, cf), lambda b, c, j: (b, j, c)),
            pl.BlockSpec((1, block_k, d + 1), lambda b, c, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, cf * d, d + 1), lambda b, c, j: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, d * d, d + 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((cf * d, d + 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(k, k, vh)


# ---------------------------------------------------------------------------
# Phase B: readout
# ---------------------------------------------------------------------------

def _readout_kernel(q_ref, qc_ref, a_ref, kv_ref, s0_ref, o_ref, acc, *,
                    cf: int, d: int, coef2: float, coef1: float,
                    coef0: float, n_keys: int, out_scale: bool,
                    divide: bool):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    q = q_ref[0].astype(jnp.float32)                     # (bq, d)
    qc = qc_ref[0].astype(jnp.float32)                   # (bq, cf)
    a = a_ref[0]                                         # (cf·d, d+1) fp32
    q2 = (qc[:, :, None] * q[:, None, :]).reshape(q.shape[0], cf * d)
    acc[...] += coef2 * jax.lax.dot_general(
        q2, a, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ic == nc - 1)
    def _finish():
        kv = kv_ref[0]                                   # (d, d+1) fp32
        s0 = s0_ref[0]                                   # (1, d+1) fp32
        y = acc[...]
        y += coef1 * jax.lax.dot_general(
            q, kv, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        y += coef0 * s0
        if not divide:
            # raw ŷ = (den, nom): shared by the custom-VJP forward (den is
            # a backward residual) and by the dV̂ backward contraction
            # (coefs 1,1,1), which is this same bilinear readout.
            o_ref[0] = y.astype(o_ref.dtype)
            return
        out = y[:, 1:] / y[:, :1]
        if out_scale:
            out = out * (float(n_keys) / d) ** 0.5
        o_ref[0] = out.astype(o_ref.dtype)


def _readout_call(q, a_mod, kv, s0, *, cf: int, block_q: int, n_keys: int,
                  out_scale: bool, out_dtype, interpret: bool, name: str,
                  coefs: tuple | None = None, divide: bool = True):
    bh, n, d = q.shape
    alpha = float(d) ** 0.25
    coef2, coef1, coef0 = (0.5, alpha ** 2, alpha ** 4) if coefs is None \
        else coefs
    nchunks = d // cf
    grid = (bh, n // block_q, nchunks)
    kernel = functools.partial(_readout_kernel, cf=cf, d=d, coef2=coef2,
                               coef1=coef1, coef0=coef0, n_keys=n_keys,
                               out_scale=out_scale, divide=divide)
    d_out = d if divide else d + 1
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, c: (b, i, 0)),
            pl.BlockSpec((1, block_q, cf), lambda b, i, c: (b, i, c)),
            pl.BlockSpec((1, cf * d, d + 1), lambda b, i, c: (b, c, 0)),
            pl.BlockSpec((1, d, d + 1), lambda b, i, c: (b, 0, 0)),
            pl.BlockSpec((1, 1, d + 1), lambda b, i, c: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d_out), lambda b, i, c: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, n, d_out), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d + 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(q, q, a_mod, kv, s0)


# ---------------------------------------------------------------------------
# Public entry
# ---------------------------------------------------------------------------

def build_vhat(v, m_valid: int) -> jnp.ndarray:
    """V̂ = concat(1, v) fp32 with padded keys (≥ m_valid) zeroed — the
    ones column included, which is what removes a padded key from both
    nominator and denominator. Single home for the padding convention:
    the forward here and the backward (taylor_grad.py) must agree."""
    bh, m, _ = v.shape
    ones = jnp.ones((bh, m, 1), jnp.float32)
    vh = jnp.concatenate([ones, v.astype(jnp.float32)], axis=-1)
    if m_valid < m:
        vh = vh * (jnp.arange(m) < m_valid)[None, :, None]
    return vh

@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "out_scale", "interpret",
                                             "m_valid"))
def taylor_efficient_attention(q, k, v, *, block_q: int | None = None,
                               block_k: int | None = None,
                               out_scale: bool = True,
                               interpret: bool = False,
                               m_valid: int | None = None):
    """Non-causal efficient-TaylorShift, fused. q,k: α-scaled normalized
    (BH, N, d); v: (BH, M, d) raw values.

    ``block_q``/``block_k``: ``None`` (the default) resolves through
    the installed tuning table's calibrated block sweep (repro.tune,
    falling back to 128); resolution happens at trace time.

    ``m_valid``: number of real keys when inputs are zero-padded up to a
    block multiple (ops.py pad-and-mask path). A padded key only enters
    the computation through V̂ (the state sums are linear in V̂), so
    zeroing its V̂ row — including the denominator ones-column — removes
    it from nominator and denominator alike.
    """
    bh, n, d = q.shape
    m = k.shape[1]
    m_valid = m if m_valid is None else m_valid
    if block_q is None or block_k is None:
        from repro.tune.table import kernel_blocks
        tq, tk = kernel_blocks(d)
        block_q = tq if block_q is None else block_q
        block_k = tk if block_k is None else block_k
    block_q = min(block_q, n)
    block_k = min(block_k, m)
    assert n % block_q == 0 and m % block_k == 0
    alpha = float(d) ** 0.25
    cf = _pick_chunk_factor(d)

    vh = build_vhat(v, m_valid)

    a_mod = _amod_call(k, vh, cf=cf, block_k=block_k, interpret=interpret,
                       name="taylor_eff_fwd_amod")
    # small summaries — plain XLA ops (negligible traffic)
    kv = jnp.einsum("bnd,bnf->bdf", k.astype(jnp.float32), vh)
    s0 = jnp.sum(vh, axis=1, keepdims=True)
    return _readout_call(q, a_mod, kv, s0, cf=cf, block_q=block_q,
                         n_keys=m_valid, out_scale=out_scale,
                         out_dtype=v.dtype, interpret=interpret,
                         name="taylor_eff_fwd_readout")
