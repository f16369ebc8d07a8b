"""Hand-written Pallas backward kernels + custom VJPs for TaylorShift.

Makes the fused attention kernels differentiable without ever
materializing what the forward avoided materializing:

* **direct** — flash-style recomputation backward. Residuals are only
  the row denominators (and the unscaled output); the N×M score matrix
  is rebuilt tile-by-tile in VMEM in both backward kernels. With
  ``u = a/den`` (the normalized Taylor scores) and ``y0`` the unscaled
  output, the cotangent chain is::

      da_ij = (g0_i·v_j - g0_i·y0_i) / den_i        (quotient rule)
      dx_ij = da_ij · (x_ij + α²)                   (p'(x) = x + α²)
      dq_i  = Σ_j dx_ij k_j     dk_j = Σ_i dx_ij q_i     dv_j = Σ_i u_ij g0_i

* **efficient** — the ⊠ tensor-product trick applies to the backward
  too. With ĝ_i = (-(g0_i·y0_i)/den_i, g0_i/den_i) ∈ R^{d+1} the
  cotangent of ŷ_i, every gradient is a rank-structured contraction:

      dA_mod = ½ (Q^⊠2)ᵀ Ĝ                          (an amod pass over Q)
      dq_i   = M_i q_i + α² KV̂ ĝ_i,      M_i = mat(A_mod ĝ_i)
      dk_j   = 2 W_j k_j + dKV̂ v̂_j,      W_j = mat(dA_mod v̂_j)
      dv̂_j  = K^⊠2_j dA_mod + k_j dKV̂ + dS0      (a raw readout pass)

  The chain rule gives ½ (M_i + M_iᵀ) q_i and (W_j + W_jᵀ) k_j; row
  π(a, b) of A_mod sums k_a·k_b v̂ over keys (of dA_mod, q_a·q_b ĝ over
  queries), so both are symmetric in (a, b), M_i = M_iᵀ and W_j = W_jᵀ.

  A_mod / KV̂ / ΣV̂ are *recomputed* from k, v̂ in the backward (they are
  cheaper to rebuild than to hold as residuals), and the N×d² expanded
  tensors are never formed in HBM: the symmetric-quadratic kernel below
  builds the ⊠ product of ĝ_i (v̂_j) and q_i (k_j) in VMEM, a block of
  queries (keys) at a time, and meets A in one MXU contraction per
  block. Peak backward memory stays O(N·d + d³).

The causal chunked backward lives in ``core/taylor.py`` (pure-jnp
two-scan recompute custom VJP) since the causal path is not a Pallas
kernel to begin with.

All entries take (BH, N, d) inputs with q, k pre-normalized and
α-scaled, mirroring the forward kernels; ops.py applies Algorithm 1's
input normalization outside (autodiff handles it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.taylor_direct import taylor_direct_attention
from repro.kernels.taylor_efficient import (_amod_call, _boxtimes_t,
                                            _pick_chunk_factor,
                                            _readout_call, build_vhat)


# ---------------------------------------------------------------------------
# Direct backward — flash-style recompute kernels
# ---------------------------------------------------------------------------

def _dq_bwd_kernel(q_ref, gaux_ref, k_ref, v_ref, dq_ref, acc, *,
                   alpha: float, causal: bool, block_q: int, block_k: int,
                   n_seq: int, m_valid: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    q = q_ref[0].astype(jnp.float32)                     # (bq, d)
    k = k_ref[0].astype(jnp.float32)                     # (bk, d)
    v = v_ref[0].astype(jnp.float32)                     # (bk, d)
    gaux = gaux_ref[0]                                   # (bq, d+2) fp32
    den, delta, g0 = gaux[:, 0:1], gaux[:, 1:2], gaux[:, 2:]

    x = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    gv = jax.lax.dot_general(g0, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    da = (gv - delta) / den
    if causal or m_valid < n_seq:
        kj = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                     (block_q, block_k), 1)
        if causal:
            qi = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            da = jnp.where(qi >= kj, da, 0.0)
        if m_valid < n_seq:
            da = jnp.where(kj < m_valid, da, 0.0)
    dx = da * (x + alpha ** 2)
    acc[...] += jax.lax.dot_general(dx, k, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finish():
        dq_ref[0] = acc[...].astype(dq_ref.dtype)


def _dkv_bwd_kernel(k_ref, v_ref, q_ref, gaux_ref, dk_ref, dv_ref,
                    acc_dk, acc_dv, *, alpha: float, causal: bool,
                    block_q: int, block_k: int, n_seq: int, m_valid: int):
    jk = pl.program_id(1)
    iq = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(iq == 0)
    def _init():
        acc_dk[...] = jnp.zeros_like(acc_dk)
        acc_dv[...] = jnp.zeros_like(acc_dv)

    k = k_ref[0].astype(jnp.float32)                     # (bk, d)
    v = v_ref[0].astype(jnp.float32)                     # (bk, d)
    q = q_ref[0].astype(jnp.float32)                     # (bq, d)
    gaux = gaux_ref[0]                                   # (bq, d+2) fp32
    den, delta, g0 = gaux[:, 0:1], gaux[:, 1:2], gaux[:, 2:]

    x = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    a = 0.5 * x * x + (alpha ** 2) * x + alpha ** 4
    gv = jax.lax.dot_general(g0, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    da = (gv - delta) / den
    if causal or m_valid < n_seq:
        kj = jk * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                     (block_q, block_k), 1)
        keep = jnp.ones_like(x, dtype=bool)
        if causal:
            qi = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            keep &= qi >= kj
        if m_valid < n_seq:
            keep &= kj < m_valid
        a = jnp.where(keep, a, 0.0)
        da = jnp.where(keep, da, 0.0)
    u = a / den
    acc_dv[...] += jax.lax.dot_general(u, g0, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)
    dx = da * (x + alpha ** 2)
    acc_dk[...] += jax.lax.dot_general(dx, q, (((0,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)

    @pl.when(iq == nq - 1)
    def _finish():
        dk_ref[0] = acc_dk[...].astype(dk_ref.dtype)
        dv_ref[0] = acc_dv[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret", "m_valid"))
def _direct_bwd_call(q, k, v, gaux, *, causal: bool, block_q: int,
                     block_k: int, interpret: bool, m_valid: int):
    bh, n, d = q.shape
    m = k.shape[1]
    alpha = float(d) ** 0.25
    common = dict(alpha=alpha, causal=causal, block_q=block_q,
                  block_k=block_k, n_seq=m, m_valid=m_valid)

    dq = pl.pallas_call(
        functools.partial(_dq_bwd_kernel, **common),
        grid=(bh, n // block_q, m // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, d + 2), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, n, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="taylor_dir_bwd_dq",
    )(q, gaux, k, v)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_bwd_kernel, **common),
        grid=(bh, m // block_k, n // block_q),
        in_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, d + 2), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, m, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, m, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="taylor_dir_bwd_dkv",
    )(k, v, q, gaux)
    return dq, dk, dv


def _direct_row_scale(n: int, d: int, causal: bool, m_valid: int):
    """sqrt(counts/d) per query row, matching the forward kernel."""
    if causal:
        counts = jnp.arange(1, n + 1, dtype=jnp.float32)
    else:
        counts = jnp.full((n,), float(m_valid), jnp.float32)
    return jnp.sqrt(counts / d)[None, :, None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _direct_vjp(cfg, q, k, v):
    causal, block_q, block_k, out_scale, interpret, m_valid = cfg
    return taylor_direct_attention(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        out_scale=out_scale, interpret=interpret, m_valid=m_valid)


def _direct_vjp_fwd(cfg, q, k, v):
    causal, block_q, block_k, out_scale, interpret, m_valid = cfg
    raw = taylor_direct_attention(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        out_scale=out_scale, interpret=interpret, m_valid=m_valid, raw=True)
    den, y0 = raw[..., :1], raw[..., 1:] / raw[..., :1]
    n, d = q.shape[1], q.shape[2]
    y = y0 * _direct_row_scale(n, d, causal, m_valid) if out_scale else y0
    return y.astype(v.dtype), (q, k, v, den, y0)


def _direct_vjp_bwd(cfg, res, g):
    causal, block_q, block_k, out_scale, interpret, m_valid = cfg
    q, k, v, den, y0 = res
    n, d = q.shape[1], q.shape[2]
    g0 = g.astype(jnp.float32)
    if out_scale:
        g0 = g0 * _direct_row_scale(n, d, causal, m_valid)
    delta = jnp.sum(g0 * y0, axis=-1, keepdims=True)
    gaux = jnp.concatenate([den, delta, g0], axis=-1)
    dq, dk, dv = _direct_bwd_call(q, k, v, gaux, causal=causal,
                                  block_q=block_q, block_k=block_k,
                                  interpret=interpret, m_valid=m_valid)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_direct_vjp.defvjp(_direct_vjp_fwd, _direct_vjp_bwd)


def taylor_direct_attention_vjp(q, k, v, *, causal: bool = False,
                                block_q: int = 128, block_k: int = 128,
                                out_scale: bool = True,
                                interpret: bool = False,
                                m_valid: int | None = None):
    """Differentiable fused direct-TaylorShift (custom VJP).

    Undifferentiated calls run the plain forward kernel; under jax.grad
    the flash-style backward kernels above produce dq/dk/dv without an
    N×M HBM residual.
    """
    m_valid = k.shape[1] if m_valid is None else m_valid
    cfg = (causal, min(block_q, q.shape[1]), min(block_k, k.shape[1]),
           out_scale, interpret, m_valid)
    return _direct_vjp(cfg, q, k, v)


# ---------------------------------------------------------------------------
# Efficient backward — symmetric-quadratic kernel
# ---------------------------------------------------------------------------

def _sym_quad_kernel(xt_ref, u0t_ref, u1t_ref, a0_ref, a1_ref, o_ref, acc, *,
                     cf: int, d: int):
    """(M_i + M_iᵀ) x_i = 2 M_i x_i with M_i = mat(A u_i), for a block of
    queries i at once, as one MXU contraction per chunk of cf value
    columns f of A.

    Precondition: A is symmetric in its row pair, A[π(a, b)] = A[π(b, a)]
    (A_mod and dA are: row π(a, b) sums k_a·k_b over keys, or q_a·q_b
    over queries), so M_i = M_iᵀ.

    Everything is transposed, queries in lanes. With u = (u0, u1),
    (M x)ᵀ = u0ᵀ ⊙ (A0 xᵀ) + Ã1 Pᵀ: A0[a, b] = A[π(a, b), 0] is the
    ones/denominator column, Pᵀ[f·d + b, i] = u1[i, f]·x[i, b] is the ⊠
    product of this chunk's columns of u1 with x, built by broadcasting
    rows (no lane relayout), and Ã1[a, f·d + b] = A[π(a, b), 1 + f]. The
    matmul's N is the query block, which fills the MXU's width.
    """
    ic = pl.program_id(2)
    nc = pl.num_programs(2)
    xt = xt_ref[0]                                       # (d, bq) fp32

    @pl.when(ic == 0)
    def _init():
        acc[...] = u0t_ref[0] * jax.lax.dot_general(
            a0_ref[0], xt, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    pt = _boxtimes_t(u1t_ref[0], xt, cf, d)              # (cf·d, bq)
    acc[...] += jax.lax.dot_general(a1_ref[0], pt, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)

    @pl.when(ic == nc - 1)
    def _finish():
        o_ref[0] = (2.0 * acc[...]).astype(o_ref.dtype)


def _sym_quad_call(x, u, a_mod, *, cf: int, block_q: int, interpret: bool,
                   name: str):
    """(BH, N, d), (BH, N, d+1), Aᵀ (BH, d+1, d²) -> (BH, N, d):
    (M_i + M_iᵀ) x_i for an A symmetric in its row pair (see the kernel).
    Aᵀ is laid out as ``_amod_call`` returns it.

    The operands are laid out for the kernel in XLA: x and u transposed
    (queries in lanes), and A's value column f moved into the
    contraction, next to b. Each is one pass over an (N, d) or (d², d)
    array, against the kernel's N·d³ multiply-adds.
    """
    bh, n, d = x.shape
    xt = jnp.swapaxes(x, 1, 2).astype(jnp.float32)       # (BH, d, N)
    ut = jnp.swapaxes(u, 1, 2).astype(jnp.float32)       # (BH, d+1, N)
    a0 = a_mod[:, 0].reshape(bh, d, d)                   # [a, b]
    a1 = jnp.swapaxes(a_mod[:, 1:].reshape(bh, d, d, d), 1, 2)  # [a, f, b]
    a1 = a1.reshape(bh, d, d * d)
    ot = pl.pallas_call(
        functools.partial(_sym_quad_kernel, cf=cf, d=d),
        grid=(bh, n // block_q, d // cf),
        in_specs=[
            pl.BlockSpec((1, d, block_q), lambda b, i, c: (b, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, c: (b, 0, i)),
            pl.BlockSpec((1, cf, block_q), lambda b, i, c: (b, c, i)),
            pl.BlockSpec((1, d, d), lambda b, i, c: (b, 0, 0)),
            pl.BlockSpec((1, d, cf * d), lambda b, i, c: (b, 0, c)),
        ],
        out_specs=pl.BlockSpec((1, d, block_q), lambda b, i, c: (b, 0, i)),
        out_shape=jax.ShapeDtypeStruct((bh, d, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((d, block_q), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(xt, ut[:, :1], ut[:, 1:], a0, a1)
    return jnp.swapaxes(ot, 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _efficient_vjp(cfg, q, k, v):
    from repro.kernels.taylor_efficient import taylor_efficient_attention
    block_q, block_k, out_scale, interpret, m_valid = cfg
    return taylor_efficient_attention(
        q, k, v, block_q=block_q, block_k=block_k, out_scale=out_scale,
        interpret=interpret, m_valid=m_valid)


def _efficient_vjp_fwd(cfg, q, k, v):
    block_q, block_k, out_scale, interpret, m_valid = cfg
    bh, n, d = q.shape
    cf = _pick_chunk_factor(d)
    vh = build_vhat(v, m_valid)
    a_mod = _amod_call(k, vh, cf=cf, block_k=block_k, interpret=interpret,
                       name="taylor_eff_fwd_amod")
    kv = jnp.einsum("bmd,bmf->bdf", k.astype(jnp.float32), vh)
    s0 = jnp.sum(vh, axis=1, keepdims=True)
    yhat = _readout_call(q, a_mod, kv, s0, cf=cf, block_q=block_q,
                         n_keys=m_valid, out_scale=False,
                         out_dtype=jnp.float32, interpret=interpret,
                         name="taylor_eff_fwd_readout", divide=False)
    den, y0 = yhat[..., :1], yhat[..., 1:] / yhat[..., :1]
    y = y0 * (float(m_valid) / d) ** 0.5 if out_scale else y0
    return y.astype(v.dtype), (q, k, v, den, y0)


def _efficient_vjp_bwd(cfg, res, g):
    block_q, block_k, out_scale, interpret, m_valid = cfg
    q, k, v, den, y0 = res
    bh, n, d = q.shape
    m = k.shape[1]
    alpha = float(d) ** 0.25
    cf = _pick_chunk_factor(d)

    g0 = g.astype(jnp.float32)
    if out_scale:
        g0 = g0 * (float(m_valid) / d) ** 0.5
    ghat = jnp.concatenate(
        [-jnp.sum(g0 * y0, axis=-1, keepdims=True) / den, g0 / den], axis=-1)

    # A_mod / KV̂ recomputed rather than saved. The call keeps the
    # forward's name: it is the forward's call, operand for operand, so
    # under remat XLA merges it with the rematerialised forward's A_mod
    # in the same backward pass; a name of its own would make that a
    # second kernel launch
    vh = build_vhat(v, m_valid)
    a_mod = _amod_call(k, vh, cf=cf, block_k=block_k, interpret=interpret,
                       name="taylor_eff_fwd_amod")
    kv = jnp.einsum("bmd,bmf->bdf", k.astype(jnp.float32), vh)

    dA = 0.5 * _amod_call(q, ghat, cf=cf, block_k=block_q,
                          interpret=interpret, name="taylor_eff_bwd_amod_da")
    dKV = (alpha ** 2) * jnp.einsum("bnd,bnf->bdf", q, ghat)
    dS0 = (alpha ** 4) * jnp.sum(ghat, axis=1, keepdims=True)

    dq = 0.5 * _sym_quad_call(q, ghat, a_mod, cf=cf, block_q=block_q,
                              interpret=interpret,
                              name="taylor_eff_bwd_symquad_dq")
    dq += (alpha ** 2) * jnp.einsum("bnf,bdf->bnd", ghat, kv)
    dk = _sym_quad_call(k, vh, dA, cf=cf, block_q=block_k,
                        interpret=interpret, name="taylor_eff_bwd_symquad_dk")
    dk += jnp.einsum("bmf,bdf->bmd", vh, dKV)
    dvh = _readout_call(k, dA, dKV, dS0, cf=cf, block_q=block_k, n_keys=m,
                        out_scale=False, out_dtype=jnp.float32,
                        interpret=interpret, name="taylor_eff_bwd_readout_dv",
                        coefs=(1.0, 1.0, 1.0), divide=False)
    if m_valid < m:
        dvh = dvh * (jnp.arange(m) < m_valid)[None, :, None]
    dv = dvh[..., 1:]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_efficient_vjp.defvjp(_efficient_vjp_fwd, _efficient_vjp_bwd)


def taylor_efficient_attention_vjp(q, k, v, *, block_q: int = 128,
                                   block_k: int = 128,
                                   out_scale: bool = True,
                                   interpret: bool = False,
                                   m_valid: int | None = None):
    """Differentiable fused efficient-TaylorShift (custom VJP).

    Backward peak memory is O(N·d + d³): no N×N matrix and no HBM-resident
    N×d² expansion, matching the forward's linear-memory claim end-to-end.
    """
    m_valid = k.shape[1] if m_valid is None else m_valid
    cfg = (min(block_q, q.shape[1]), min(block_k, k.shape[1]),
           out_scale, interpret, m_valid)
    return _efficient_vjp(cfg, q, k, v)


__all__ = ["taylor_direct_attention_vjp", "taylor_efficient_attention_vjp"]
