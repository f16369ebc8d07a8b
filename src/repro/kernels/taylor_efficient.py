"""Fused efficient-TaylorShift Pallas TPU kernels.

This is the IO-aware implementation the paper's Appendix D.2 calls for:
the N×d² expanded tensors K^⊠2 / Q^⊠2 are **never materialized in HBM**.

Both phases put tokens in lanes: they take xᵀ (d, N) and ûᵀ (d+1, N),
transposed in XLA outside the kernel (one pass over an (N, d) array
each, which XLA shares between the calls that read the same operand),
and build the ⊠ operand as Pᵀ[a·d + b, n] = xᵀ[a, n]·xᵀ[b, n], rows
broadcast against rows and merged over leading dims, so no lane moves.

Phase A (``amod``):  A_modᵀ = Σ_blocks V̂_blkᵀ K_blk^⊠2, stored
  (BH, d+1, d²): row f, column π(a, b). Grid (BH, d²-chunks, N-blocks);
  each step forms the (cf·d, block_k) slice of (K^⊠2)ᵀ in VMEM and
  accumulates a (d+1, cf·d) tile in VMEM scratch. HBM traffic: read K,
  V̂ once per d²-chunk, write A_mod once — O(N·d·ceil(d/cf) + d²·(d+1))
  instead of O(N·d²).

Phase B (``readout``): Ŷ = ½ Q^⊠2 A_mod + α² Q (KᵀV̂) + α⁴ ΣV̂
  grid (BH, N-blocks, d²-chunks); accumulates Ŷᵀ (d+1, block_q) in
  scratch, adds the linear/constant Taylor terms on the last chunk,
  divides every row by the denominator row 0 and writes Ŷᵀ; XLA turns
  it back to (BH, N, d), dropping row 0.

MXU alignment: A_mod contracts Ûᵀ (d+1, block_k) with Pᵀ (cf·d, block_k)
over the lanes of both (the q·kᵀ form), the read-out A_modᵀ (d+1, cf·d)
with Pᵀ (cf·d, block_q): in both the matmul's N is wide (cf·d or the
token block) and d+1 is its M, sublanes, where the extra row costs
one sublane, not a lane tile. cf is chosen so cf·d is a multiple of 128
where possible (the glued denominator is the paper's trick of putting
it onto V as column 0). A lane block is a multiple of 128 or all of N;
``_lane_block`` widens the caller's block where N and VMEM allow.

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


def _lane_block(n: int, block: int, d: int, cf: int,
                vmem_budget: int = 12 * 1024 * 1024) -> int:
    """Token block of the phase kernels: ``block`` doubled, up to 512,
    while twice it divides n and the fp32 ⊠ operand (cf·d, block) beside
    an A_mod tile (d+1, cf·d) fits ``vmem_budget``. Every grid step has a
    fixed cost (A_mod's whole tile read and written back, the step
    itself) that a wider block spreads over more tokens. A block that is
    not a multiple of 128 (all of a short N, or interpret mode's small
    blocks) stays."""
    if block % 128:
        return block
    rows = cf * d
    while (2 * block <= 512 and n % (2 * block) == 0
           and rows * (2 * block + d + 1) * 4 <= vmem_budget):
        block *= 2
    return block


# ---------------------------------------------------------------------------
# Phase A: accumulate A_mod
# ---------------------------------------------------------------------------

def _boxtimes_t(xct, xt, cf: int, d: int):
    """Pᵀ[a·d + b, n] = xᵀ[a, n]·xᵀ[b, n] for the cf rows a of ``xct``:
    rows broadcast against rows, merged over leading dims only, so no
    lane is moved."""
    return (xct[:, None, :] * xt[None, :, :]).reshape(cf * d, xt.shape[1])


def _amod_kernel(xt_ref, xct_ref, ut_ref, a_ref, acc, *, cf: int, d: int):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    pt = _boxtimes_t(xct_ref[0], xt_ref[0], cf, d)       # (cf·d, bk)
    # A_modᵀ chunk += Ûᵀ P: both operands contract over their lanes
    acc[...] += jax.lax.dot_general(ut_ref[0], pt, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finish():
        a_ref[0] = acc[...]


def _amod_call(x, u, *, cf: int, block_k: int, interpret: bool, name: str):
    """(BH, N, d), (BH, N, d+1) -> A_modᵀ (BH, d+1, d²): row f, column
    π(a, b) sums x_a·x_b·u_f over the tokens."""
    bh, n, d = x.shape
    block_k = _lane_block(n, block_k, d, cf)
    xt = jnp.swapaxes(x, 1, 2).astype(jnp.float32)       # (BH, d, N)
    ut = jnp.swapaxes(u, 1, 2).astype(jnp.float32)       # (BH, d+1, N)
    kernel = functools.partial(_amod_kernel, cf=cf, d=d)
    return pl.pallas_call(
        kernel,
        grid=(bh, d // cf, n // block_k),
        in_specs=[
            pl.BlockSpec((1, d, block_k), lambda b, c, j: (b, 0, j)),
            pl.BlockSpec((1, cf, block_k), lambda b, c, j: (b, c, j)),
            pl.BlockSpec((1, d + 1, block_k), lambda b, c, j: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, d + 1, cf * d), lambda b, c, j: (b, 0, c)),
        out_shape=jax.ShapeDtypeStruct((bh, d + 1, d * d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((d + 1, cf * d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(xt, xt, ut)


# ---------------------------------------------------------------------------
# Phase B: readout
# ---------------------------------------------------------------------------

def _readout_kernel(xt_ref, xct_ref, a_ref, kv_ref, s0_ref, o_ref, acc, *,
                    cf: int, d: int, coef2: float, coef1: float,
                    coef0: float, n_keys: int, out_scale: bool,
                    divide: bool):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    xt = xt_ref[0]                                       # (d, bq) fp32
    pt = _boxtimes_t(xct_ref[0], xt, cf, d)              # (cf·d, bq)
    acc[...] += coef2 * jax.lax.dot_general(
        a_ref[0], pt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ic == nc - 1)
    def _finish():
        kv = kv_ref[0]                                   # (d+1, d) fp32
        s0 = s0_ref[0]                                   # (d+1, 1) fp32
        y = acc[...]                                     # ŷᵀ (d+1, bq)
        y += coef1 * jax.lax.dot_general(
            kv, xt, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        y += coef0 * s0
        if divide:
            # every row over the denominator row 0; the caller drops row 0
            y = y / y[:1]
            if out_scale:
                y = y * (float(n_keys) / d) ** 0.5
        # raw ŷ = (den, nom) when not dividing: shared by the custom-VJP
        # forward (den is a backward residual) and by the dV̂ backward
        # contraction (coefs 1,1,1), which is this same bilinear readout.
        o_ref[0] = y.astype(o_ref.dtype)


def _readout_call(x, a_mod, kv, s0, *, cf: int, block_q: int, n_keys: int,
                  out_scale: bool, out_dtype, interpret: bool, name: str,
                  coefs: tuple | None = None, divide: bool = True):
    """(BH, N, d) queries against A_modᵀ (BH, d+1, d²), KV̂ (BH, d, d+1)
    and ΣV̂ (BH, 1, d+1) -> (BH, N, d), or the raw (BH, N, d+1) ŷ."""
    bh, n, d = x.shape
    alpha = float(d) ** 0.25
    coef2, coef1, coef0 = (0.5, alpha ** 2, alpha ** 4) if coefs is None \
        else coefs
    kernel = functools.partial(_readout_kernel, cf=cf, d=d, coef2=coef2,
                               coef1=coef1, coef0=coef0, n_keys=n_keys,
                               out_scale=out_scale, divide=divide)
    block_q = _lane_block(n, block_q, d, cf)
    xt = jnp.swapaxes(x, 1, 2).astype(jnp.float32)       # (BH, d, N)
    yt = pl.pallas_call(
        kernel,
        grid=(bh, n // block_q, d // cf),
        in_specs=[
            pl.BlockSpec((1, d, block_q), lambda b, i, c: (b, 0, i)),
            pl.BlockSpec((1, cf, block_q), lambda b, i, c: (b, c, i)),
            pl.BlockSpec((1, d + 1, cf * d), lambda b, i, c: (b, 0, c)),
            pl.BlockSpec((1, d + 1, d), lambda b, i, c: (b, 0, 0)),
            pl.BlockSpec((1, d + 1, 1), lambda b, i, c: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, d + 1, block_q), lambda b, i, c: (b, 0, i)),
        out_shape=jax.ShapeDtypeStruct((bh, d + 1, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((d + 1, block_q), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name=name,
    )(xt, xt, a_mod, jnp.swapaxes(kv, 1, 2), jnp.swapaxes(s0, 1, 2))
    y = jnp.swapaxes(yt, 1, 2)
    return y[..., 1:] if divide else y


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
