"""Flash-style direct-TaylorShift Pallas TPU kernel.

Tiled O(N²d) attention with the Taylor-softmax numerator
``p(x) = x²/2 + α²·x + α⁴`` (inputs pre-scaled by α = d^¼, Alg. 1).

Key TPU adaptation vs FlashAttention: Taylor-softmax needs **no running
max and no rescaling** — the polynomial is positive and bounded after
the paper's normalization — so the kernel keeps only (nominator,
denominator) accumulators in VMEM and makes a single pass over K/V
tiles. One fewer VMEM tensor and no per-tile exp/rescale traffic than
online-softmax.

Inputs are (BH, N, d) with q, k already ℓ2-normalized and α-scaled
(ops.py does Alg. 1 lines 4–6). All accumulation in fp32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_nom, acc_den, *,
            alpha: float, causal: bool, block_q: int, block_k: int,
            n_seq: int, out_scale: bool, d: int, m_valid: int,
            raw: bool = False):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        acc_nom[...] = jnp.zeros_like(acc_nom)
        acc_den[...] = jnp.zeros_like(acc_den)

    q = q_ref[0].astype(jnp.float32)                    # (bq, d)
    k = k_ref[0].astype(jnp.float32)                    # (bk, d)
    v = v_ref[0].astype(jnp.float32)                    # (bk, d)

    x = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    a = 0.5 * x * x + (alpha ** 2) * x + alpha ** 4     # Taylor numerator
    if causal or m_valid < n_seq:
        kj = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                     (block_q, block_k), 1)
        if causal:
            qi = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            a = jnp.where(qi >= kj, a, 0.0)
        if m_valid < n_seq:     # keys beyond m_valid are padding
            a = jnp.where(kj < m_valid, a, 0.0)

    acc_nom[...] += jax.lax.dot_general(a, v, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)
    acc_den[...] += jnp.sum(a, axis=1)

    @pl.when(ik == nk - 1)
    def _finish():
        if raw:
            # VJP path: emit (denominator, nominator) unscaled — the
            # wrapper divides in jnp and keeps den as a residual.
            o_ref[0] = jnp.concatenate(
                [acc_den[...][:, None], acc_nom[...]], axis=1
            ).astype(o_ref.dtype)
            return
        y = acc_nom[...] / acc_den[...][:, None]
        if out_scale:
            if causal:
                qi = (iq * block_q
                      + jax.lax.broadcasted_iota(jnp.int32, (block_q,), 0))
                counts = (qi + 1).astype(jnp.float32)
            else:
                counts = jnp.full((block_q,), float(m_valid), jnp.float32)
            y = y * jnp.sqrt(counts / d)[:, None]
        o_ref[0] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "out_scale", "interpret",
                                             "m_valid", "raw"))
def taylor_direct_attention(q, k, v, *, causal: bool = False,
                            block_q: int | None = None,
                            block_k: int | None = None,
                            out_scale: bool = True, interpret: bool = False,
                            m_valid: int | None = None, raw: bool = False):
    """q, k, v: (BH, N, d) — q, k pre-normalized and α-scaled.

    ``block_q``/``block_k``: grid block shapes; ``None`` (the default)
    resolves through the installed tuning table's calibrated sweep
    (repro.tune, falling back to 128). Resolution happens at trace
    time — install the table before the first dispatch.

    ``m_valid``: number of real keys when k/v are zero-padded up to a
    block multiple (ops.py pad-and-mask path); keys ≥ m_valid are masked
    out of both nominator and denominator.

    ``raw``: emit (BH, N, d+1) fp32 ``concat(den, nom)`` without the
    division or output scaling — the custom-VJP forward uses this to keep
    the row denominators as residuals for the backward kernels.
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
    grid = (bh, n // block_q, m // block_k)

    kernel = functools.partial(
        _kernel, alpha=alpha, causal=causal, block_q=block_q,
        block_k=block_k, n_seq=m, out_scale=out_scale, d=d, m_valid=m_valid,
        raw=raw)

    d_out = d + 1 if raw else d
    out_dtype = jnp.float32 if raw else v.dtype
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d_out), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, n, d_out), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="taylor_dir_fwd",
    )(q, k, v)
