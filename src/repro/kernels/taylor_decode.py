"""Fused TaylorShift decode-step Pallas kernel (serving hot path).

One generated token per call: absorb (k, v) into the S2 state and read
out with q — the O(d²(d+1)) inner loop that replaces KV-cache attention
(docs/design.md §4.2). Fusing update+readout halves state HBM traffic vs the
two-pass jnp form: S2 is read once, updated in VMEM, written once, and
the readout contraction happens on the already-resident tile.

Grid: (BH, d²-chunks). Each step owns a (cf·d, d+1) tile of S2:
  S2_c   += K2_c^T · v̂           (rank-1 in the chunk rows)
  y_part  = Q2_c · S2_c           (partial readout, summed in the wrapper)

The small S1/S0 terms (d·(d+1) and (d+1)) stay in jnp — they are < 1 %
of the traffic. Validated against core.taylor.taylor_decode_step in
tests/test_kernels.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import taylor as T
from repro.kernels.taylor_efficient import _pick_chunk_factor


def _decode_kernel(q_ref, qc_ref, k_ref, kc_ref, vh_ref, s2_ref, s2_out,
                   yp_ref, *, cf: int, d: int):
    q = q_ref[0].astype(jnp.float32)          # (1, d)
    qc = qc_ref[0].astype(jnp.float32)        # (1, cf)
    k = k_ref[0].astype(jnp.float32)
    kc = kc_ref[0].astype(jnp.float32)
    vh = vh_ref[0].astype(jnp.float32)
    s2 = s2_ref[0]

    k2 = (kc[:, :, None] * k[:, None, :]).reshape(1, cf * d)
    s2 = s2 + jax.lax.dot_general(k2, vh, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    s2_out[0] = s2

    q2 = (qc[:, :, None] * q[:, None, :]).reshape(1, cf * d)
    yp_ref[0] = jax.lax.dot_general(q2, s2, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("normalize_inputs",
                                             "output_scale", "interpret"))
def taylor_decode_kernel(state: T.TaylorState, q, k, v, *, tau=1.0,
                         normalize_inputs: bool = True,
                         output_scale: bool = True,
                         interpret: bool = False):
    """Fused decode step. q,k,v: (BH, 1, d); state.s2: (BH, d², d+1).

    Returns (y (BH, 1, d), new TaylorState) — bit-compatible with
    core.taylor.taylor_decode_step.
    """
    bh, _, d = q.shape
    alpha = d ** 0.25
    if normalize_inputs:
        q, k = T.normalize_qk(q, k, tau)
    qs = (q * alpha).astype(jnp.float32)
    ks = (k * alpha).astype(jnp.float32)
    ones = jnp.ones((bh, 1, 1), jnp.float32)
    vh = jnp.concatenate([ones, v.astype(jnp.float32)], axis=-1)

    cf = _pick_chunk_factor(d)
    nchunks = d // cf
    grid = (bh, nchunks)
    kernel = functools.partial(_decode_kernel, cf=cf, d=d)
    s2_new, y_parts = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, d), lambda b, c: (b, 0, 0)),       # q
            pl.BlockSpec((1, 1, cf), lambda b, c: (b, 0, c)),      # q chunk
            pl.BlockSpec((1, 1, d), lambda b, c: (b, 0, 0)),       # k
            pl.BlockSpec((1, 1, cf), lambda b, c: (b, 0, c)),      # k chunk
            pl.BlockSpec((1, 1, d + 1), lambda b, c: (b, 0, 0)),   # vh
            pl.BlockSpec((1, cf * d, d + 1), lambda b, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, cf * d, d + 1), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, d + 1), lambda b, c: (b, c, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, d * d, d + 1), jnp.float32),
            jax.ShapeDtypeStruct((bh, nchunks, d + 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="taylor_decode",
    )(qs, qs, ks, ks, vh, state.s2)

    # small terms in jnp (<1 % of traffic)
    s1 = state.s1 + jnp.einsum("bcd,bcf->bdf", ks, vh)
    s0 = state.s0 + vh
    n = state.n + 1
    y_hat = 0.5 * jnp.sum(y_parts, axis=1, keepdims=True)
    y_hat += (alpha**2) * jnp.einsum("bcd,bdf->bcf", qs, s1)
    y_hat += (alpha**4) * s0
    y = y_hat[..., 1:] / y_hat[..., :1]
    if output_scale:
        y = y * jnp.sqrt(T._nb(n, y.ndim) / d)   # n: scalar or per-row (BH,)
    return y.astype(v.dtype), T.TaylorState(s2=s2_new, s1=s1, s0=s0, n=n)
