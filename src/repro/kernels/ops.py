"""Jitted wrappers around the Pallas TaylorShift kernels.

These are the entry points the attention layer uses when
``cfg.taylor.use_kernel`` is set: they apply Algorithm 1's input
normalization (ℓ2 + temperature τ + α-scaling) in plain JAX, reshape
(B, H, N, d) → (BH, N, d), and dispatch to the kernels. On the CPU they
run the kernels in interpret mode (Python execution of the kernel body)
so correctness is testable there; see :func:`interpret_mode`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import taylor as T
from repro.kernels.taylor_direct import taylor_direct_attention
from repro.kernels.taylor_efficient import taylor_efficient_attention
from repro.kernels.taylor_grad import (taylor_direct_attention_vjp,
                                       taylor_efficient_attention_vjp)


def interpret_mode() -> bool:
    """Whether the Pallas kernels run interpreted on this backend.

    The one place that decides it: compiled on a TPU, interpreted on the
    CPU (tests and CPU rehearsals). Any other platform has no lowering
    for these kernels, and interpreting them there would hide the
    device, so it raises.
    """
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas TPU kernels cannot run on platform {platform!r}")


def _flatten_heads(x):
    b, h, n, d = x.shape
    return x.reshape(b * h, n, d)


def _prep(q, k, tau):
    d = q.shape[-1]
    alpha = d ** 0.25
    q, k = T.normalize_qk(q, k, tau)
    return (q * alpha).astype(jnp.float32), (k * alpha).astype(jnp.float32)


def taylor_attention_kernel(q, k, v, *, tau=1.0, causal: bool = False,
                            mode: str = "auto", out_scale: bool = True,
                            block_q: int = 128, block_k: int = 128,
                            interpret: bool | None = None):
    """Fused TaylorShift attention. q,k,v: (B, H, N, d) raw.

    mode: auto → paper crossover N0(d); causal currently implies the
    direct kernel (the chunked-causal efficient form stays in core/).
    """
    interp = interpret_mode() if interpret is None else interpret
    b, h, n, d = q.shape
    m = k.shape[2]
    if mode == "auto":
        mode = T.pick_mode(n, d)
    if causal:
        mode = "direct"
    qs, ks = _prep(q, k, tau)
    qf = _flatten_heads(qs)
    kf = _flatten_heads(ks)
    vf = _flatten_heads(v)
    bq, n_pad = _good_block(n, block_q)
    bk, m_pad = _good_block(m, block_k)
    qf = _pad_rows(qf, n_pad)
    kf = _pad_rows(kf, m_pad)
    vf = _pad_rows(vf, m_pad)
    mv = m if m_pad != m else None
    # Dispatch through the custom-VJP entries (kernels/taylor_grad.py):
    # undifferentiated calls execute the plain forward kernels, while
    # jax.grad gets the hand-written Pallas backward — so this one entry
    # serves inference and training alike.
    if mode == "direct":
        y = taylor_direct_attention_vjp(qf, kf, vf, causal=causal, block_q=bq,
                                        block_k=bk, out_scale=out_scale,
                                        interpret=interp, m_valid=mv)
    else:
        y = taylor_efficient_attention_vjp(qf, kf, vf, block_q=bq, block_k=bk,
                                           out_scale=out_scale,
                                           interpret=interp, m_valid=mv)
    return y[:, :n].reshape(b, h, n, d)


def _good_block(n: int, want: int) -> tuple[int, int]:
    """(block, padded_n): keep the wanted block size and pad n up to the
    next multiple, rather than shrinking the block until it divides n
    (which degrades to block=1 — a catastrophic grid — for prime n).
    Padded keys are masked out via ``m_valid``; padded query rows are
    sliced off the output."""
    b = min(want, max(n, 1))
    return b, -(-n // b) * b


def _pad_rows(x, n_pad: int):
    n = x.shape[1]
    if n_pad == n:
        return x
    return jnp.pad(x, ((0, 0), (0, n_pad - n), (0, 0)))


__all__ = ["interpret_mode", "taylor_attention_kernel",
           "taylor_direct_attention", "taylor_efficient_attention",
           "taylor_direct_attention_vjp", "taylor_efficient_attention_vjp"]
