"""Multi-head attention with TaylorShift / softmax backends.

The paper's technique is integrated as a first-class backend: every
attention site (global causal, global non-causal, sliding-window local,
cross-attention, and single-token decode) has a TaylorShift form. *Which*
form runs — direct/efficient crossover, fused Pallas kernels,
chunked-causal scan (sequential or sequence-parallel), fused decode — is
resolved by ``models/backend.py:select_backend``; this module only
implements the sites and dispatches on the returned Selection.

Caches for decode:
  * ``kv``     — classic KV cache (softmax or direct-Taylor readout)
  * ``taylor`` — constant-size TaylorState (efficient-Taylor readout);
                 this is what makes ``long_500k`` feasible for
                 full-attention architectures.
  * local layers always use a bounded ring-buffer window cache.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import taylor as T
from repro.distributed import ctx
from repro.models import backend as B
from repro.models import layers as L

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def attn_init(key, cfg: ModelConfig) -> Params:
    dh, H, KV = cfg.dim_head, cfg.n_heads, cfg.kv_heads
    ks = jax.random.split(key, 4)
    dt = cfg.param_dtype
    p: Params = {
        "wq": L.dense_init(ks[0], cfg.d_model, H * dh, dt),
        "wk": L.dense_init(ks[1], cfg.d_model, KV * dh, dt),
        "wv": L.dense_init(ks[2], cfg.d_model, KV * dh, dt),
        "wo": L.dense_init(ks[3], H * dh, cfg.d_model, dt),
    }
    if cfg.attn_backend == "taylor":
        p["tau"] = jnp.full((H,), cfg.taylor.tau_init, jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = L.rmsnorm_init(dh)
        p["k_norm"] = L.rmsnorm_init(dh)
    return p


def _split_heads(x, n_heads, dh):
    b, n, _ = x.shape
    return x.reshape(b, n, n_heads, dh).transpose(0, 2, 1, 3)  # (B,H,N,dh)


def _merge_heads(x):
    b, h, n, dh = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, n, h * dh)


def _project_qkv(params, cfg: ModelConfig, x, positions, *, rope=True):
    dh, H, KV = cfg.dim_head, cfg.n_heads, cfg.kv_heads
    q = _split_heads(L.dense(params["wq"], x), H, dh)
    k = _split_heads(L.dense(params["wk"], x), KV, dh)
    v = _split_heads(L.dense(params["wv"], x), KV, dh)
    if cfg.qk_norm:
        q = L.rmsnorm(params["q_norm"], q)
        k = L.rmsnorm(params["k_norm"], k)
    if rope and cfg.pos_embed == "rope":
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _group_q(q, KV):
    """(B,H,N,d) -> (B,KV,G,N,d) so Taylor states are per-kv-head."""
    b, h, n, d = q.shape
    return q.reshape(b, KV, h // KV, n, d)


def _tau(params, cfg: ModelConfig, grouped: bool):
    tau = params["tau"].astype(jnp.float32)
    if grouped:
        return tau.reshape(1, cfg.kv_heads, cfg.n_heads // cfg.kv_heads, 1, 1)
    return tau.reshape(1, cfg.n_heads, 1, 1)


# ---------------------------------------------------------------------------
# Full-sequence attention (train / prefill)
# ---------------------------------------------------------------------------

def _softmax_attention(cfg, q, k, v, *, causal, window=0):
    """Vanilla baseline (the paper's comparison target). GQA by repeat."""
    b, h, n, d = q.shape
    kv = k.shape[1]
    if kv != h:
        rep = h // kv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    x = jnp.einsum("bhnd,bhmd->bhnm", q, k,
                   preferred_element_type=jnp.float32) / math.sqrt(d)
    if cfg.softcap_attn:
        x = L.softcap(x, cfg.softcap_attn)
    m = k.shape[2]
    if causal:
        mask = jnp.tril(jnp.ones((n, m), bool), m - n)
        if window:
            mask &= jnp.triu(jnp.ones((n, m), bool), m - n - window + 1)
        x = jnp.where(mask, x, -1e30)
    a = jax.nn.softmax(x, axis=-1)
    y = jnp.einsum("bhnm,bhmd->bhnd", a.astype(v.dtype), v)
    return y


def _repeat_kv(cfg: ModelConfig, k, v):
    rep = cfg.n_heads // cfg.kv_heads
    return jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)


def _causal_scan_opts(sel: B.Selection) -> dict:
    """causal_taylorshift kwargs implementing a causal-scan Selection:
    which chunk-scan core runs and (for the sequential core) the
    mesh-aware state sharder."""
    if sel.scan == "seq-parallel":
        from repro.distributed import seqscan
        c = ctx.get()
        return {"chunk": sel.chunk,
                "scan_fn": seqscan.make_seq_scan(c.mesh, axis=c.seq_axis)}
    if sel.scan == "parallel":
        return {"chunk": sel.chunk, "scan_impl": "parallel"}
    c = ctx.get()
    sharder = None
    if c.enabled:
        dpspec = c.dp_spec
        sharder = lambda s2: ctx.constrain(
            s2, dpspec, None, *([None] * (s2.ndim - 4)), "model", None)
    return {"chunk": sel.chunk, "state_sharder": sharder}


def _taylor_global(cfg: ModelConfig, params, q, k, v, *, causal):
    """Full-sequence TaylorShift: resolve the path through
    models/backend.py:select_backend and dispatch on the Selection —
    all routing heuristics (crossovers, mesh gates, kernel gates, GQA
    constraints) live in the backend module."""
    tc = cfg.taylor
    N, d = q.shape[-2], q.shape[-1]
    sel = B.select_backend(cfg, N=N, d=d, site="full", causal=causal)
    if sel.repeat_kv:
        k, v = _repeat_kv(cfg, k, v)
    if sel.backend.caps.kernel:
        from repro.kernels import ops as K
        return K.taylor_attention_kernel(
            q, k, v, tau=_tau(params, cfg, False), causal=causal,
            mode=sel.mode, out_scale=tc.output_scale)
    if sel.name == "direct":
        return T.direct_taylorshift(
            q, k, v, tau=_tau(params, cfg, False), causal=causal,
            normalize_inputs=tc.normalize_inputs,
            output_scale=tc.output_scale)
    qg = _group_q(q, cfg.kv_heads)
    kg, vg = k[:, :, None], v[:, :, None]
    tau = _tau(params, cfg, True)
    if sel.name == "causal-scan":
        y = T.causal_taylorshift(
            qg, kg, vg, tau=tau,
            normalize_inputs=tc.normalize_inputs,
            output_scale=tc.output_scale, **_causal_scan_opts(sel))
    else:
        y = T.efficient_taylorshift(
            qg, kg, vg, tau=tau,
            normalize_inputs=tc.normalize_inputs,
            output_scale=tc.output_scale)
    return y.reshape(q.shape)


def _local_taylor(cfg: ModelConfig, params, q, k, v):
    """Causal sliding-window attention, blocked so cost is O(N·w).

    Window w sits far below the paper's N0 crossover, so the *direct*
    Taylor form is the paper-optimal choice here ("and Back").
    Query block i attends key blocks i-1 and i with a banded mask.
    """
    w = cfg.window
    b, h, n, d = q.shape
    kv = k.shape[1]
    if kv != h:
        rep = h // kv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    if n <= w or n % w:
        # Small or ragged sequences: banded direct form (O(N²), only hit
        # far below the crossover / in tests).
        qpos = jnp.arange(n)[:, None]
        kpos = jnp.arange(n)[None, :]
        band = (kpos <= qpos) & (kpos > qpos - w)
        y = T.direct_taylorshift(
            q, k, v, tau=_tau(params, cfg, False), causal=False, mask=band,
            normalize_inputs=cfg.taylor.normalize_inputs, output_scale=False)
        if cfg.taylor.output_scale:
            counts = jnp.minimum(jnp.arange(1, n + 1), w).astype(jnp.float32)
            y = y * jnp.sqrt(counts / d)[None, None, :, None]
        return y
    nb = n // w
    tau = _tau(params, cfg, False)
    tc = cfg.taylor
    if tc.normalize_inputs:
        q, k = T.normalize_qk(q, k, tau)
    qb = q.reshape(b, h, nb, w, d)
    kb = k.reshape(b, h, nb, w, d)
    vb = v.reshape(b, h, nb, w, d)
    k_prev = jnp.concatenate([jnp.zeros_like(kb[:, :, :1]), kb[:, :, :-1]], 2)
    v_prev = jnp.concatenate([jnp.zeros_like(vb[:, :, :1]), vb[:, :, :-1]], 2)
    kk = jnp.concatenate([k_prev, kb], axis=3)           # (B,H,nb,2w,d)
    vv = jnp.concatenate([v_prev, vb], axis=3)
    x = jnp.einsum("bhgqd,bhgkd->bhgqk", qb, kk,
                   preferred_element_type=jnp.float32)
    a = T.taylor_exp(x)
    qpos = jnp.arange(w)[:, None]
    kpos = jnp.arange(2 * w)[None, :] - w
    band = (kpos <= qpos) & (kpos > qpos - w)            # exactly w keys
    first_blk = jnp.arange(nb) == 0
    valid = jnp.where(first_blk[:, None, None],
                      (kpos >= 0) & (kpos <= qpos), band[None, :, :])
    a = jnp.where(valid[None, None], a, 0.0)
    denom = jnp.sum(a, axis=-1, keepdims=True)
    y = jnp.einsum("bhgqk,bhgkd->bhgqd", a / denom, vv.astype(a.dtype))
    if tc.output_scale:
        counts = jnp.where(first_blk[:, None], qpos.T + 1, w).astype(jnp.float32)
        y = y * jnp.sqrt(counts / d)[None, None, :, :, None]
    return y.reshape(b, h, n, d).astype(v.dtype)


def attn_apply(params: Params, cfg: ModelConfig, x: jnp.ndarray, *,
               positions: jnp.ndarray, kind: str = "global",
               causal: bool = True,
               cross_kv: tuple[jnp.ndarray, jnp.ndarray] | None = None
               ) -> jnp.ndarray:
    """Full-sequence attention. x: (B, N, d_model)."""
    q, k, v = _project_qkv(params, cfg, x, positions,
                           rope=(cross_kv is None))
    if cross_kv is not None:
        k, v = cross_kv  # (B, KV, M, dh) — already projected by the encoder side
        causal = False
    if cfg.attn_backend == "softmax":
        y = _softmax_attention(cfg, q, k, v, causal=causal,
                               window=cfg.window if kind == "local" else 0)
    elif kind == "local" and causal:
        y = _local_taylor(cfg, params, q, k, v)
    else:
        y = _taylor_global(cfg, params, q, k, v, causal=causal)
    return L.dense(params["wo"], _merge_heads(y).astype(x.dtype))


def project_cross_kv(params: Params, cfg: ModelConfig,
                     enc_out: jnp.ndarray):
    """Project encoder outputs to (K, V) once for all decoder steps."""
    dh, KV = cfg.dim_head, cfg.kv_heads
    k = _split_heads(L.dense(params["wk"], enc_out), KV, dh)
    v = _split_heads(L.dense(params["wv"], enc_out), KV, dh)
    if cfg.qk_norm:
        k = L.rmsnorm(params["k_norm"], k)
    return k, v


# ---------------------------------------------------------------------------
# Decode caches
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, *, kind: str, cache_len: int,
               cache_kind: str = "taylor", dtype=jnp.bfloat16,
               per_slot: bool = False):
    """Cache pytree for one attention layer.

    ``per_slot=True`` gives every batch row its own position counter
    (shape (batch,) instead of scalar) so rows can sit at different
    context lengths — the layout the continuous-batching slot pool in
    ``repro.serve`` decodes over.
    """
    dh, KV = cfg.dim_head, cfg.kv_heads
    n_dims = (batch,) if per_slot else ()
    if kind == "local":
        w = cfg.window
        return {
            "k": jnp.zeros((batch, KV, w, dh), dtype),
            "v": jnp.zeros((batch, KV, w, dh), dtype),
            "pos": jnp.zeros(n_dims, jnp.int32),
        }
    if cache_kind == "taylor":
        return T.TaylorState.zeros((batch, KV, 1), dh, n_dims=n_dims)
    return {
        "k": jnp.zeros((batch, KV, cache_len, dh), dtype),
        "v": jnp.zeros((batch, KV, cache_len, dh), dtype),
        "pos": jnp.zeros(n_dims, jnp.int32),
    }


def _decode_positions(pos: jnp.ndarray) -> jnp.ndarray:
    """Rope-broadcastable positions for a one-token step: scalar shared
    position -> (1,); per-slot (B,) -> (B, 1, 1) so the angle table
    broadcasts over heads."""
    return pos[None] if pos.ndim == 0 else pos[:, None, None]


def attn_decode(params: Params, cfg: ModelConfig, x: jnp.ndarray, cache,
                *, kind: str = "global",
                cross_state: T.TaylorState | None = None):
    """One-token decode. x: (B, 1, d_model). Returns (y, new_cache)."""
    if cross_state is not None:
        # cross-attention readout from the frozen encoder Taylor state
        dh, H, KV = cfg.dim_head, cfg.n_heads, cfg.kv_heads
        q = _split_heads(L.dense(params["wq"], x), H, dh)
        if cfg.qk_norm:
            q = L.rmsnorm(params["q_norm"], q)
        qg = _group_q(q, KV)
        y = T.taylor_readout(cross_state, qg, tau=_tau(params, cfg, True),
                             normalize_inputs=cfg.taylor.normalize_inputs,
                             output_scale=cfg.taylor.output_scale)
        y = y.reshape(q.shape).astype(x.dtype)
        return L.dense(params["wo"], _merge_heads(y)), cache

    is_taylor_state = isinstance(cache, T.TaylorState)
    pos = cache.n if is_taylor_state else cache["pos"]
    q, k, v = _project_qkv(params, cfg, x, _decode_positions(pos))

    sel = B.select_backend(cfg, N=1, d=cfg.dim_head, site="decode",
                           cache_kind="taylor" if is_taylor_state else "kv")
    if is_taylor_state:
        if sel.name == "fused-decode":
            y, cache = _fused_taylor_decode(params, cfg, cache, q, k, v)
        else:
            # causal-scan's one-token limit: the recurrent decode step
            # (grouped per-kv-head states — the GQA layout fused-decode's
            # flat (B·H) kernel can't serve; see its capability flags)
            qg = _group_q(q, cfg.kv_heads)
            kg, vg = k[:, :, None], v[:, :, None]
            y, cache = T.taylor_decode_step(
                cache, qg, kg, vg, tau=_tau(params, cfg, True),
                normalize_inputs=cfg.taylor.normalize_inputs,
                output_scale=cfg.taylor.output_scale)
            y = y.reshape(q.shape)
    else:
        w = cache["k"].shape[2]
        slot = jnp.mod(pos, w) if kind == "local" else pos
        kc = k.astype(cache["k"].dtype)
        vc = v.astype(cache["v"].dtype)
        if pos.ndim:   # per-slot cache: every sequence writes its own index
            upd = jax.vmap(
                lambda c, u, s: jax.lax.dynamic_update_slice_in_dim(c, u, s, 1))
            ck, cv = upd(cache["k"], kc, slot), upd(cache["v"], vc, slot)
        else:
            ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], kc, slot, 2)
            cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], vc, slot, 2)
        cache = {"k": ck, "v": cv, "pos": pos + 1}
        n_valid = jnp.minimum(pos + 1, w) if kind == "local" else pos + 1
        y = _decode_attend(cfg, params, q, ck, cv, n_valid, w)
    return L.dense(params["wo"], _merge_heads(y).astype(x.dtype)), cache


def _fused_taylor_decode(params: Params, cfg: ModelConfig,
                         cache: T.TaylorState, q, k, v):
    """Route the one-token update+readout through the fused Pallas
    decode kernel (kernels/taylor_decode.py). MHA only (H == KV): the
    kernel works on flattened (B·H, ...) states with no GQA grouping."""
    from repro.kernels.ops import interpret_mode
    from repro.kernels.taylor_decode import taylor_decode_kernel

    B, H, _, dh = q.shape
    interp = interpret_mode()
    flat3 = lambda t: t.reshape(B * H, *t.shape[3:])   # (B,H,1,X,Y)->(BH,X,Y)
    n_flat = cache.n if cache.n.ndim == 0 else jnp.repeat(cache.n, H)
    st = T.TaylorState(s2=flat3(cache.s2), s1=flat3(cache.s1),
                       s0=flat3(cache.s0), n=n_flat)
    tau = jnp.tile(params["tau"].astype(jnp.float32).reshape(H, 1, 1),
                   (B, 1, 1))
    yf, stn = taylor_decode_kernel(
        st, q.reshape(B * H, 1, dh), k.reshape(B * H, 1, dh),
        v.reshape(B * H, 1, dh), tau=tau,
        normalize_inputs=cfg.taylor.normalize_inputs,
        output_scale=cfg.taylor.output_scale, interpret=interp)
    unflat = lambda t: t.reshape(B, H, 1, *t.shape[1:])
    new = T.TaylorState(s2=unflat(stn.s2), s1=unflat(stn.s1),
                        s0=unflat(stn.s0), n=cache.n + 1)
    return yf.reshape(B, H, 1, dh), new


def attn_prefill(params: Params, cfg: ModelConfig, x: jnp.ndarray, cache,
                 *, kind: str = "global"):
    """Chunked-prefill attention with state handoff.

    Attends causally over (cached context + this chunk) and absorbs the
    chunk into the cache in one shot — the multi-token replacement for
    looping :func:`attn_decode` over prompt tokens. For a TaylorState
    cache this drives ``core.taylor.causal_taylorshift(initial_state=...,
    return_state=True)``; the resulting state is then consumed by the
    decode path (``taylor_decode_step`` / the fused decode kernel).

    x: (B, C, d_model); cache: TaylorState or kv dict with a *scalar*
    position counter (prefill is per-sequence — the serve engine scatters
    the finished state into its slot pool). Returns (y, new_cache).
    """
    if kind != "global":
        raise NotImplementedError(
            "chunked prefill supports global attention only "
            f"(got kind={kind!r}); local ring-buffer windows would need "
            "windowed chunk logic")
    is_taylor_state = isinstance(cache, T.TaylorState)
    pos = cache.n if is_taylor_state else cache["pos"]
    if pos.ndim:
        raise ValueError("attn_prefill is per-sequence (scalar position); "
                         "got a per-slot cache")
    C = x.shape[1]
    positions = pos + jnp.arange(C)
    q, k, v = _project_qkv(params, cfg, x, positions)

    sel = B.select_backend(cfg, N=C, d=cfg.dim_head, site="prefill",
                           cache_kind="taylor" if is_taylor_state else "kv")
    if is_taylor_state:
        qg = _group_q(q, cfg.kv_heads)
        kg, vg = k[:, :, None], v[:, :, None]
        y, cache = T.causal_taylorshift(
            qg, kg, vg, tau=_tau(params, cfg, True),
            normalize_inputs=cfg.taylor.normalize_inputs,
            output_scale=cfg.taylor.output_scale,
            initial_state=cache, return_state=True,
            **_causal_scan_opts(sel))
        y = y.reshape(q.shape)
    else:
        cache_len = cache["k"].shape[2]
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), pos, 2)
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), pos, 2)
        cache = {"k": ck, "v": cv, "pos": pos + C}
        qpos = pos + jnp.arange(C)
        # row i sees keys at absolute index <= pos+i; unwritten cache
        # slots sit beyond pos+C-1 and are excluded by the same mask
        mask = jnp.arange(cache_len)[None, :] <= qpos[:, None]      # (C, L)
        y = _prefill_attend(cfg, params, q, ck, cv, mask, counts=qpos + 1)
    return L.dense(params["wo"], _merge_heads(y).astype(x.dtype)), cache


def attn_verify(params: Params, cfg: ModelConfig, x: jnp.ndarray, cache,
                *, kind: str = "global"):
    """Multi-token scoring + absorb from a *per-slot* decode cache — the
    speculative-verify site (src/repro/spec/, docs/design.md §4.4).

    Where :func:`attn_prefill` continues one sequence (scalar counters),
    verify continues every slot of the pool at once: x is (B, C,
    d_model) with B = slots and C = speculate_k + 1, and the cache
    carries per-slot (B,) position counters, so each row attends —
    causally within its block — from its own context length. The same
    path also serves the rollback re-absorb (a gathered batch-1 slot,
    counters (1,)). Routed through ``select_backend(site="verify")``:
    one sequential ``causal_taylorshift`` chunk for Taylor state, a
    per-slot masked direct attend for kv caches.

    Returns (y, new_cache) with every slot advanced by C tokens; the
    caller snapshots/restores slots whose drafts are rejected.
    """
    if kind != "global":
        raise NotImplementedError(
            f"speculative verify supports global attention only "
            f"(got kind={kind!r})")
    is_taylor_state = isinstance(cache, T.TaylorState)
    pos = cache.n if is_taylor_state else cache["pos"]
    C = x.shape[1]
    step = jnp.arange(C)
    # rope positions broadcast over heads: (B, 1, C) per-slot, (C,) scalar
    rpos = pos + step if pos.ndim == 0 else pos[:, None, None] + step
    q, k, v = _project_qkv(params, cfg, x, rpos)

    sel = B.select_backend(cfg, N=C, d=cfg.dim_head, site="verify",
                           cache_kind="taylor" if is_taylor_state else "kv")
    if is_taylor_state:
        qg = _group_q(q, cfg.kv_heads)
        kg, vg = k[:, :, None], v[:, :, None]
        y, cache = T.causal_taylorshift(
            qg, kg, vg, tau=_tau(params, cfg, True),
            normalize_inputs=cfg.taylor.normalize_inputs,
            output_scale=cfg.taylor.output_scale,
            initial_state=cache, return_state=True, chunk=sel.chunk)
        y = y.reshape(q.shape)
    else:
        cache_len = cache["k"].shape[2]
        kc = k.astype(cache["k"].dtype)
        vc = v.astype(cache["v"].dtype)
        if pos.ndim:   # per-slot cache: every sequence writes its own index
            upd = jax.vmap(
                lambda c, u, s: jax.lax.dynamic_update_slice_in_dim(c, u, s, 1))
            ck, cv = upd(cache["k"], kc, pos), upd(cache["v"], vc, pos)
            qpos = pos[:, None] + step                            # (B, C)
            mask = jnp.arange(cache_len)[None, None] <= qpos[:, :, None]
        else:
            ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], kc, pos, 2)
            cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], vc, pos, 2)
            qpos = pos + step
            mask = jnp.arange(cache_len)[None] <= qpos[:, None]   # (C, L)
        cache = {"k": ck, "v": cv, "pos": pos + C}
        y = _prefill_attend(cfg, params, q, ck, cv, mask, counts=qpos + 1)
    return L.dense(params["wo"], _merge_heads(y).astype(x.dtype)), cache


def _prefill_attend(cfg, params, q, ck, cv, mask, counts):
    """Masked multi-query attention over a kv cache during chunked
    prefill / speculative verify. q: (B,H,C,d); ck/cv: (B,KV,L,d);
    mask: (C, L) shared, or (B, C, L) per-slot; counts: (C,) or (B, C)
    true per-row context lengths."""
    b, h, _, d = q.shape
    kv = ck.shape[1]
    if kv != h:
        rep = h // kv
        ck = jnp.repeat(ck, rep, axis=1)
        cv = jnp.repeat(cv, rep, axis=1)
    mask4 = mask[None, None] if mask.ndim == 2 else mask[:, None]
    if cfg.attn_backend == "softmax":
        x = jnp.einsum("bhcd,bhmd->bhcm", q, ck,
                       preferred_element_type=jnp.float32) / math.sqrt(d)
        if cfg.softcap_attn:
            x = L.softcap(x, cfg.softcap_attn)
        x = jnp.where(mask4, x, -1e30)
        a = jax.nn.softmax(x, -1)
        return jnp.einsum("bhcm,bhmd->bhcd", a.astype(cv.dtype), cv)
    tc = cfg.taylor
    tau = _tau(params, cfg, False)
    if tc.normalize_inputs:
        q, ck = T.normalize_qk(q, ck, tau)
    x = jnp.einsum("bhcd,bhmd->bhcm", q, ck,
                   preferred_element_type=jnp.float32)
    a = jnp.where(mask4, T.taylor_exp(x), 0.0)
    y = jnp.einsum("bhcm,bhmd->bhcd", a / jnp.sum(a, -1, keepdims=True),
                   cv.astype(a.dtype))
    if tc.output_scale:
        cf = counts.astype(jnp.float32)
        cf = cf[None, None, :, None] if cf.ndim == 1 else cf[:, None, :, None]
        y = y * jnp.sqrt(cf / d)
    return y.astype(cv.dtype)


def _decode_attend(cfg, params, q, ck, cv, n_valid, cache_len):
    """Masked single-query attention over a (possibly ring) cache.

    ``n_valid`` is scalar (shared context length) or (B,) per-slot.
    """
    b, h, _, d = q.shape
    kv = ck.shape[1]
    if kv != h:
        rep = h // kv
        ck = jnp.repeat(ck, rep, axis=1)
        cv = jnp.repeat(cv, rep, axis=1)
    # (1 or B, cache_len) validity, broadcast over heads and the 1 query
    valid = (jnp.arange(cache_len)[None]
             < jnp.reshape(n_valid, (-1, 1)))[:, None, None, :]
    if cfg.attn_backend == "softmax":
        x = jnp.einsum("bhqd,bhmd->bhqm", q, ck,
                       preferred_element_type=jnp.float32) / math.sqrt(d)
        if cfg.softcap_attn:
            x = L.softcap(x, cfg.softcap_attn)
        x = jnp.where(valid, x, -1e30)
        a = jax.nn.softmax(x, -1)
        return jnp.einsum("bhqm,bhmd->bhqd", a.astype(cv.dtype), cv)
    tc = cfg.taylor
    tau = _tau(params, cfg, False)
    if tc.normalize_inputs:
        q, ck = T.normalize_qk(q, ck, tau)
    x = jnp.einsum("bhqd,bhmd->bhqm", q, ck,
                   preferred_element_type=jnp.float32)
    a = jnp.where(valid, T.taylor_exp(x), 0.0)
    y = jnp.einsum("bhqm,bhmd->bhqd", a / jnp.sum(a, -1, keepdims=True),
                   cv.astype(a.dtype))
    if tc.output_scale:
        y = y * jnp.sqrt(T._nb(n_valid, y.ndim) / d)
    return y.astype(cv.dtype)
