"""Model assembly: decoder LMs, hybrids, xLSTM, and encoder-decoder.

Layers are grouped by the config's ``layer_pattern`` (the repeating
heterogeneity unit) and scanned with ``lax.scan`` over stacked parameter
pytrees, so HLO size and compile time are O(pattern length), not
O(n_layers) — essential for 46–81-layer archs compiled 80× in the
dry-run sweep. A remainder of ``n_layers mod len(pattern)`` layers is
unrolled at the end.

Public surface:
  init_params(cfg, rng)                      -> params (or eval_shape'able)
  forward(params, cfg, batch, training)      -> (hidden, aux_loss)
  loss_fn(params, cfg, batch)                -> scalar (chunked xent)
  init_decode_state(cfg, batch, cache_len, cache_kind) -> cache
  decode_step(params, cfg, batch, cache)     -> (logits, cache)
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import taylor as T
from repro.distributed import ctx
from repro.models import attention as A
from repro.models import layers as L
from repro.models import mamba2 as M2
from repro.models import moe as MOE
from repro.models import xlstm as XL

Params = dict[str, Any]

ATTN_KINDS = ("global", "local", "global_moe")


# ---------------------------------------------------------------------------
# Block init / apply / decode — dispatch on pattern kind
# ---------------------------------------------------------------------------

def _norm(cfg: ModelConfig):
    """(init, apply) of the configured norm, at the configured RMS eps."""
    return L.make_norm(cfg.norm, cfg.rms_norm_eps)


def _ffn_init(kind: str, key, cfg: ModelConfig, ninit) -> Params:
    """Params of the MLP (or MoE) sub-layer after a block's mixer."""
    p: Params = {"norm2": ninit(cfg.d_model)}
    if kind == "global_moe":
        p["moe"] = MOE.moe_init(key, cfg)
    else:
        p["mlp"] = L.mlp_init(key, cfg.d_model, cfg.d_ff,
                              gated=cfg.gated_mlp, dtype=cfg.param_dtype)
    if cfg.post_norm:
        p["norm2_post"] = ninit(cfg.d_model)
    return p


def _block_init(kind: str, key, cfg: ModelConfig, *, cross: bool = False) -> Params:
    ninit, _ = _norm(cfg)
    ks = jax.random.split(key, 4)
    p: Params = {}
    if kind in ATTN_KINDS:
        p["norm1"] = ninit(cfg.d_model)
        p["attn"] = A.attn_init(ks[0], cfg)
        if cfg.post_norm:
            p["norm1_post"] = ninit(cfg.d_model)
        if cross:
            p["norm_x"] = ninit(cfg.d_model)
            p["cross"] = A.attn_init(ks[3], cfg)
        if cfg.d_ff:
            p.update(_ffn_init(kind, ks[1], cfg, ninit))
    elif kind in ("mamba", "mamba_shared"):
        if kind == "mamba_shared":
            # shared attention weights live at top level; only norms
            # are local
            p["norm_shared"] = ninit(cfg.d_model)
        p["norm1"] = ninit(cfg.d_model)
        p["mamba"] = M2.mamba2_init(ks[0], cfg)
        if cfg.ssm.mlp:
            p.update(_ffn_init(kind, ks[1], cfg, ninit))
    elif kind == "mlstm":
        p["norm1"] = ninit(cfg.d_model)
        p["mlstm"] = XL.mlstm_init(ks[0], cfg)
    elif kind == "slstm":
        p["norm1"] = ninit(cfg.d_model)
        p["slstm"] = XL.slstm_init(ks[0], cfg)
    else:
        raise ValueError(f"unknown block kind {kind}")
    return p


def _block_apply(kind: str, p: Params, cfg: ModelConfig, x, *, positions,
                 causal: bool, shared: Params | None,
                 cross_kv=None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One residual block. Sequence-parallel discipline: the residual
    carry x stays d_model-sharded; each sub-layer's input is explicitly
    all-gathered in bf16 (ctx.gathered) and its output reduce-scattered
    back (ctx.activations)."""
    _, norm = _norm(cfg)
    aux = jnp.zeros((), jnp.float32)
    if kind in ATTN_KINDS:
        z = ctx.gathered(norm(p["norm1"], x))
        h = A.attn_apply(p["attn"], cfg, z, positions=positions,
                         kind="local" if kind == "local" else "global",
                         causal=causal)
        h = ctx.activations(h)
        if cfg.post_norm:
            h = norm(p["norm1_post"], h)
        x = _residual(cfg, x, h)
        if cross_kv is not None:
            h = A.attn_apply(p["cross"], cfg,
                             ctx.gathered(norm(p["norm_x"], x)),
                             positions=positions, cross_kv=cross_kv)
            x = _residual(cfg, x, ctx.activations(h))
    elif kind in ("mamba", "mamba_shared"):
        if kind == "mamba_shared":
            assert shared is not None
            h = A.attn_apply(shared["attn"], cfg,
                             ctx.gathered(norm(p["norm_shared"], x)),
                             positions=positions, causal=causal)
            x = _residual(cfg, x, ctx.activations(h))
        h = M2.mamba2_apply(p["mamba"], cfg,
                            ctx.gathered(norm(p["norm1"], x)))
        x = _residual(cfg, x, ctx.activations(h))
    elif kind == "mlstm":
        h = XL.mlstm_apply(p["mlstm"], cfg, ctx.gathered(norm(p["norm1"], x)))
        x = _residual(cfg, x, ctx.activations(h))
    elif kind == "slstm":
        h = XL.slstm_apply(p["slstm"], cfg, ctx.gathered(norm(p["norm1"], x)))
        x = _residual(cfg, x, ctx.activations(h))
    if "norm2" in p:
        x, aux = _ffn(kind, p, cfg, x, norm, seq_parallel=True)
    return x, aux


def _residual(cfg: ModelConfig, x, h):
    """x + h, the sub-layer's output scaled by the residual multiplier
    (skipped at 1, so those models' programs are unchanged)."""
    if cfg.residual_multiplier == 1.0:
        return x + h
    return x + h * jnp.asarray(cfg.residual_multiplier, h.dtype)


def _ffn(kind: str, p: Params, cfg: ModelConfig, x, norm, *,
         seq_parallel: bool = False):
    """The MLP (or MoE) sub-layer after a block's mixer, with its
    residual: shared by the full-sequence, decode and chunk-absorb
    paths. ``seq_parallel`` puts in the full-sequence path's explicit
    gather and scatter (see :func:`_block_apply`). Returns (x, aux)."""
    aux = jnp.zeros((), jnp.float32)
    z = norm(p["norm2"], x)
    if seq_parallel:
        z = ctx.gathered(z)
    if kind == "global_moe":
        h, aux = MOE.moe_apply(p["moe"], cfg, z)
    else:
        h = L.mlp(p["mlp"], z, act=cfg.act)
    if seq_parallel:
        h = ctx.activations(h)
    if cfg.post_norm:
        h = norm(p["norm2_post"], h)
    return _residual(cfg, x, h), aux


def _block_init_cache(kind: str, cfg: ModelConfig, batch: int, cache_len: int,
                      cache_kind: str, dtype, per_slot: bool = False):
    if kind in ATTN_KINDS:
        return A.init_cache(cfg, batch, kind="global", cache_len=cache_len,
                            cache_kind=cache_kind, dtype=dtype,
                            per_slot=per_slot)
    if kind == "local":  # pragma: no cover — kind handled above
        raise AssertionError
    if kind == "mamba":
        return M2.mamba2_init_cache(cfg, batch)
    if kind == "mamba_shared":
        return {"attn": A.init_cache(cfg, batch, kind="global",
                                     cache_len=cache_len,
                                     cache_kind=cache_kind, dtype=dtype,
                                     per_slot=per_slot),
                "mamba": M2.mamba2_init_cache(cfg, batch)}
    if kind == "mlstm":
        return XL.mlstm_init_cache(cfg, batch)
    if kind == "slstm":
        return XL.slstm_init_cache(cfg, batch)
    raise ValueError(kind)


def _cache_kind_for(kind: str, cfg: ModelConfig, cache_kind: str, batch: int,
                    cache_len: int, dtype, per_slot: bool = False):
    if kind == "local":
        return A.init_cache(cfg, batch, kind="local", cache_len=cache_len,
                            cache_kind="kv", dtype=dtype, per_slot=per_slot)
    return _block_init_cache(kind, cfg, batch, cache_len, cache_kind, dtype,
                             per_slot)


def _block_decode(kind: str, p: Params, cfg: ModelConfig, x, cache, *,
                  shared: Params | None, cross_state=None):
    _, norm = _norm(cfg)
    if kind in ATTN_KINDS or kind == "local":
        akind = "local" if kind == "local" else "global"
        h, cache_a = A.attn_decode(
            p["attn"], cfg, norm(p["norm1"], x),
            cache["self"] if cross_state is not None else cache, kind=akind)
        if cfg.post_norm:
            h = norm(p["norm1_post"], h)
        x = _residual(cfg, x, h)
        if cross_state is not None:
            h, _ = A.attn_decode(p["cross"], cfg, norm(p["norm_x"], x), None,
                                 cross_state=cross_state)
            x = _residual(cfg, x, h)
            cache = {"self": cache_a}
        else:
            cache = cache_a
    elif kind in ("mamba", "mamba_shared"):
        if kind == "mamba_shared":
            h, ca = A.attn_decode(shared["attn"], cfg,
                                  norm(p["norm_shared"], x), cache["attn"])
            x = _residual(cfg, x, h)
            y, cm = M2.mamba2_decode(p["mamba"], cfg, norm(p["norm1"], x),
                                     cache["mamba"])
            x = _residual(cfg, x, y)
            cache = {"attn": ca, "mamba": cm}
        else:
            y, cache = M2.mamba2_decode(p["mamba"], cfg, norm(p["norm1"], x),
                                        cache)
            x = _residual(cfg, x, y)
    elif kind == "mlstm":
        y, cache = XL.mlstm_decode(p["mlstm"], cfg, norm(p["norm1"], x), cache)
        x = _residual(cfg, x, y)
    elif kind == "slstm":
        y, cache = XL.slstm_decode(p["slstm"], cfg, norm(p["norm1"], x), cache)
        x = _residual(cfg, x, y)
    if "norm2" in p:
        x, _ = _ffn(kind, p, cfg, x, norm)
    return x, cache


PREFILL_KINDS = ("global", "global_moe", "mamba")


def _block_absorb(kind: str, p: Params, cfg: ModelConfig, x, cache, *,
                  attend, what: str):
    """One residual block over a multi-token chunk that consumes and
    returns the decode cache — shared body of chunked prefill
    (``attend=attn_prefill``, per-sequence scalar counters) and the
    per-slot body of pooled prefill and speculative verify
    (``attend=attn_verify``, per-slot counters; the whole pool in one
    call). Global attention, and Mamba2, whose conv tail and SSM state
    carry no counter, so both bodies run the same chunked SSD from the
    cached state. Local ring-buffer windows, the shared-attention
    hybrid and xLSTM blocks would need their own chunkwise handoff."""
    if kind not in PREFILL_KINDS:
        raise NotImplementedError(f"{what}: unsupported block kind {kind!r}")
    _, norm = _norm(cfg)
    if kind == "mamba":
        h, cache = M2.mamba2_prefill(p["mamba"], cfg, norm(p["norm1"], x),
                                     cache)
    else:
        h, cache = attend(p["attn"], cfg, norm(p["norm1"], x), cache)
        if cfg.post_norm:
            h = norm(p["norm1_post"], h)
    x = _residual(cfg, x, h)
    if "norm2" in p:
        x, _ = _ffn(kind, p, cfg, x, norm)
    return x, cache


def _block_prefill(kind: str, p: Params, cfg: ModelConfig, x, cache):
    return _block_absorb(kind, p, cfg, x, cache, attend=A.attn_prefill,
                         what="chunked prefill")


def _block_verify(kind: str, p: Params, cfg: ModelConfig, x, cache):
    return _block_absorb(kind, p, cfg, x, cache, attend=A.attn_verify,
                         what="speculative verify")


# ---------------------------------------------------------------------------
# Stacking machinery
# ---------------------------------------------------------------------------

def _pattern_layout(cfg: ModelConfig, n_layers: int | None = None):
    pattern = tuple(cfg.layer_pattern)
    n = n_layers if n_layers is not None else cfg.n_layers
    P = len(pattern)
    return pattern, n // P, tuple(pattern[i] for i in range(n % P))


def _stacked_init(fn, key, n: int):
    return jax.vmap(fn)(jax.random.split(key, n))


def init_params(cfg: ModelConfig, rng) -> Params:
    pattern, n_groups, rem = _pattern_layout(cfg)
    keys = jax.random.split(rng, 8)
    p: Params = {"embed": L.embedding_init(keys[0], cfg.vocab, cfg.d_model,
                                           cfg.param_dtype)}
    ninit, _ = _norm(cfg)
    p["final_norm"] = ninit(cfg.d_model)
    if not cfg.tie_embeddings:
        p["unembed"] = L.dense_init(keys[6], cfg.d_model, cfg.vocab,
                                    cfg.param_dtype)
    if cfg.pos_embed == "learned":
        p["pos"] = L.learned_pos_init(keys[5], cfg.max_seq_len, cfg.d_model,
                                      cfg.param_dtype)
    if any(k == "mamba_shared" for k in cfg.layer_pattern):
        p["shared_attn"] = {"attn": A.attn_init(keys[4], cfg)}

    if n_groups:
        p["groups"] = [
            _stacked_init(lambda k, kind=kind: _block_init(kind, k, cfg),
                          jax.random.fold_in(keys[1], i), n_groups)
            for i, kind in enumerate(pattern)
        ]
    else:
        p["groups"] = []
    p["rem"] = [_block_init(kind, jax.random.fold_in(keys[2], i), cfg)
                for i, kind in enumerate(rem)]

    if cfg.family == "encdec":
        enc_cfg = cfg
        p["enc"] = {
            # STUB frontend (per assignment): linear mel->d_model projection
            "frontend_proj": L.dense_init(
                jax.random.fold_in(keys[7], 1), 128, cfg.d_model,
                cfg.param_dtype),
            "pos": L.learned_pos_init(keys[7], max(cfg.encoder_frames,
                                                   cfg.max_seq_len),
                                      cfg.d_model, cfg.param_dtype),
            "blocks": _stacked_init(
                lambda k: _block_init("global", k, enc_cfg),
                keys[3], cfg.n_encoder_layers),
            "final_norm": ninit(cfg.d_model),
        }
        # decoder blocks get cross-attention
        p["groups"] = [_stacked_init(
            lambda k: _block_init("global", k, cfg, cross=True),
            keys[1], n_groups)]
        p["rem"] = []
    return p


# ---------------------------------------------------------------------------
# Forward (train / full-sequence)
# ---------------------------------------------------------------------------

def _embed_tokens(params, cfg: ModelConfig, tokens):
    """Token embedding times the embedding multiplier (sqrt(d_model)
    unless the configuration sets one)."""
    x = L.embed(params["embed"], tokens)
    mult = (jnp.sqrt(cfg.d_model) if cfg.embedding_multiplier is None
            else cfg.embedding_multiplier)
    return x * jnp.asarray(mult, cfg.param_dtype)


def _embed_inputs(params, cfg: ModelConfig, batch):
    """Token (+ frontend-stub) embedding. Returns (x, positions)."""
    x = _embed_tokens(params, cfg, batch["tokens"])
    if cfg.frontend == "vision_stub" and "patch_embeds" in batch:
        x = jnp.concatenate([batch["patch_embeds"].astype(x.dtype), x], axis=1)
    positions = jnp.arange(x.shape[1])
    if cfg.pos_embed == "learned":
        x = L.add_learned_pos(params["pos"], x, positions)
    return ctx.activations(x), positions


def _run_blocks(params, cfg: ModelConfig, x, positions, *, causal: bool,
                cross_kv_list=None, n_layers: int | None = None):
    pattern, n_groups, rem = _pattern_layout(cfg, n_layers)
    shared = params.get("shared_attn")
    aux_total = jnp.zeros((), jnp.float32)

    if n_groups:
        def group_body(x, sliced):
            aux = jnp.zeros((), jnp.float32)
            for kind, bp in zip(pattern, sliced):
                x, a = _block_apply(kind, bp, cfg, x, positions=positions,
                                    causal=causal, shared=shared)
                aux += a
            return ctx.activations(x), aux

        body = jax.checkpoint(group_body) if cfg.remat else group_body

        def scan_fn(x, sliced):
            return body(x, sliced)

        x, auxs = jax.lax.scan(scan_fn, x, tuple(params["groups"]))
        aux_total += jnp.sum(auxs)

    for kind, bp in zip(rem, params["rem"]):
        x, a = _block_apply(kind, bp, cfg, x, positions=positions,
                            causal=causal, shared=shared)
        aux_total += a
    return x, aux_total


def _encode(params, cfg: ModelConfig, frames):
    """Whisper encoder over (stubbed) mel frames (B, M, n_mels) or
    precomputed embeddings (B, M, d_model)."""
    x = frames.astype(cfg.param_dtype)
    if x.shape[-1] != cfg.d_model:
        x = L.dense(params["enc"]["frontend_proj"], x)
    x = ctx.activations(x)
    pos = jnp.arange(x.shape[1])
    x = L.add_learned_pos(params["enc"]["pos"], x, pos)

    def body(x, bp):
        x, _ = _block_apply("global", bp, cfg, x, positions=pos,
                            causal=cfg.encoder_causal, shared=None)
        return x, None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    x, _ = jax.lax.scan(body_fn, x, params["enc"]["blocks"])
    _, norm = _norm(cfg)
    return norm(params["enc"]["final_norm"], x)


def forward(params, cfg: ModelConfig, batch, *, training: bool = False):
    """Returns (hidden (B,N,d), aux_loss). N includes any stub prefix."""
    _, norm = _norm(cfg)
    if cfg.family == "encdec":
        enc_out = _encode(params, cfg, batch["frames"])
        x, positions = _embed_inputs(params, cfg, batch)
        pattern, n_groups, _ = _pattern_layout(cfg)

        def body(x, bp):
            cross_kv = A.project_cross_kv(bp["cross"], cfg, enc_out)
            h = x
            h, _ = _block_apply("global", bp, cfg, h, positions=positions,
                                causal=True, shared=None, cross_kv=cross_kv)
            return h, None

        body_fn = jax.checkpoint(body) if cfg.remat else body
        x, _ = jax.lax.scan(body_fn, x, params["groups"][0])
        return norm(params["final_norm"], x), jnp.zeros((), jnp.float32)

    x, positions = _embed_inputs(params, cfg, batch)
    x, aux = _run_blocks(params, cfg, x, positions, causal=cfg.causal)
    return norm(params["final_norm"], x), aux


def logits_from_hidden(params, cfg: ModelConfig, hidden):
    if cfg.tie_embeddings:
        lg = L.unembed(params["embed"], hidden)
    else:
        lg = L.dense(params["unembed"], hidden).astype(jnp.float32)
    if cfg.logits_divisor != 1.0:
        lg = lg / cfg.logits_divisor
    if cfg.softcap_final:
        lg = L.softcap(lg, cfg.softcap_final)
    return lg


def loss_fn(params, cfg: ModelConfig, batch):
    """Next-token cross-entropy, chunked over the sequence so the full
    (B, N, vocab) logits tensor never materializes (decisive for
    vocab=262k × 1M tokens)."""
    hidden, aux = forward(params, cfg, batch, training=True)
    labels = batch["labels"]
    if hidden.shape[1] != labels.shape[1]:      # vlm stub prefix
        hidden = hidden[:, hidden.shape[1] - labels.shape[1]:]
    B, N, _ = hidden.shape
    chunk = cfg.logits_chunk or max(min(N, (128 * 1024 * 1024)
                                        // max(cfg.vocab, 1)), 1)
    chunk = min(chunk, N)
    while N % chunk:
        chunk -= 1
    nc = N // chunk

    def xent(h, y):
        lg = logits_from_hidden(params, cfg, h)
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, y[..., None], axis=-1)[..., 0]
        return jnp.sum(lse - gold)

    if nc <= 1:
        total = xent(hidden, labels)
    else:
        hs = hidden.reshape(B, nc, chunk, -1).transpose(1, 0, 2, 3)
        ys = labels.reshape(B, nc, chunk).transpose(1, 0, 2)

        def body(acc, hy):
            h, y = hy
            return acc + xent(h, y), None

        total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hs, ys))
    return total / (B * N) + aux


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def init_decode_state(cfg: ModelConfig, batch: int, cache_len: int,
                      cache_kind: str = "taylor", dtype=jnp.bfloat16,
                      per_slot: bool = False):
    """Cache pytree mirroring the params' group/remainder structure.

    ``per_slot=True`` builds a continuous-batching slot pool: every batch
    row ("slot") carries its own position counter (``pos``/TaylorState
    ``n`` get shape (batch,)), so sequences at different context lengths
    decode in one fixed-shape batch. Slots are populated / recycled with
    :func:`cache_scatter_slot` / :func:`cache_reset_slot`.
    """
    pattern, n_groups, rem = _pattern_layout(cfg)
    if per_slot and cfg.family == "encdec":
        raise NotImplementedError("per-slot pools: decoder-only families")
    if cfg.family == "encdec":
        blk = A.init_cache(cfg, batch, kind="global", cache_len=cache_len,
                           cache_kind=cache_kind, dtype=dtype)
        self_caches = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_groups, *a.shape)), {"self": blk})
        d = cfg.dim_head
        cross = T.TaylorState(
            s2=jnp.zeros((n_groups, batch, cfg.kv_heads, 1, d * d, d + 1),
                         jnp.float32),
            s1=jnp.zeros((n_groups, batch, cfg.kv_heads, 1, d, d + 1),
                         jnp.float32),
            s0=jnp.zeros((n_groups, batch, cfg.kv_heads, 1, 1, d + 1),
                         jnp.float32),
            n=jnp.zeros((n_groups,), jnp.int32),
        )
        return {"groups": [self_caches], "rem": [], "cross": cross,
                "pos": jnp.zeros((), jnp.int32)}

    def stack(kind):
        one = _cache_kind_for(kind, cfg, cache_kind, batch, cache_len, dtype,
                              per_slot)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (n_groups, *a.shape)).copy(), one)

    groups = [stack(kind) for kind in pattern] if n_groups else []
    remc = [_cache_kind_for(kind, cfg, cache_kind, batch, cache_len, dtype,
                            per_slot)
            for kind in rem]
    pos = jnp.zeros((batch,) if per_slot else (), jnp.int32)
    return {"groups": groups, "rem": remc, "pos": pos}


def encode_for_decode(params, cfg: ModelConfig, frames, cache):
    """encdec: run the encoder once, fold K/V into per-layer Taylor states."""
    enc_out = _encode(params, cfg, frames)

    def per_layer(bp):
        k, v = A.project_cross_kv(bp["cross"], cfg, enc_out)
        return T.taylor_encode_state(k[:, :, None], v[:, :, None],
                                     normalize_inputs=cfg.taylor.normalize_inputs)

    cross = jax.vmap(per_layer)(params["groups"][0])
    return {**cache, "cross": cross}


def decode_step(params, cfg: ModelConfig, batch, cache):
    """One token for every sequence in the batch.

    batch: {"tokens": (B, 1)}. Returns (logits (B,1,V), new cache).
    """
    _, norm = _norm(cfg)
    x = _embed_tokens(params, cfg, batch["tokens"])
    if cfg.pos_embed == "learned":
        p = cache["pos"]
        x = L.add_learned_pos(params["pos"], x,
                              p[None] if p.ndim == 0 else p[:, None])
    pattern, n_groups, rem = _pattern_layout(cfg)
    shared = params.get("shared_attn")
    is_encdec = cfg.family == "encdec"
    eff_pattern = ("global",) if is_encdec else pattern

    new_groups = []
    if n_groups:
        if is_encdec:
            def body(x, sliced):
                bp, bc, cs = sliced
                x, nc = _block_decode("global", bp, cfg, x, bc, shared=None,
                                      cross_state=cs)
                return x, (nc,)

            x, (ncache,) = jax.lax.scan(
                body, x,
                (params["groups"][0], cache["groups"][0], cache["cross"]))
            new_groups.append(ncache)
        else:
            # One scan over groups; the body applies every pattern position
            # in order so the layer interleaving matches forward().
            def body(x, sliced):
                new_caches = []
                for kind, bp, bc in zip(eff_pattern, sliced[0], sliced[1]):
                    x, nc = _block_decode(kind, bp, cfg, x, bc, shared=shared)
                    new_caches.append(nc)
                return x, tuple(new_caches)

            x, ncaches = jax.lax.scan(
                body, x, (tuple(params["groups"]), tuple(cache["groups"])))
            new_groups = list(ncaches)

    new_rem = []
    for kind, bp, bc in zip(rem, params["rem"], cache["rem"]):
        x, nc = _block_decode(kind, bp, cfg, x, bc, shared=shared)
        new_rem.append(nc)

    x = norm(params["final_norm"], x)
    lg = logits_from_hidden(params, cfg, x)
    out = {"groups": new_groups, "rem": new_rem, "pos": cache["pos"] + 1}
    if is_encdec:
        out["cross"] = cache["cross"]
    return lg, out


# ---------------------------------------------------------------------------
# Chunked prefill — the serving prefill path (repro.serve)
# ---------------------------------------------------------------------------

def _chunk_apply(params, cfg: ModelConfig, batch, cache, block_fn,
                 what: str):
    """Shared teacher-forced forward over a (B, C) token block that
    consumes and returns a decode cache — the body of both
    :func:`prefill_chunk` and :func:`verify_chunk` (they differ only in
    which attention site each block runs). Position counters may be
    scalar (per-sequence) or per-slot (B,)."""
    if cfg.family == "encdec":
        raise NotImplementedError(f"{what}: decoder families only")
    _, norm = _norm(cfg)
    tokens = batch["tokens"]
    C = tokens.shape[1]
    x = _embed_tokens(params, cfg, tokens)
    if cfg.pos_embed == "learned":
        p = cache["pos"]
        step = jnp.arange(C)
        x = L.add_learned_pos(params["pos"], x,
                              p + step if p.ndim == 0 else p[:, None] + step)
    pattern, n_groups, rem = _pattern_layout(cfg)

    new_groups = []
    if n_groups:
        def body(x, sliced):
            new_caches = []
            for kind, bp, bc in zip(pattern, sliced[0], sliced[1]):
                x, nc = block_fn(kind, bp, cfg, x, bc)
                new_caches.append(nc)
            return x, tuple(new_caches)

        x, ncaches = jax.lax.scan(
            body, x, (tuple(params["groups"]), tuple(cache["groups"])))
        new_groups = list(ncaches)

    new_rem = []
    for kind, bp, bc in zip(rem, params["rem"], cache["rem"]):
        x, nc = block_fn(kind, bp, cfg, x, bc)
        new_rem.append(nc)

    x = norm(params["final_norm"], x)
    lg = logits_from_hidden(params, cfg, x)
    return lg, {"groups": new_groups, "rem": new_rem,
                "pos": cache["pos"] + C}


def prefill_chunk(params, cfg: ModelConfig, batch, cache):
    """Teacher-forced forward over a (B, C) prompt chunk that consumes
    and returns the decode cache — the state-handoff path that replaces
    looping :func:`decode_step` over prompt tokens.

    Each attention layer runs ``causal_taylorshift(initial_state=...,
    return_state=True)`` (or a masked cache attend for kv caches), so a
    prompt is absorbed chunk by chunk at full-sequence arithmetic
    intensity and the final state drops straight into the recurrent
    decode step. Cache must carry a scalar position (per-sequence
    prefill — ``attn_prefill`` enforces it, and its `site="prefill"`
    routing supports seq-parallel chunk scans); the serve engine
    scatters the result into its slot pool.

    Returns (logits (B, C, vocab), new_cache).
    """
    return _chunk_apply(params, cfg, batch, cache, _block_prefill,
                        "chunked prefill")


def prefill_from_state(params, cfg: ModelConfig, batch, cache):
    """Absorb a (B, C) token block into a decode cache seeded from a
    cached prefix snapshot — the shared-prefix serving entry
    (serve/prefix_cache.py), dispatching on the counter layout and
    thereby generalizing the per-slot verify path (:func:`verify_chunk`).

    A *scalar*-counter cache — a private resumed prefill, i.e. a
    ``PrefixCache`` entry taken as the initial state — runs the exact
    :func:`prefill_chunk` body, so a resumed stream computes the same
    float ops in the same order as a cold prefill over the same chunk
    plan: bit-identical logits and tokens. A *per-slot* ``(B,)``-counter
    cache — a cold pool slot seeded straight from a snapshot via
    :func:`cache_scatter_slot` then gathered, or the whole pool at once
    — runs the verify body, each row absorbing from its own position;
    this is the entry batched cross-slot prefix prefill builds on.

    Returns (logits (B, C, vocab), new_cache).
    """
    scalar = cache["pos"].ndim == 0
    return _chunk_apply(params, cfg, batch, cache,
                        _block_prefill if scalar else _block_verify,
                        "prefill-from-state")


# ---------------------------------------------------------------------------
# Speculative verify — score k drafted tokens per slot (repro.spec)
# ---------------------------------------------------------------------------

def verify_chunk(params, cfg: ModelConfig, batch, cache):
    """Teacher-forced forward over a (B, C) token block that consumes and
    returns a *per-slot* decode cache — the speculative-verification path
    (src/repro/spec/).

    Where :func:`prefill_chunk` continues one sequence (scalar position),
    verify continues every slot of a continuous-batching pool at once:
    B = slots, C = speculate_k + 1, and ``cache["pos"]`` / TaylorState
    ``n`` are (B,) so each row attends from its own context length. The
    same function also serves the rollback re-absorb on a gathered
    batch-1 slot. Causality holds within the block, so ``logits[:, i]``
    is exactly the next-token distribution after absorbing tokens
    ``[0..i]`` — what greedy verification compares drafts against.

    Returns (logits (B, C, vocab), new_cache) with every slot advanced
    by C tokens; the caller snapshots/restores slots whose drafts are
    rejected (serve/pool.py: ``StatePool.snapshot/restore``).
    """
    return _chunk_apply(params, cfg, batch, cache, _block_verify,
                        "speculative verify")


def verify_rollback(params, cfg: ModelConfig, cache, snap, slot, batch):
    """Fused rejected-draft rollback: restore ``slot`` from the
    pre-verify pool snapshot ``snap`` and advance it by the accepted
    prefix ``batch["tokens"]`` (1, a+1), all in one traceable call —
    gather-from-snapshot, :func:`verify_chunk` re-absorb, scatter into
    ``cache``. ``slot`` may be traced (no retrace per slot); only the
    accepted-prefix length changes the shape (≤ speculate_k variants).

    ``snap`` is simply a reference to the pool pytree from before the
    verify call — jax arrays are immutable, so holding the old cache IS
    a bit-exact snapshot of every slot at zero copy cost.
    """
    sub = cache_gather_slot(snap, slot)
    _, sub = verify_chunk(params, cfg, batch, sub)
    return cache_scatter_slot(cache, sub, slot)


# ---------------------------------------------------------------------------
# Slot-indexed cache pools (continuous batching, repro.serve)
# ---------------------------------------------------------------------------
#
# A pool is an ``init_decode_state(..., per_slot=True)`` cache over
# ``slots`` batch rows. Group-stacked leaves carry layers on axis 0 and
# the slot on axis 1; remainder leaves and the position counters carry
# the slot on axis 0. Counter leaves (``pos``, TaylorState ``n``) have
# one fewer dim in a per-sequence cache than in the pool — the update
# helpers expand them on the slot axis.

def _slot_tree_update(pool_leaf, src_leaf, slot, axis: int):
    if src_leaf.ndim < pool_leaf.ndim:          # scalar counters
        src_leaf = jnp.expand_dims(src_leaf, axis)
    return jax.lax.dynamic_update_slice_in_dim(
        pool_leaf, src_leaf.astype(pool_leaf.dtype), slot, axis)


def cache_gather_slot(cache, slot):
    """Slice one slot out of a pool (slot dims kept, size 1)."""
    g = lambda axis: lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis)
    return {
        "groups": [jax.tree.map(g(1), gr) for gr in cache["groups"]],
        "rem": [jax.tree.map(g(0), r) for r in cache["rem"]],
        "pos": jax.lax.dynamic_slice_in_dim(cache["pos"], slot, 1, 0),
    }


def cache_scatter_slot(cache, src, slot):
    """Write a single-sequence cache (batch=1, scalar or size-1 counters
    — e.g. a finished :func:`prefill_chunk` state) into pool slot
    ``slot``. Overwrites every leaf of the slot, so a recycled slot
    carries no trace of its previous occupant."""
    u = lambda axis: (lambda p, s: _slot_tree_update(p, s, slot, axis))
    return {
        "groups": [jax.tree.map(u(1), gr, sr)
                   for gr, sr in zip(cache["groups"], src["groups"])],
        "rem": [jax.tree.map(u(0), r, s)
                for r, s in zip(cache["rem"], src["rem"])],
        "pos": _slot_tree_update(cache["pos"], src["pos"], slot, 0),
    }


def cache_reset_slot(cache, slot):
    """Zero every leaf of one slot (sequence released)."""
    sub = cache_gather_slot(cache, slot)
    return cache_scatter_slot(cache, jax.tree.map(jnp.zeros_like, sub), slot)


def cache_merge_slots(mask, new, old):
    """Per-slot select between two pool caches: slot i takes ``new``
    where ``mask[i]`` and keeps ``old`` otherwise — the write-back of a
    batched pool-level prefill, protecting decoding slots whose rows
    computed on throwaway tokens. ``mask``: (slots,) bool."""
    def sel(axis):
        def f(n, o):
            m = mask.reshape((1,) * axis + (-1,)
                             + (1,) * (n.ndim - axis - 1))
            return jnp.where(m, n, o)
        return f
    return {
        "groups": [jax.tree.map(sel(1), gn, go)
                   for gn, go in zip(new["groups"], old["groups"])],
        "rem": [jax.tree.map(sel(0), rn, ro)
                for rn, ro in zip(new["rem"], old["rem"])],
        "pos": jnp.where(mask, new["pos"], old["pos"]),
    }


def _map_counters(tree, fn):
    """Apply ``fn`` to every position-counter leaf of a decode cache:
    ``pos`` dict entries (the top-level counter and each kv layer's) and
    TaylorState ``n``. Non-counter leaves pass through untouched."""
    if isinstance(tree, T.TaylorState):
        return tree._replace(n=fn(tree.n))
    if isinstance(tree, dict):
        return {k: (fn(v) if k == "pos" else _map_counters(v, fn))
                for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return type(tree)(_map_counters(x, fn) for x in tree)
    return tree


def cache_slot_to_sequence(sub):
    """Normalize a :func:`cache_gather_slot` result (size-1 slot dims,
    per-slot counters) to the canonical single-sequence layout a private
    :func:`prefill_chunk` produces — scalar/(layers,) counters. Needed
    when a pool-resident prefill boundary becomes a prefix-cache entry:
    entries must be layout-identical whichever path built them, so a
    later hit resumes through the scalar-counter (bit-exact prefill)
    body."""
    return _map_counters(sub, lambda a: jnp.squeeze(a, -1))


def cache_truncate(cache, n_tokens: int):
    """Clamp every position counter of a kv decode cache to
    ``n_tokens`` — the partial-prefix reuse primitive. kv rows are
    positionally addressed and the cache attends with an exact-zero
    mask at ``index >= pos``, so rows beyond the clamped counter are
    unobservable: resuming prefill from the truncated cache is
    bit-identical to a cold prefill of the matching ``n_tokens``-token
    prefix. Taylor states are running sums, not positional rows — they
    cannot be truncated (callers gate on ``cache_kind == "kv"``;
    TaylorState leaves here raise)."""
    for leaf in jax.tree.leaves(cache, is_leaf=lambda x: isinstance(
            x, T.TaylorState)):
        if isinstance(leaf, T.TaylorState):
            raise ValueError("cache_truncate: Taylor states are prefix "
                             "sums, not positional rows — kv caches only")
    return _map_counters(cache, lambda a: jnp.minimum(a, n_tokens))


def prefill_slots(params, cfg: ModelConfig, batch, cache, slot_mask):
    """Batched pool-level prefill: absorb a (slots, C) token block
    directly into the slot pool, advancing only the slots ``slot_mask``
    selects. One dispatch covers every same-chunk-length prefilling
    sequence; unselected slots (decoding, free) compute on throwaway
    tokens and are restored bit-exactly by :func:`cache_merge_slots` —
    the same fixed-shape discipline as the batched decode step.

    The per-slot-counter body this runs (:func:`verify_chunk`'s) is
    bit-identical to the scalar prefill body for Taylor caches — rows
    are computationally independent, so batching cannot change a row's
    float ops — which is what keeps pooled prefill streams equal to
    per-sequence ones token for token. (kv caches attend over a
    different extent per body and are NOT bit-identical across the two;
    the engine keeps them on the per-sequence path.)

    Returns (logits (slots, C, vocab), merged pool cache).
    """
    logits, new = prefill_from_state(params, cfg, batch, cache)
    return logits, cache_merge_slots(slot_mask, new, cache)


# ---------------------------------------------------------------------------
# Analytic parameter counts (for MODEL_FLOPS = 6·N·D)
# ---------------------------------------------------------------------------

def count_params(params) -> int:
    return sum(int(jnp.size(x)) for x in jax.tree.leaves(params)
               if hasattr(x, "size"))


def count_params_analytic(cfg: ModelConfig, active_only: bool = False) -> int:
    """Exact count via eval_shape (no allocation); MoE optionally counted
    at top_k/n_experts activation."""
    shapes = jax.eval_shape(
        functools.partial(init_params, cfg),
        jax.random.PRNGKey(0))
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        n = 1
        for s in leaf.shape:
            n *= s
        keys = "/".join(str(p) for p in path)
        if active_only and ("w_up" in keys or "w_gate" in keys
                            or "w_down" in keys):
            n = n * cfg.moe.top_k // max(cfg.moe.n_experts, 1)
        total += n
    return total


def count_embedding_params(cfg: ModelConfig) -> int:
    n = cfg.vocab * cfg.d_model
    if not cfg.tie_embeddings:
        n *= 2
    return n
