"""Composed 3D-parallel training: FSDP × pipeline × sequence scan on
one ``(data, pipe, seq)`` mesh.

The paper's headline is linear *memory* for full token-to-token
attention; PR 2 proved it per-kernel, PR 3 per-scan-shard. This module
composes the three proven parts into one measured training path:

  * ``seq``  — the causal Taylor chunk scan runs per sequence shard with
    the log-depth boundary state exchange (`seqscan.make_axis_seq_scan`,
    same impls as the standalone sequence-parallel path), so the
    TaylorState crosses seq shards *at every pipeline stage*; the
    non-causal form psums its O(d³) key-side sums instead
    (`taylor.efficient_taylorshift_sharded`).
  * ``pipe`` — a GPipe microbatch ring over stage-stacked layer
    parameters, written with `lax.scan` over T = M + S - 1 ticks (the
    scan is reverse-differentiable where `fori_loop` is not) and
    `ppermute` rotation.
  * ``data`` — batch parallelism, plus ZeRO-3-style FSDP: weight
    matrices rest sharded over ``data`` and are all-gathered
    just-in-time inside the step; the gather's transpose is the gradient
    reduce-scatter, so data-axis gradient reduction costs nothing extra.

Everything lives in ONE fully-manual `shard_map` region
(``check_vma=False``) with `jax.value_and_grad` *inside* the body.
Rationale: nesting the existing mesh-level shard_map wrappers
(`seqscan.make_seq_scan`, `pipeline.pipeline_forward`) is impossible
(shard_map does not nest), and `auto` mode next to manual axes trips an
XLA SPMD-partitioner check on this jax version (see seqscan._wrap). The
collective transposes this relies on — psum ↔ psum of cotangents,
ppermute ↔ inverse ppermute, all_gather(tiled) ↔ psum_scatter — are the
true adjoints on this jax version (verified by the parity tests in
tests/test_composed_parallel.py at ≤1e-4 against single-device grads).

Gradient bookkeeping (grad-of-local-loss + explicit psums): the body
differentiates the *local* scalar loss. Because reverse-mode seeds every
shard's own scalar with 1 and the transposed collectives mix cotangents
across shards, each shard ends holding ∂(Σ_shards local_loss)/∂(its
param copy). The logical gradient of a leaf is then the psum of those
partials over exactly the axes the leaf is *replicated* on:

  * outer leaves (embed/pos/final_norm/unembed): psum over all three
    axes (the loss head is computed redundantly per pipe shard at weight
    1/S, so the head contributions sum back to 1× — while the embedding
    path, masked to the injecting stage, contributes once);
  * stage leaves: psum over ``seq`` (+ ``data`` for non-FSDP leaves;
    FSDP leaves already got their data-sum from the gather transpose).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import taylor as T
from repro.distributed import seqscan
from repro.distributed import sharding as S
from repro.models import attention as A
from repro.models import backend as B
from repro.models import layers as L
from repro.optim.optimizers import make_optimizer


# ---------------------------------------------------------------------------
# Parameter layout: {"outer", "stages"} ⟷ models.model.init_params
# ---------------------------------------------------------------------------

def check_composed_config(cfg, n_stages: int) -> None:
    """The composed path needs a uniform stacked decoder: one repeating
    'global' block so layers split evenly into S stages of L each."""
    pattern = tuple(cfg.layer_pattern)
    if pattern != ("global",):
        raise ValueError(
            f"composed path needs layer_pattern=('global',), got {pattern}")
    if cfg.n_layers % n_stages:
        raise ValueError(
            f"n_layers={cfg.n_layers} not divisible by {n_stages} stages")
    if getattr(cfg, "family", "decoder") == "encdec":
        raise ValueError("composed path does not support encdec")


def split_params(cfg, params, n_stages: int):
    """init_params tree -> {"outer": head/embed leaves,
    "stages": block leaves reshaped (S, L, ...)}."""
    check_composed_config(cfg, n_stages)
    if params.get("rem"):
        raise ValueError("composed path requires a fully-stacked layout "
                         "(no remainder blocks)")
    L_per = cfg.n_layers // n_stages
    stages = jax.tree.map(
        lambda a: a.reshape(n_stages, L_per, *a.shape[1:]),
        params["groups"][0])
    outer = {k: v for k, v in params.items() if k not in ("groups", "rem")}
    return {"outer": outer, "stages": stages}


def merge_params(split):
    """Inverse of :func:`split_params` (grads map back the same way)."""
    blocks = jax.tree.map(
        lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:]),
        split["stages"])
    out = dict(split["outer"])
    out["groups"] = [blocks]
    out["rem"] = []
    return out


def _fsdp_dims(split, mesh, fsdp: bool):
    """Int tree matching stages: the data-sharded dim per leaf, -1 = none."""
    data = mesh.shape["data"]
    return jax.tree.map(
        lambda a: S.composed_fsdp_dim(tuple(a.shape), data) if fsdp else -1,
        split["stages"])


def _split_shapes_thunk(cfg, n_stages: int):
    from repro.models import model as M

    def thunk():
        return split_params(
            cfg, M.init_params(cfg, jax.random.PRNGKey(0)), n_stages)

    return thunk


# ---------------------------------------------------------------------------
# The composed loss+grad step (one shard_map over the whole mesh)
# ---------------------------------------------------------------------------

def build_composed_grad_fn(cfg, mesh, *, global_batch: int, seq_len: int,
                           n_microbatches: int, fsdp: bool = False):
    """Returns ``(grad_fn, specs)`` where ``grad_fn(split_params, batch)
    -> (loss, grads_split)`` runs the full composed step and ``specs``
    is the PartitionSpec tree for the split params (grads share it).

    batch: {"tokens","labels"} of (global_batch, seq_len) int32, laid
    out P("data","seq") — data/pipeline.py's device_put_batch does this.
    """
    Dd = mesh.shape["data"]
    Sp = mesh.shape["pipe"]
    Sq = mesh.shape["seq"]
    check_composed_config(cfg, Sp)
    if global_batch % (Dd * n_microbatches):
        raise ValueError(
            f"global_batch={global_batch} must divide by data axis {Dd} × "
            f"microbatches {n_microbatches} (remainders: size the batch "
            f"explicitly; see pipeline.pipeline_forward's remainder "
            f"policy for the standalone path)")
    if seq_len % Sq:
        raise ValueError(f"seq_len={seq_len} not divisible by seq={Sq}")
    N_loc = seq_len // Sq
    B_loc = global_batch // Dd
    mb_rows = B_loc // n_microbatches
    M = n_microbatches
    d_model = cfg.d_model
    _, norm = L.make_norm(cfg.norm)
    tc = cfg.taylor

    sel = B.select_composed_scan(cfg, N=seq_len, d=cfg.dim_head,
                                 causal=cfg.causal, mesh=mesh)
    if cfg.causal:
        chunk = sel.chunk
        if N_loc % chunk:
            raise ValueError(f"chunk {chunk} does not divide local seq "
                             f"{N_loc}")
        scan_fn = (seqscan.make_axis_seq_scan("seq", Sq)
                   if sel.scan == "seq-parallel" else None)

    def _attn(p_attn, x, positions, n_prev):
        q, k, v = A._project_qkv(p_attn, cfg, x, positions)
        qg = A._group_q(q, cfg.kv_heads)
        kg, vg = k[:, :, None], v[:, :, None]
        tau = A._tau(p_attn, cfg, True)
        if cfg.causal:
            init = T.TaylorState.zeros((), q.shape[-1])._replace(n=n_prev)
            y = T.causal_taylorshift(
                qg, kg, vg, tau=tau, chunk=chunk,
                normalize_inputs=tc.normalize_inputs,
                output_scale=tc.output_scale,
                initial_state=init, scan_fn=scan_fn,
                scan_impl="sequential")
        else:
            y = T.efficient_taylorshift_sharded(
                qg, kg, vg, tau=tau,
                axis_name="seq" if Sq > 1 else None, n_global=seq_len,
                normalize_inputs=tc.normalize_inputs,
                output_scale=tc.output_scale)
        y = y.reshape(q.shape)
        return L.dense(p_attn["wo"], A._merge_heads(y).astype(x.dtype))

    def _block(p, x, positions, n_prev):
        z = norm(p["norm1"], x)
        h = _attn(p["attn"], z, positions, n_prev)
        if cfg.post_norm:
            h = norm(p["norm1_post"], h)
        x = x + h
        if cfg.d_ff:
            z = norm(p["norm2"], x)
            h = L.mlp(p["mlp"], z, act=cfg.act)
            if cfg.post_norm:
                h = norm(p["norm2_post"], h)
            x = x + h
        return x

    def _stage_fn(p_stage, h, positions, n_prev):
        def body(x, bp):
            return _block(bp, x, positions, n_prev), None

        body_fn = jax.checkpoint(body) if cfg.remat else body
        h, _ = jax.lax.scan(body_fn, h, p_stage)
        return h

    # static per-leaf FSDP dims (python ints, closed over — the psum-axis
    # choice below must be resolved at trace time)
    split_shapes = jax.eval_shape(_split_shapes_thunk(cfg, Sp))
    dims = _fsdp_dims(split_shapes, mesh, fsdp)
    specs = S.composed_param_specs(split_shapes, mesh, fsdp=fsdp)

    def body(outer, stages, batch):
        r_seq = jax.lax.axis_index("seq")
        stage_idx = jax.lax.axis_index("pipe")
        tokens, labels = batch["tokens"], batch["labels"]
        positions = r_seq * N_loc + jnp.arange(N_loc)
        n_prev = r_seq * N_loc

        def f(outer, stages):
            # FSDP: reconstruct the full local stage slice; the gather's
            # transpose reduce-scatters the gradient over `data`.
            full = jax.tree.map(
                lambda a, dim: (jax.lax.all_gather(a, "data", axis=dim,
                                                   tiled=True)
                                if dim >= 0 else a),
                stages, dims)
            p_local = jax.tree.map(lambda a: a[0], full)

            x = L.embed(outer["embed"], tokens) * jnp.asarray(
                jnp.sqrt(d_model), cfg.param_dtype)
            if cfg.pos_embed == "learned":
                x = L.add_learned_pos(outer["pos"], x, positions)
            mb = x.reshape(M, mb_rows, N_loc, d_model)

            def tick(buf, t):
                inj = jax.lax.dynamic_index_in_dim(
                    mb, jnp.minimum(t, M - 1), 0, keepdims=False)
                buf = jnp.where((stage_idx == 0) & (t < M), inj, buf)
                buf = _stage_fn(p_local, buf, positions, n_prev)
                y = jnp.where((stage_idx == Sp - 1) & (t >= Sp - 1),
                              buf, jnp.zeros_like(buf))
                buf = jax.lax.ppermute(
                    buf, "pipe", [(i, (i + 1) % Sp) for i in range(Sp)])
                return buf, y

            buf0 = jnp.zeros((mb_rows, N_loc, d_model), mb.dtype)
            _, ys = jax.lax.scan(tick, buf0, jnp.arange(M + Sp - 1))
            # only the last stage emitted non-zeros; replicate over pipe
            outs = jax.lax.psum(ys[Sp - 1:], "pipe")
            hidden = norm(outer["final_norm"],
                          outs.reshape(B_loc, N_loc, d_model))

            # loss head, computed redundantly on each pipe shard at
            # weight 1/S so Σ_shards local_loss == the global mean loss
            if cfg.tie_embeddings:
                lg = L.unembed(outer["embed"], hidden)
            else:
                lg = L.dense(outer["unembed"], hidden).astype(jnp.float32)
            if cfg.softcap_final:
                lg = L.softcap(lg, cfg.softcap_final)
            lse = jax.nn.logsumexp(lg, axis=-1)
            gold = jnp.take_along_axis(lg, labels[..., None],
                                       axis=-1)[..., 0]
            total = jnp.sum(lse - gold)
            return total / (global_batch * seq_len * Sp)

        loss_local, (g_outer, g_stages) = jax.value_and_grad(
            f, argnums=(0, 1))(outer, stages)
        loss = jax.lax.psum(loss_local, ("data", "pipe", "seq"))
        g_outer = jax.tree.map(
            lambda g: jax.lax.psum(g, ("data", "pipe", "seq")), g_outer)
        g_stages = jax.tree.map(
            lambda g, dim: jax.lax.psum(
                g, ("seq",) if dim >= 0 else ("data", "seq")),
            g_stages, dims)
        return loss, g_outer, g_stages

    batch_specs = {"tokens": P("data", "seq"), "labels": P("data", "seq")}
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(specs["outer"], specs["stages"], batch_specs),
        out_specs=(P(), specs["outer"], specs["stages"]),
        check_vma=False)

    def grad_fn(split, batch):
        loss, g_outer, g_stages = fn(split["outer"], split["stages"], batch)
        return loss, {"outer": g_outer, "stages": g_stages}

    return grad_fn, specs


# ---------------------------------------------------------------------------
# Full train step (grad + optimizer), jitted over the composed mesh
# ---------------------------------------------------------------------------

def composed_param_shardings(split, mesh, *, fsdp: bool = False):
    specs = S.composed_param_specs(split, mesh, fsdp=fsdp)
    return jax.tree.map(lambda sp: NamedSharding(mesh, sp), specs,
                        is_leaf=lambda x: isinstance(x, P))


def composed_opt_shardings(opt_state, pshard, mesh):
    """Moments (and master copies) shard like their params; step scalar
    is replicated."""
    rep = NamedSharding(mesh, P())
    out = {"step": rep}
    for k in opt_state:
        if k != "step":
            out[k] = pshard
    return out


def build_composed_train_step(cfg, opt_cfg, mesh, *, global_batch: int,
                              seq_len: int, n_microbatches: int,
                              fsdp: bool = False):
    """Returns ``(init_fn, step_fn, shard_fn)``:

      * ``init_fn(rng) -> (params_split, opt_state)`` device-placed on
        the composed mesh;
      * ``step_fn(params, opt_state, batch) -> (params, opt_state,
        metrics)`` — jitted, donates params/opt_state;
      * ``shard_fn(params_split) -> shardings tree`` for checkpointing.
    """
    from repro.models import model as M

    grad_fn, specs = build_composed_grad_fn(
        cfg, mesh, global_batch=global_batch, seq_len=seq_len,
        n_microbatches=n_microbatches, fsdp=fsdp)
    init_opt, update = make_optimizer(opt_cfg)
    Sp = mesh.shape["pipe"]

    split_shapes = jax.eval_shape(_split_shapes_thunk(cfg, Sp))
    pshard = composed_param_shardings(split_shapes, mesh, fsdp=fsdp)
    oshard = composed_opt_shardings(
        jax.eval_shape(init_opt, split_shapes), pshard, mesh)

    def init_fn(rng):
        params = M.init_params(cfg, rng)
        split = jax.device_put(split_params(cfg, params, Sp), pshard)
        opt_state = jax.jit(init_opt, out_shardings=oshard)(split)
        return split, opt_state

    def step(params, opt_state, batch):
        loss, grads = grad_fn(params, batch)
        rng = jax.random.fold_in(jax.random.PRNGKey(17), opt_state["step"])
        params, opt_state, metrics = update(params, grads, opt_state,
                                            rng=rng)
        metrics = dict(metrics)
        metrics["loss"] = loss
        return params, opt_state, metrics

    step_fn = jax.jit(step, in_shardings=(pshard, oshard, None),
                      out_shardings=(pshard, oshard, None),
                      donate_argnums=(0, 1))

    def shard_fn(split):
        return composed_param_shardings(split, mesh, fsdp=fsdp)

    return init_fn, step_fn, shard_fn


def measure_seq_exchange(mesh, *, d: int, heads: int = 1,
                         seq_axis: str = "seq", repeats: int = 3) -> dict:
    """One-shot probe of the seq-axis chunk-boundary state exchange.

    Times a jitted shard_map whose body performs the same communication
    pattern as the scan's boundary exchange (seqscan.py): a log-depth
    ``ppermute`` chain plus one final ``psum``, each hop moving one
    TaylorState-sized segment total — ``(d², d+1) + (d, d+1) + (1,
    d+1)`` floats per head, independent of sequence length. Runs once
    at trainer startup (never inside the step), so the published
    ``train_seq_exchange_*`` gauges cost nothing on the training path.

    Returns ``{"seconds", "bytes_per_device", "rounds"}``; bytes are
    the analytic per-device wire total (state bytes × (rounds + 1)).
    """
    import time as _time

    S_seq = int(mesh.shape[seq_axis]) if seq_axis in mesh.shape else 1
    if S_seq <= 1:
        return {"seconds": 0.0, "bytes_per_device": 0, "rounds": 0}
    rounds = int(math.ceil(math.log2(S_seq)))
    state = (jnp.zeros((heads, d * d, d + 1), jnp.float32),
             jnp.zeros((heads, d, d + 1), jnp.float32),
             jnp.zeros((heads, 1, d + 1), jnp.float32))

    def body(s2, s1, s0):
        st = (s2, s1, s0)
        hop = 1
        while hop < S_seq:
            perm = [(i, (i + hop) % S_seq) for i in range(S_seq)]
            st = tuple(x + jax.lax.ppermute(x, seq_axis, perm)
                       for x in st)
            hop *= 2
        return tuple(jax.lax.psum(x, seq_axis) for x in st)

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(), P(), P()),
                              out_specs=(P(), P(), P()),
                              check_vma=False))
    jax.block_until_ready(f(*state))            # compile + warm
    t0 = _time.perf_counter()
    out = None
    for _ in range(repeats):
        out = f(*state)
    jax.block_until_ready(out)
    seconds = (_time.perf_counter() - t0) / repeats
    state_bytes = 4 * heads * (d * d + d + 1) * (d + 1)
    return {"seconds": seconds,
            "bytes_per_device": state_bytes * (rounds + 1),
            "rounds": rounds}
