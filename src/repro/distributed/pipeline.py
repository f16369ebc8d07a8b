"""Pipeline parallelism: GPipe-style schedule over a stage-sharded mesh.

First-class PP option (docs/design.md §5): layers are partitioned into S
stages along a ``stage`` mesh axis; microbatches flow through stages
with `shard_map` + `ppermute` rotation. With M microbatches and S
stages the bubble fraction is (S-1)/(M+S-1) — the driver picks M ≥ 4·S.

This module is self-contained (used by tests and available to the
launcher via ``--pp``); the production dry-run table uses DP×TP(+EP)
which fits every assigned model at 256–512 chips, so PP here is
validated at feature level rather than swept over all 40 cells.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_pp_mesh(n_stages: int, data: int = 1):
    devs = jax.devices()
    assert len(devs) >= n_stages * data
    return jax.make_mesh((data, n_stages), ("data", "stage"),
                         devices=devs[:data * n_stages])


def pipeline_forward(stage_fn: Callable, params_stacked, x,
                     mesh: Mesh, *, n_microbatches: int,
                     remainder: str = "error"):
    """Run ``stage_fn(stage_params, h) -> h`` over S stages.

    params_stacked: pytree with leading dim S (stage-sharded).
    x: (B, ...) global batch.
    Returns y with the same shape as stage_fn's composition.

    ``remainder`` makes the ``B % n_microbatches != 0`` case an explicit
    policy instead of a shape accident:

      * ``"error"`` (default): raise — the caller sized the batch wrong;
      * ``"pad"``: zero-pad B up to the next multiple, run the padded
        schedule, slice the pad rows off the output (all B rows kept;
        costs up to one extra row per microbatch);
      * ``"drop"``: truncate to the largest multiple and return only the
        kept rows (output batch may be smaller than B — the caller
        owns loss re-weighting).

    GPipe schedule via shard_map: each device holds one stage; the
    activation ring rotates with ppermute. T = M + S - 1 ticks.
    """
    S = mesh.shape["stage"]
    M = n_microbatches
    B = x.shape[0]
    n_keep = B
    if B % M:
        if remainder == "error":
            raise ValueError(
                f"batch {B} not divisible by n_microbatches {M}; pass "
                f"remainder='pad' or 'drop' for an explicit policy")
        if remainder == "pad":
            pad = M - B % M
            x = jnp.concatenate(
                [x, jnp.zeros((pad, *x.shape[1:]), x.dtype)], axis=0)
        elif remainder == "drop":
            n_keep = (B // M) * M
            x = x[:n_keep]
        else:
            raise ValueError(f"unknown remainder policy {remainder!r}")
    mb = x.reshape(M, x.shape[0] // M, *x.shape[1:])

    def body(params, mb):
        # params: (1, ...) local stage slice; mb: (M, b, ...) replicated
        stage = jax.lax.axis_index("stage")
        p_local = jax.tree.map(lambda a: a[0], params)
        # check_vma=False: the ring carries no varying-axis annotations
        buf = jnp.zeros_like(mb[0])
        outs = jnp.zeros_like(mb)
        T = M + S - 1

        def tick(t, carry):
            buf, outs = carry
            # stage 0 injects microbatch t (if any)
            inject = jnp.where(t < M, t, M - 1)
            buf = jnp.where(stage == 0,
                            jnp.where(t < M, mb[inject], buf), buf)
            buf = stage_fn(p_local, buf)
            # last stage emits microbatch t-S+1
            emit = t - (S - 1)
            emit_c = jnp.clip(emit, 0, M - 1)
            outs = jnp.where(
                (stage == S - 1) & (emit >= 0),
                outs.at[emit_c].set(buf), outs)
            # rotate ring: stage i -> i+1
            buf = jax.lax.ppermute(
                buf, "stage", [(i, (i + 1) % S) for i in range(S)])
            return buf, outs

        buf, outs = jax.lax.fori_loop(0, T, tick, (buf, outs))
        # collect outputs from the last stage to all (psum of one-hot)
        outs = jax.lax.psum(
            jnp.where(stage == S - 1, outs, jnp.zeros_like(outs)), "stage")
        return outs

    shmap = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("stage"), P()),
        out_specs=P(),
        check_vma=False,
    )
    y = shmap(params_stacked, mb)
    return y.reshape(-1, *y.shape[2:])[:n_keep]


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)


def stage_occupancy(n_stages: int, n_microbatches: int) -> list[dict]:
    """Per-stage tick attribution of the GPipe schedule.

    The forward schedule runs ``T = M + S - 1`` ticks; stage ``s`` is
    busy exactly on ticks ``[s, s + M - 1]`` — ``s`` idle warmup ticks
    (waiting for the first microbatch to arrive) and ``S - 1 - s`` idle
    drain ticks (done while later stages finish). Deterministic, so the
    trainer publishes it as the per-stage bubble breakdown instead of
    timing inside the compiled scan.
    """
    ticks = n_microbatches + n_stages - 1
    return [{"stage": s, "warmup_idle": s, "busy": n_microbatches,
             "drain_idle": n_stages - 1 - s,
             "idle_fraction": (n_stages - 1) / ticks}
            for s in range(n_stages)]
