"""Sequence-parallel causal Taylor scan across the `seq` mesh axis.

The chunked causal scan's carry (`TaylorState` = S2/S1/S0 prefix sums)
composes associatively (`core.taylor.combine_states`), so the sequence
axis can be sharded: each shard runs the associative chunk scan over its
local chunks and the only cross-device traffic is a *chunk-boundary
state exchange* — a log-depth ppermute prefix (plus one psum for the
final state) over the shards' segment totals,
``(d², d+1) + (d, d+1) + (1, d+1)`` floats per head per hop, independent
of sequence length.

Layering (who owns what):

  * `core/taylor.py` owns the math: `_causal_scan_par_impl` /
    `_causal_scan_par_bwd_impl` take an ``axis_name`` and do the
    exchange with `all_gather` when given one.
  * this module owns the mesh: `shard_map` over the `seq` axis around
    those impls, with the custom VJP at the *global* level — forward
    and backward are each one shard_map call over non-differentiated
    bodies, so shard_map's autodiff/replication rules never enter the
    picture. Mesh axes other than `seq` are left in GSPMD `auto` mode,
    so batch/head/model sharding of the surrounding jit program passes
    straight through.

`make_seq_scan(mesh)` returns a drop-in for the ``scan_fn`` hook of
:func:`core.taylor.causal_taylorshift`; selection (when the mesh has a
`seq` axis, N divides, …) lives in `models/backend.py`.
"""

from __future__ import annotations

import functools

import jax
from jax.sharding import PartitionSpec as P

from repro.core import taylor as T


def _seq_spec(ndim: int, axis: str) -> P:
    """Chunk-major arrays (G, *lead, C, d): shard the chunk axis."""
    return P(axis, *([None] * (ndim - 1)))


def _wrap(mesh, axis, body, n_sharded_in, n_rep_in, n_sharded_out,
          n_rep_out, arrs):
    """shard_map ``body`` with the first ``n_sharded_in`` args sharded
    over ``axis`` on dim 0, the rest replicated (same split for
    outputs).

    Fully-manual mode over every mesh axis: dims not naming an axis are
    replicated across it inside the scan region. The batch/head dims
    *could* ride the data/model axes instead of replicating, but
    shard_map's `auto` mode (leave non-seq axes to GSPMD) trips an XLA
    SPMD-partitioner check in this jax version whenever an auto axis is
    non-trivial — revisit when the partitioner accepts manual subgroups
    next to auto axes. The jit wrapper makes the call traceable from
    eager callers; it is free when the caller is already jitted.
    """
    in_specs = tuple(_seq_spec(a.ndim, axis) for a in arrs[:n_sharded_in]) \
        + tuple(P() for _ in range(n_rep_in))
    out_specs = tuple([_seq_spec(arrs[0].ndim, axis)] * n_sharded_out
                      + [P()] * n_rep_out)
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _seq_scan(mesh, axis, qm, km, vm, s2_0, s1_0, s0_0):
    def body(qm, km, vm, s2_0, s1_0, s0_0):
        return T._causal_scan_par_impl(qm, km, vm, s2_0, s1_0, s0_0,
                                       axis_name=axis,
                                       axis_size=mesh.shape[axis])
    f = _wrap(mesh, axis, body, 3, 3, 1, 3, (qm, km, vm))
    return f(qm, km, vm, s2_0, s1_0, s0_0)


def _seq_scan_fwd(mesh, axis, qm, km, vm, s2_0, s1_0, s0_0):
    out = _seq_scan(mesh, axis, qm, km, vm, s2_0, s1_0, s0_0)
    return out, (qm, km, vm, s2_0, s1_0, s0_0)


def _seq_scan_bwd(mesh, axis, res, cot):
    qm, km, vm, s2_0, s1_0, s0_0 = res
    yb, dS2_f, dS1_f, dS0_f = cot

    def body(qm, km, vm, yb, s2_0, s1_0, s0_0, dS2_f, dS1_f, dS0_f):
        return T._causal_scan_par_bwd_impl(
            qm, km, vm, s2_0, s1_0, s0_0, yb, dS2_f, dS1_f, dS0_f,
            axis_name=axis, axis_size=mesh.shape[axis])

    f = _wrap(mesh, axis, body, 4, 6, 3, 3, (qm, km, vm, yb))
    return f(qm, km, vm, yb, s2_0, s1_0, s0_0, dS2_f, dS1_f, dS0_f)


_seq_scan.defvjp(_seq_scan_fwd, _seq_scan_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _axis_scan(axis, axis_size, qm, km, vm, s2_0, s1_0, s0_0):
    return T._causal_scan_par_impl(qm, km, vm, s2_0, s1_0, s0_0,
                                   axis_name=axis, axis_size=axis_size)


def _axis_scan_fwd(axis, axis_size, qm, km, vm, s2_0, s1_0, s0_0):
    out = _axis_scan(axis, axis_size, qm, km, vm, s2_0, s1_0, s0_0)
    return out, (qm, km, vm, s2_0, s1_0, s0_0)


def _axis_scan_bwd(axis, axis_size, res, cot):
    qm, km, vm, s2_0, s1_0, s0_0 = res
    yb, dS2_f, dS1_f, dS0_f = cot
    return T._causal_scan_par_bwd_impl(
        qm, km, vm, s2_0, s1_0, s0_0, yb, dS2_f, dS1_f, dS0_f,
        axis_name=axis, axis_size=axis_size)


_axis_scan.defvjp(_axis_scan_fwd, _axis_scan_bwd)


def make_axis_seq_scan(axis: str, axis_size: int):
    """A ``scan_fn`` for callers *already inside* a fully-manual
    shard_map region over ``axis`` — the composed 3D train step
    (distributed/composed.py), where the pipeline ring, FSDP gathers and
    this scan all live in one manual region and a nested shard_map is
    unavailable. Same boundary-exchange impls as :func:`make_seq_scan`,
    same recompute custom VJP, minus the mesh wrapper: the prefix/suffix
    state exchange runs over the ambient named axis, so Taylor-state
    continuity holds across seq shards at every pipeline stage."""
    def scan_fn(qm, km, vm, s2_0, s1_0, s0_0):
        return _axis_scan(axis, axis_size, qm, km, vm, s2_0, s1_0, s0_0)

    return scan_fn


def make_seq_scan(mesh, axis: str = "seq"):
    """A ``scan_fn`` for :func:`core.taylor.causal_taylorshift`: the
    chunk scan sharded over ``mesh``'s ``axis``. Requires the leading
    chunk count G to be divisible by the axis size (the selector in
    `models/backend.py` guarantees it, falling back to the sequential
    scan otherwise)."""
    size = mesh.shape[axis]

    def scan_fn(qm, km, vm, s2_0, s1_0, s0_0):
        if qm.shape[0] % size:
            raise ValueError(
                f"chunk count {qm.shape[0]} not divisible by seq axis "
                f"size {size}")
        return _seq_scan(mesh, axis, qm, km, vm, s2_0, s1_0, s0_0)

    return scan_fn
