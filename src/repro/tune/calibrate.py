"""Measure the real crossovers and kernel block shapes on this backend.

The analytic N0/N1 (core/taylor.py Eq. 7/9) count FLOPs and tensor
entries; a real backend adds constants the algebra cannot see — fusion
quality, cache hierarchy, dispatch overhead. ``calibrate`` runs the
``benchmarks/crossover.py``-style sweep directly against the reference
implementations and writes what it *measured*:

* **N0 (speed)**: ``direct_taylorshift`` vs ``efficient_taylorshift``
  timed (best-of-``reps``, blocked until ready) over a geometric N grid
  bracketing the analytic value; the empirical crossover is the
  geometric midpoint of the last direct-faster and first
  efficient-faster grid points. No sign change inside the grid leaves
  ``n0=None`` — the analytic value stays in charge for that d.
* **N1 (memory)**: compiled-executable temp-byte accounting
  (``.memory_analysis()``) where the backend reports it, bisected the
  same way; backends that report nothing fall back to the Eq. (8)
  entries model evaluated at real dtype widths (``source`` records
  which).
* **block shapes**: the fused Pallas kernels timed over a candidate
  ``(block_q, block_k)`` grid (interpret mode on the CPU), best wall time
  wins.

A recorded decision log (PR 6 ``--decision-log`` JSONL) seeds the sweep:
``divergent_dims`` extracts the (d, site) cells where the recorded
choice sat on the wrong side of the analytic N0 — exactly the cells
worth measuring first.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core import taylor as T
from repro.tune.table import TuneEntry, TuningTable


def _time_best(fn, *args, reps: int = 3) -> float:
    """Best-of-``reps`` wall seconds; compiles on the warmup call."""
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _n_grid(d: int, *, quick: bool) -> list[int]:
    """Geometric N grid bracketing the analytic N0 (multiples of 8)."""
    n0 = T.crossover_n0(d)
    factors = (0.5, 1.0, 2.0) if quick else (0.25, 0.5, 0.71, 1.0,
                                             1.41, 2.0, 4.0)
    return sorted({max(8, int(round(n0 * f / 8)) * 8) for f in factors})


def _cross_from_sweep(ns: list[int], direct_wins: list[bool]
                      ) -> float | None:
    """Geometric midpoint of the last direct-win / first efficient-win
    pair; None when the grid never sees a sign change."""
    for i in range(len(ns) - 1):
        if direct_wins[i] and not direct_wins[i + 1]:
            return float((ns[i] * ns[i + 1]) ** 0.5)
    return None


def measure_n0(d: int, *, reps: int = 3, quick: bool = False,
               batch: int = 1) -> tuple[float | None, dict]:
    """Empirical speed crossover for head dim d (None = no crossing)."""
    key = jax.random.PRNGKey(0)
    direct = jax.jit(lambda q, k, v: T.direct_taylorshift(q, k, v))
    efficient = jax.jit(lambda q, k, v: T.efficient_taylorshift(q, k, v))
    ns, wins, cells = _n_grid(d, quick=quick), [], {}
    for n in ns:
        q, k, v = (jax.random.normal(jax.random.fold_in(key, i),
                                     (batch, n, d), jnp.float32)
                   for i in range(3))
        td = _time_best(direct, q, k, v, reps=reps)
        te = _time_best(efficient, q, k, v, reps=reps)
        wins.append(td <= te)
        cells[n] = {"direct_s": td, "efficient_s": te}
    return _cross_from_sweep(ns, wins), cells


def _temp_bytes(fn, *args) -> int | None:
    """Compiled temp allocation in bytes, when the backend reports it."""
    try:
        mem = jax.jit(fn).lower(*args).compile().memory_analysis()
        return int(mem.temp_size_in_bytes) if mem is not None else None
    except Exception:
        return None


def measure_n1(d: int, *, quick: bool = False,
               batch: int = 1) -> tuple[float | None, str]:
    """Empirical memory crossover; falls back to the entries model
    (Eq. 8 at fp32 widths) when the backend reports no temp bytes."""
    key = jax.random.PRNGKey(1)
    n0 = T.crossover_n1(d)
    factors = (0.5, 1.0, 2.0) if quick else (0.25, 0.5, 1.0, 2.0, 4.0)
    ns = sorted({max(8, int(round(n0 * f / 8)) * 8) for f in factors})
    wins, measured = [], True
    for n in ns:
        q, k, v = (jax.random.normal(jax.random.fold_in(key, i),
                                     (batch, n, d), jnp.float32)
                   for i in range(3))
        bd = _temp_bytes(lambda q, k, v: T.direct_taylorshift(q, k, v),
                         q, k, v)
        be = _temp_bytes(lambda q, k, v: T.efficient_taylorshift(q, k, v),
                         q, k, v)
        if bd is None or be is None or not (bd and be):
            measured = False
            break
        wins.append(bd <= be)
    if measured:
        cross = _cross_from_sweep(ns, wins)
        if cross is not None:
            return cross, "measured"
    # entries model at real widths — same crossover as Eq. (9), recorded
    # as modeled so the table is honest about its provenance
    wins = [T.entries_direct(n, d) <= T.entries_efficient(n, d) for n in ns]
    return _cross_from_sweep(ns, wins), "modeled"


BLOCK_CANDIDATES = ((64, 64), (128, 128), (64, 128), (128, 64))


def sweep_kernel_blocks(d: int, *, n: int = 256, reps: int = 3,
                        candidates=BLOCK_CANDIDATES,
                        quick: bool = False) -> tuple[int, int]:
    """Best (block_q, block_k) for the fused Pallas kernels at this d.

    Times ``taylor_direct_attention`` + ``taylor_efficient_attention``
    per candidate (interpret mode on the CPU, where the sweep still
    orders candidates by the work the grid shape implies)."""
    from repro.kernels.ops import interpret_mode
    from repro.kernels.taylor_direct import taylor_direct_attention
    from repro.kernels.taylor_efficient import taylor_efficient_attention

    interpret = interpret_mode()
    if quick:
        candidates = candidates[:2]
        n, reps = min(n, 128), 1
    key = jax.random.PRNGKey(2)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (2, n, d),
                                 jnp.float32) for i in range(3))
    best, best_t = candidates[0], float("inf")
    for bq, bk in candidates:
        if n % min(bq, n) or n % min(bk, n):
            continue
        # compiled, the efficient kernels hold a token block in lanes:
        # a multiple of 128, or all of N
        if not interpret and any(b < n and b % 128 for b in (bq, bk)):
            continue
        t = _time_best(
            lambda q, k, v: taylor_direct_attention(
                q, k, v, block_q=bq, block_k=bk, interpret=interpret),
            q, k, v, reps=reps)
        t += _time_best(
            lambda q, k, v: taylor_efficient_attention(
                q, k, v, block_q=bq, block_k=bk, interpret=interpret),
            q, k, v, reps=reps)
        if t < best_t:
            best, best_t = (bq, bk), t
    return best


def divergent_dims(records: list[dict]) -> set[int]:
    """Head dims whose recorded direct/efficient choice sat on the wrong
    side of the analytic N0 — the decision-log seed for calibration."""
    out = set()
    for r in records:
        if r.get("mode") in ("direct", "efficient") \
                and r.get("cache_kind") != "kv":
            predicted = ("direct" if r["N"] <= T.crossover_n0(r["d"])
                         else "efficient")
            if r["mode"] != predicted:
                out.add(int(r["d"]))
    return out


def calibrate(ds=(16, 32), *, reps: int = 3, quick: bool = False,
              blocks: bool = True, verbose: bool = False) -> TuningTable:
    """Run the full sweep and return the persisted-form table."""
    entries, meta_cells = [], {}
    for d in ds:
        n0, cells = measure_n0(d, reps=reps, quick=quick)
        n1, n1_source = measure_n1(d, quick=quick)
        bq = bk = None
        if blocks:
            bq, bk = sweep_kernel_blocks(d, reps=reps, quick=quick)
        source = "measured" if n1_source == "measured" else \
            "measured-n0-modeled-n1"
        if n0 is None and n1 is None and bq is None:
            continue          # nothing measured — leave analytic in charge
        entries.append(TuneEntry(d=d, n0=n0, n1=n1, block_q=bq,
                                 block_k=bk, source=source))
        meta_cells[str(d)] = cells
        if verbose:
            print(f"d={d}: measured N0={n0 and round(n0)} "
                  f"(analytic {T.crossover_n0(d):.0f}), "
                  f"N1={n1 and round(n1)} [{n1_source}] "
                  f"(analytic {T.crossover_n1(d):.0f}), "
                  f"blocks=({bq},{bk})")
    return TuningTable(backend=jax.default_backend(), entries=entries,
                       meta={"reps": reps, "quick": quick,
                             "cells": meta_cells})
