"""Backward peak memory vs sequence length: the training-side version of
the paper's §4.2 memory claim.

Measures XLA's compiled temp-buffer allocation (``memory_analysis()`` of
the lowered grad function — buffer-assignment bytes, not a simulator)
for one attention-backward at growing N:

  * ``reference direct``  — jax.grad through the O(N²) jnp oracle: the
    backward keeps N×N score/cotangent buffers alive, so temp bytes grow
    quadratically;
  * ``efficient custom-VJP`` — the fused kernel path
    (kernels/taylor_grad.py): residuals are O(N·d), A_mod/KV̂ are
    recomputed, gradients stream through (cf·d, d+1) chunks — temp bytes
    grow linearly;
  * ``causal chunked custom-VJP`` — the two-scan recompute backward in
    core/taylor.py: O(N·d + d³).

Prints per-N temp bytes and the fitted log-log slope over the top half
of the sweep. The efficient/causal slopes must be sub-quadratic (~1);
the reference slope ~2 beyond the crossover.

``--composed`` runs the full-model version: the composed 3D-parallel
train step (distributed/composed.py) swept over N ∈ {4k, 16k, 64k} with
weak-scaling mesh shapes on 8 host devices — the sequence axis absorbs
the growth (4k→(2,2,2), 16k→(1,2,4), 64k→(1,1,8)), so per-device
activation bytes grow sub-linearly (slope ≤ 0.6) while the
direct-attention single-device baseline grows quadratically (~2.2,
measured compile-only — the O(N²) step never has to run). Measured step
time + tokens/s at every runnable size. ``--json PATH`` writes the
schema-checked BENCH_training.json document
(benchmarks.run.validate_training_doc; the CI train-parallel job
re-validates the committed file).

Run:  PYTHONPATH=src python -m benchmarks.train_step_memory [--fast]
      PYTHONPATH=src python -m benchmarks.train_step_memory \
          --composed --json BENCH_training.json
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

if __name__ == "__main__":
    _ap = argparse.ArgumentParser()
    _ap.add_argument("--fast", action="store_true")
    _ap.add_argument("--composed", action="store_true",
                     help="composed 3D train-step sweep (forces a "
                          "host-platform device mesh before jax loads)")
    _ap.add_argument("--devices", type=int, default=8)
    _ap.add_argument("--seq-lens", type=int, nargs="+",
                     default=[4096, 16384, 65536])
    _ap.add_argument("--global-batch", type=int, default=4)
    _ap.add_argument("--steps", type=int, default=2,
                     help="measured steps per composed cell")
    _ap.add_argument("--json", default="",
                     help="write the BENCH_training.json document here")
    ARGS = _ap.parse_args()
    if ARGS.composed:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={ARGS.devices} "
            + os.environ.get("XLA_FLAGS", ""))

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402

from repro.core import taylor as T                          # noqa: E402
from repro.kernels import ops                               # noqa: E402

from benchmarks.common import emit                          # noqa: E402


def _bwd_temp_bytes(loss_fn, *shapes) -> int:
    """Temp-buffer bytes of the compiled gradient of ``loss_fn``."""
    args = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
    compiled = jax.jit(jax.grad(loss_fn, argnums=(0, 1, 2))) \
        .lower(*args).compile()
    return int(compiled.memory_analysis().temp_size_in_bytes)


def _slope(ns, bys) -> float:
    """log-log slope over the top half of the sweep (asymptotic regime)."""
    pts = [(math.log(n), math.log(max(b, 1))) for n, b in zip(ns, bys)]
    pts = pts[len(pts) // 2 - 1:]
    n = len(pts)
    mx = sum(p[0] for p in pts) / n
    my = sum(p[1] for p in pts) / n
    num = sum((p[0] - mx) * (p[1] - my) for p in pts)
    den = sum((p[0] - mx) ** 2 for p in pts)
    return num / den


def run(d: int = 16, n_values=(128, 256, 512, 1024), heads: int = 2):
    interp = ops.interpret_mode()

    def loss_ref(q, k, v):
        return jnp.sum(T.direct_taylorshift(q, k, v) ** 2)

    def loss_eff(q, k, v):
        return jnp.sum(ops.taylor_attention_kernel(
            q, k, v, mode="efficient", interpret=interp) ** 2)

    def loss_causal(q, k, v):
        return jnp.sum(T.causal_taylorshift(q, k, v, chunk=64) ** 2)

    rows = {"ref_direct": [], "eff_vjp": [], "causal_vjp": []}
    for n in n_values:
        shape = (1, heads, n, d)
        b_ref = _bwd_temp_bytes(loss_ref, shape, shape, shape)
        b_eff = _bwd_temp_bytes(loss_eff, shape, shape, shape)
        b_cau = _bwd_temp_bytes(loss_causal, shape, shape, shape)
        rows["ref_direct"].append(b_ref)
        rows["eff_vjp"].append(b_eff)
        rows["causal_vjp"].append(b_cau)
        emit(f"bwd_temp_d{d}_n{n}", 0.0,
             f"ref_direct_B={b_ref};efficient_vjp_B={b_eff};"
             f"causal_vjp_B={b_cau}")

    slopes = {name: _slope(n_values, bys) for name, bys in rows.items()}
    for name, s in slopes.items():
        growth = ("quadratic" if s > 1.7
                  else "sub-quadratic" if s > 1.2 else "~linear")
        emit(f"bwd_temp_slope_{name}", 0.0,
             f"loglog_slope={s:.2f};growth={growth}")
    print(f"# backward peak-memory growth (temp bytes, d={d}): "
          f"reference direct slope {slopes['ref_direct']:.2f} vs "
          f"efficient custom-VJP slope {slopes['eff_vjp']:.2f} "
          f"(sub-quadratic: {slopes['eff_vjp'] < 1.7}), "
          f"causal custom-VJP slope {slopes['causal_vjp']:.2f}",
          flush=True)
    return slopes


# ---------------------------------------------------------------------------
# Composed 3D-parallel full-model sweep (BENCH_training.json)
# ---------------------------------------------------------------------------

# Weak scaling: the device pool grows with N (2 → 4 → 8) and each cell
# uses the measured-best layout for its device count — seq-dominant,
# because pipeline layouts cost 2.5–2.9× the temp bytes at equal device
# count (GPipe tick buffers; e.g. (1,2,4) at N=64k measured 1.66 GB vs
# 0.57 GB for (1,1,8)).  The full (data,pipe,seq) composition is proven
# by tests/test_composed_parallel.py and the CI train smoke at (2,2,2);
# this sweep isolates the activation-memory slope.
COMPOSED_MESHES = {4096: (1, 1, 2), 16384: (1, 1, 4), 65536: (1, 1, 8)}


def _composed_cfg(n: int, *, d_model: int, n_layers: int, mode: str):
    import dataclasses

    from repro.configs import get_config

    cfg = get_config("taylorshift-lra").reduced()
    cfg = cfg.with_(n_layers=n_layers, d_model=d_model, n_heads=2,
                    n_kv_heads=2, d_ff=2 * d_model, max_seq_len=n,
                    dtype="float32", causal=True, remat=True)
    return cfg.with_(taylor=dataclasses.replace(
        cfg.taylor, mode=mode, use_kernel=False))


def _direct_step_temp_bytes(n: int, global_batch: int, *, d_model: int,
                            n_layers: int) -> int:
    """Single-device direct-attention train step, compile-only: the
    O(N²) step never has to run to report its buffer assignment."""
    from repro.launch.steps import (build_train_step, default_opt_config,
                                    param_shapes)
    from repro.optim import make_optimizer

    cfg = _composed_cfg(n, d_model=d_model, n_layers=n_layers,
                        mode="direct")
    opt_cfg = default_opt_config(cfg)
    init_opt, _ = make_optimizer(opt_cfg)
    pshapes = param_shapes(cfg)
    oshapes = jax.eval_shape(init_opt, pshapes)
    batch = {k: jax.ShapeDtypeStruct((global_batch, n), jnp.int32)
             for k in ("tokens", "labels")}
    compiled = jax.jit(build_train_step(cfg, opt_cfg)) \
        .lower(pshapes, oshapes, batch).compile()
    return int(compiled.memory_analysis().temp_size_in_bytes)


def run_composed(seq_lens, *, global_batch: int = 4, d_model: int = 64,
                 n_layers: int = 2, steps: int = 2, json_path: str = ""):
    import time

    import numpy as np

    from repro.data.pipeline import device_put_batch
    from repro.distributed import composed as Cmp
    from repro.launch import mesh as MESH
    from repro.launch.steps import default_opt_config

    n_dev = len(jax.devices())
    cells = []
    comp_bytes, direct_bytes, ns = [], [], []
    for n in seq_lens:
        dd, pp, ss = COMPOSED_MESHES.get(n, (1, 1, n_dev))
        if dd * pp * ss > n_dev:
            print(f"# skip N={n}: mesh ({dd},{pp},{ss}) needs "
                  f"{dd * pp * ss} devices, have {n_dev}", file=sys.stderr)
            continue
        cfg = _composed_cfg(n, d_model=d_model, n_layers=n_layers,
                            mode="efficient")
        mesh = MESH.make_composed_mesh(data=dd, pipe=pp, seq=ss)
        # One sequence per microbatch: under remat the peak working set
        # scales with B/mb (measured: mb 1 → 4 cuts the N=64k cell 3×),
        # and with S=1 stages the pipeline bubble is zero regardless.
        mb = max(1, global_batch // dd)
        init_fn, step_fn, _ = Cmp.build_composed_train_step(
            cfg, default_opt_config(cfg), mesh, global_batch=global_batch,
            seq_len=n, n_microbatches=mb, fsdp=True)
        params, opt_state = init_fn(jax.random.PRNGKey(0))
        rng = np.random.default_rng(n)
        tok = rng.integers(0, cfg.vocab, (global_batch, n), dtype=np.int32)
        batch = device_put_batch(
            {"tokens": tok,
             "labels": np.roll(tok, -1, axis=1).astype(np.int32)}, mesh)

        compiled = step_fn.lower(params, opt_state, batch).compile()
        temp = int(compiled.memory_analysis().temp_size_in_bytes)
        times = []
        for _ in range(steps):
            t0 = time.perf_counter()
            params, opt_state, metrics = compiled(params, opt_state, batch)
            float(metrics["loss"])          # block
            times.append(time.perf_counter() - t0)
        dt = min(times)

        d_temp = _direct_step_temp_bytes(n, global_batch,
                                         d_model=d_model,
                                         n_layers=n_layers)
        cells.append({
            "seq_len": n, "mesh_data": dd, "mesh_pipe": pp, "mesh_seq": ss,
            "microbatches": mb, "composed_temp_bytes": temp,
            "direct_temp_bytes": d_temp, "step_time_s": dt,
            "tokens_per_s": global_batch * n / dt,
            "loss": float(metrics["loss"]),
        })
        ns.append(n)
        comp_bytes.append(temp)
        direct_bytes.append(d_temp)
        emit(f"composed_step_n{n}_mesh{dd}x{pp}x{ss}", dt * 1e6,
             f"composed_temp_B={temp};direct_temp_B={d_temp};"
             f"tok_s={global_batch * n / dt:.0f}")

    if len(ns) < 2:
        print("# need >= 2 sequence lengths for slopes; no document "
              "written", flush=True)
        return {"cells": cells}
    slopes = {"composed_activation": _slope(ns, comp_bytes),
              "direct_activation": _slope(ns, direct_bytes)}
    emit("composed_memory_slopes", 0.0,
         f"composed={slopes['composed_activation']:.2f};"
         f"direct={slopes['direct_activation']:.2f}")
    print(f"# composed activation-memory slope "
          f"{slopes['composed_activation']:.2f} (gate < 0.8) vs direct "
          f"{slopes['direct_activation']:.2f} (gate > 1.7)", flush=True)

    doc = {
        "name": "training_composed",
        "config": {"arch": "taylorshift-lra", "d_model": d_model,
                   "n_layers": n_layers, "heads": 2,
                   "global_batch": global_batch, "devices": n_dev,
                   "fsdp": True, "backend": jax.default_backend()},
        "cells": cells,
        "slopes": slopes,
    }
    from benchmarks.run import check_training_doc
    check_training_doc(doc)
    if json_path:
        with open(json_path, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"# wrote {json_path}", flush=True)
    return doc


if __name__ == "__main__":
    if ARGS.composed:
        run_composed(ARGS.seq_lens, global_batch=ARGS.global_batch,
                     steps=ARGS.steps, json_path=ARGS.json)
    else:
        run(n_values=(128, 256, 512) if ARGS.fast
            else (128, 256, 512, 1024))
