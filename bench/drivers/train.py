"""Training cells: the program's jitted train step over its data loader.

Set-up builds one object, the step as ``launch/train.py:train()`` builds
it (``build_train_step`` under ``jax.jit`` with the mesh's parameter and
optimizer shardings, parameters and optimizer state donated), with the
weights made from the seed and the optimizer configured as the
configuration states. It drives that object through its first
``check_steps`` steps on the loader's batches, copying to the host what
the check needs before each donation: the float32 master parameters
before the first step, the first moment after it, the master after the
last. The window then runs the same object on the loader's next
batches, one loss read back a step as ``train()`` does, until its time
is up.

``train_tokens_per_s``: tokens of the steps finished in the window over
the window (start of the first step to the end of the last). Each step's
time is kept in three parts (the batch, the call into the step, the wait
for its loss) with the main thread's CPU time, and the slowest steps are
printed with the garbage collection that ran inside them.

Correctness: the plain reference follows the checked steps from the same
weights and batches, and three numbers are compared, each the worst
case over steps or over parameter leaves:

- ``loss_gap``: |loss - reference| / |reference| of each step;
- ``grad_gap``: the first gradient as the optimizer got it (its first
  moment after one step over 1 - b1) against the reference's clipped
  gradient, leaf by leaf: | |g| - |g_ref| | / max(|g_ref|, median leaf
  |g_ref|);
- ``update_gap``: the same for the change of the master parameters over
  the checked steps, leaving out leaves whose reference gradient is
  under a thousandth of the median leaf's.
"""

from __future__ import annotations

import gc
import time

import jax
import numpy as np

from bench import flops as F
from bench.weights import make_params, to_host

QUIET_LEAF = 1e-3


def norms(leaves) -> np.ndarray:
    return np.array([float(np.linalg.norm(np.asarray(x, np.float64)))
                     for x in leaves])


def norm_gap(got: np.ndarray, want: np.ndarray, keep=None) -> float:
    """Worst leaf of | |got| - |want| | / max(|want|, median |want|)."""
    if keep is not None:
        got, want = got[keep], want[keep]
    floor = np.median(want)
    return float(np.max(np.abs(got - want) / np.maximum(want, floor)))


def build(run, mesh):
    from repro.data.pipeline import DataConfig, DataLoader
    from repro.distributed import sharding as S
    from repro.launch.steps import (build_train_step, opt_state_shardings,
                                    param_shapes)
    from repro.models import backend as B
    from repro.optim import OptConfig, make_optimizer

    t = run.traffic
    cfg = run.program_config()
    if t.get("kernels"):
        cfg = B.configure_for_training(cfg)
    opt_cfg = OptConfig(**run.config["optimizer"])
    init_opt, _ = make_optimizer(opt_cfg)
    pshapes = param_shapes(cfg)
    pshard = S.param_shardings(pshapes, mesh)
    oshard = opt_state_shardings(cfg, opt_cfg, pshapes, pshard, mesh)
    step_fn = jax.jit(build_train_step(cfg, opt_cfg),
                      in_shardings=(pshard, oshard, None),
                      out_shardings=(pshard, oshard, None),
                      donate_argnums=(0, 1))
    params = make_params(pshapes, seed=run.seed, d_model=cfg.d_model,
                         init=run.config.get("init"), sharding=pshard)
    opt_state = jax.device_put(init_opt(params), oshard)
    loader = DataLoader(DataConfig(vocab=cfg.vocab,
                                   global_batch=t["global_batch"],
                                   seq_len=t["seq_len"], seed=run.seed))
    return cfg, step_fn, params, opt_state, loader


def run(run):
    from repro.distributed import ctx
    from repro.launch.mesh import make_local_mesh

    mesh = make_local_mesh()
    with mesh, ctx.use(mesh):
        return _run(run, mesh)


def _run(run, mesh):
    from repro.data.pipeline import device_put_batch

    t = run.traffic
    cfg, step_fn, params, opt_state, loader = build(run, mesh)
    tokens_per_step = t["global_batch"] * t["seq_len"]
    treedef = jax.tree.structure(params)
    stored = jax.tree.map(lambda x: x.dtype, params)
    host_params = to_host(params)
    first_batches, losses = [], []
    mu1 = master3 = None
    try:
        for i in range(t["check_steps"]):
            _, batch = next(loader)
            first_batches.append((batch["tokens"], batch["labels"]))
            params, opt_state, out = step_fn(
                params, opt_state, device_put_batch(batch, mesh))
            losses.append(float(out["loss"]))
            if i == 0:
                mu1 = to_host(opt_state["mu"])
        master3 = to_host(opt_state["master"])
        run.setup_done()

        n_steps = 0
        t0 = time.perf_counter()
        t_end = t0 + run.seconds
        last = t0
        steps = []        # (start, batch made, step called, loss read, cpu)
        while last < t_end:
            run.tracer.maybe_start(last - t0)
            cpu = time.thread_time()
            with jax.profiler.TraceAnnotation("bench.input_batch"):
                _, batch = next(loader)
                batch = device_put_batch(batch, mesh)
            t_batch = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.train_step",
                                              step=n_steps):
                params, opt_state, out = step_fn(params, opt_state, batch)
                t_call = time.perf_counter()
                loss = float(out["loss"])
            now = time.perf_counter()
            steps.append((last, t_batch, t_call, now,
                          time.thread_time() - cpu))
            last = now
            n_steps += 1
            if not np.isfinite(loss):
                break
        run.window_closed()
    finally:
        loader.close()
    window = last - t0
    step_s = [s[3] - s[0] for s in steps]
    e2e = {"train_tokens_per_s": n_steps * tokens_per_step / window}
    stats = {"steps_in_window": n_steps, "window_s": window,
             "step_s_mean": window / n_steps,
             "step_s_median": float(np.median(step_s)),
             "step_s_max": max(step_s), "check_losses": losses,
             "last_loss": loss,
             "slowest_steps": run.slowest(
                 [(s[0], s[3], s[4]) for s in steps], t0,
                 parts={"batch_ms": [s[1] - s[0] for s in steps],
                        "call_ms": [s[2] - s[1] for s in steps],
                        "loss_wait_ms": [s[3] - s[2] for s in steps]})}
    run.memory_peak()
    run.flops_in_window = n_steps * F.train_step_flops(
        run.model, batch=t["global_batch"], seq_len=t["seq_len"],
        layer_flops=getattr(run.reference(), "layer_flops", None))
    del params, opt_state, out, batch
    gc.collect()

    t_check = time.perf_counter()
    checks, controls = compare(run, treedef, stored, host_params,
                               first_batches, losses, mu1, master3)
    stats["check_s"] = time.perf_counter() - t_check
    return {"e2e": e2e, "stats": stats, "checks": checks,
            "controls": controls,
            "attempted": n_steps, "failed": 0 if np.isfinite(loss) else 1,
            "ok": True}


def compare(run, treedef, stored, host_params, batches, losses, mu1,
            master3):
    """The three numbers, and in a calibration run the same three read
    from the control and from the half-batch fault, each put in the
    program's place."""
    ref = run.reference()
    opt = run.config["optimizer"]
    p0 = jax.tree.unflatten(treedef, host_params)

    def readings(r_losses, r_grad, r_params, got_losses, got_grad, got_delta):
        g_ref = norms(jax.tree.leaves(r_grad))
        d_ref = norms([a - b for a, b in zip(jax.tree.leaves(r_params),
                                             host_params)])
        keep = g_ref >= QUIET_LEAF * np.median(g_ref)
        loss_gap = max(abs(a - b) / abs(b) for a, b in zip(got_losses,
                                                            r_losses))
        return {"loss_gap": float(loss_gap),
                "grad_gap": norm_gap(got_grad, g_ref),
                "update_gap": norm_gap(got_delta, d_ref, keep)}

    r_losses, r_grad, r_params = ref.train_steps(p0, run.model, opt, batches,
                                                 stored=stored)
    got_grad = norms(mu1) / (1 - opt["b1"])
    got_delta = norms([a - b for a, b in zip(master3, host_params)])
    checks = readings(r_losses, r_grad, r_params, losses, got_grad,
                      got_delta)
    controls = {}
    if run.calibrate:
        for name, kw, bs in (
                ("control_fp8", {"rounding": "fp8"}, batches),
                ("fault_half_batch", {},
                 [(x[: len(x) // 2], y[: len(y) // 2]) for x, y in batches])):
            c_losses, c_grad, c_params = ref.train_steps(
                p0, run.model, opt, bs, stored=stored, **kw)
            c_delta = norms([a - b for a, b in zip(jax.tree.leaves(c_params),
                                                   host_params)])
            controls[name] = readings(r_losses, r_grad, r_params, c_losses,
                                      norms(jax.tree.leaves(c_grad)), c_delta)
    return checks, controls
