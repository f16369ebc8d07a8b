"""End-to-end training driver.

Wires every substrate layer together: config registry → data pipeline →
sharded train step (pjit) → checkpoint manager → fault tolerance
(preemption handler, straggler detector, restart supervision).

Scales from CPU smoke runs to the production mesh unchanged:

  PYTHONPATH=src python -m repro.launch.train --arch taylorshift-lra \
      --steps 200 --batch 8 --seq 256 --d-model 128
  PYTHONPATH=src python -m repro.launch.train --arch yi-9b --mesh single …
"""

from __future__ import annotations

import argparse
import logging
import platform
import time

import jax
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_config
from repro.data.pipeline import DataConfig, DataLoader, device_put_batch
from repro.distributed import composed as C
from repro.distributed import ctx
from repro.distributed import sharding as S
from repro.distributed.ft import (PreemptionHandler, StragglerDetector,
                                  run_with_restarts)
from repro.distributed.pipeline import bubble_fraction
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import (make_composed_mesh, make_local_mesh,
                               make_production_mesh, make_seq_mesh,
                               pipe_size, seq_size)
from repro.launch.steps import (build_train_step, default_opt_config,
                                opt_state_shardings, param_shapes)
from repro.models import backend as B
from repro.models import model as M
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import profile, tracer
from repro.optim import make_optimizer

log = logging.getLogger("repro.train")


class TrainObs:
    """Step-loop observability, same surfaces as the serving path
    (docs/observability.md): a MetricsRegistry rendered to Prometheus
    text via :meth:`write`, plus the process-global tracer — one
    ``train_step`` span per step (``input_batch``, ``step_dispatch``,
    ``loss_readback`` inside) lands in the Chrome trace when callers
    enable it and in any captured ``jax.profiler`` trace, so
    pipeline-bubble stalls show up in Perfetto next to the jit-warmup
    (``compile=true``) span."""

    def __init__(self):
        self.registry = MetricsRegistry()
        r = self.registry
        self.step_time = r.histogram(
            "train_step_seconds", "wall time per optimizer step",
            buckets=(0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
                     10.0, 30.0, 120.0))
        self.tokens_per_sec = r.gauge(
            "train_tokens_per_second",
            "tokens of the steps since the first one (which compiles) "
            "over their wall time")
        self._rate_tokens = 0
        self._rate_s = 0.0
        self.loss = r.gauge("train_loss", "last step loss")
        self.steps_total = r.counter("train_steps_total",
                                     "optimizer steps run")
        self.activation_bytes = r.gauge(
            "train_activation_bytes",
            "per-device temp (activation+workspace) bytes of the "
            "compiled train step, from XLA's memory analysis")
        self.bubble = r.gauge(
            "train_pipeline_bubble_fraction",
            "(S-1)/(M+S-1) of the GPipe schedule; 0 off the composed "
            "path")
        # per-axis deep metrics (composed path, PR 9): bubble
        # attribution per stage from the deterministic GPipe schedule,
        # FSDP collective bytes from the compiled HLO, the seq-axis
        # boundary-exchange probe, and a per-host step gauge whose
        # spread across fleet snapshots is the straggler signal
        self.stage_busy = r.gauge(
            "train_pipeline_stage_busy_ticks",
            "GPipe ticks stage s computes (= microbatches)",
            labelnames=("stage",))
        self.stage_warmup = r.gauge(
            "train_pipeline_stage_warmup_ticks",
            "idle ticks before the first microbatch reaches stage s",
            labelnames=("stage",))
        self.stage_drain = r.gauge(
            "train_pipeline_stage_drain_ticks",
            "idle ticks after stage s's last microbatch",
            labelnames=("stage",))
        self.collective_count = r.gauge(
            "train_collective_count",
            "collectives per compiled step, from the post-SPMD HLO",
            labelnames=("op",))
        self.collective_bytes = r.gauge(
            "train_collective_buffer_bytes",
            "per-device buffer bytes per collective kind (FSDP "
            "all-gather / reduce-scatter live here)", labelnames=("op",))
        self.collective_wire = r.gauge(
            "train_collective_wire_bytes_per_device",
            "modeled per-device wire bytes of one compiled step")
        self.kernel_calls = r.gauge(
            "train_kernel_calls",
            "compiled Pallas kernel calls (tpu_custom_call) per compiled "
            "step; 0 where the kernels are interpreted or gated off")
        self.seq_exchange_s = r.gauge(
            "train_seq_exchange_seconds",
            "measured seq-axis boundary-exchange time (log-depth "
            "ppermute + psum probe, distributed/composed.py)")
        self.seq_exchange_b = r.gauge(
            "train_seq_exchange_bytes_per_device",
            "analytic per-device bytes of one boundary exchange")
        self.host_step = r.gauge(
            "train_host_step_seconds",
            "last step wall time on this host (fleet straggler signal)",
            labelnames=("host",))
        self._host = platform.node() or "host0"

    def record_compiled(self, step_fn, *example_args) -> None:
        """AOT-lower the step to read XLA's activation-memory figure
        and the per-collective byte accounting (hlo_analysis). Costs
        one extra compile, so only runs when obs is requested."""
        try:
            compiled = step_fn.lower(*example_args).compile()
        except Exception:   # pragma: no cover — backend without AOT
            log.debug("AOT compile unavailable", exc_info=True)
            return
        try:
            mem = compiled.memory_analysis()
            self.activation_bytes.set(float(mem.temp_size_in_bytes))
        except Exception:   # pragma: no cover — backend without analysis
            log.debug("memory_analysis unavailable", exc_info=True)
        text = compiled.as_text()
        self.kernel_calls.set(
            text.count('custom_call_target="tpu_custom_call"'))
        try:
            from repro.distributed.hlo_analysis import collective_stats
            stats = collective_stats(text)
            for op, n in stats.counts.items():
                self.collective_count.labels(op=op).set(n)
            for op, b in stats.buffer_bytes.items():
                self.collective_bytes.labels(op=op).set(b)
            self.collective_wire.set(stats.wire_bytes_per_device)
        except Exception:   # pragma: no cover — no post-SPMD text
            log.debug("collective_stats unavailable", exc_info=True)

    def record_pipeline(self, n_stages: int, n_microbatches: int) -> None:
        """Whole-schedule bubble plus the per-stage warmup/busy/drain
        tick split (distributed/pipeline.py:stage_occupancy)."""
        from repro.distributed.pipeline import (bubble_fraction,
                                                stage_occupancy)
        self.bubble.set(bubble_fraction(n_stages, n_microbatches))
        for occ in stage_occupancy(n_stages, n_microbatches):
            s = str(occ["stage"])
            self.stage_busy.labels(stage=s).set(occ["busy"])
            self.stage_warmup.labels(stage=s).set(occ["warmup_idle"])
            self.stage_drain.labels(stage=s).set(occ["drain_idle"])

    def record_seq_exchange(self, probe: dict) -> None:
        self.seq_exchange_s.set(probe["seconds"])
        self.seq_exchange_b.set(probe["bytes_per_device"])

    def observe(self, *, dt: float, tokens: int, loss: float,
                first: bool = False) -> None:
        """One finished step; ``first`` marks the step that compiled,
        which the tokens-per-second rate leaves out."""
        self.step_time.observe(dt)
        if not first:
            self._rate_tokens += tokens
            self._rate_s += dt
            self.tokens_per_sec.set(self._rate_tokens
                                    / max(self._rate_s, 1e-9))
        self.loss.set(loss)
        self.steps_total.inc()
        self.host_step.labels(host=self._host).set(dt)

    def write(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.registry.render())


def train(cfg, *, steps: int, global_batch: int, seq_len: int,
          mesh=None, ckpt_dir: str | None = None, ckpt_every: int = 50,
          log_every: int = 10, seed: int = 0, opt_cfg=None,
          obs: TrainObs | None = None):
    mesh = mesh or make_local_mesh()
    opt_cfg = opt_cfg or default_opt_config(cfg)
    init_opt, _ = make_optimizer(opt_cfg)
    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None

    data_cfg = DataConfig(vocab=cfg.vocab, global_batch=global_batch,
                          seq_len=seq_len, seed=seed)

    with mesh, ctx.use(mesh):
        sel = B.select_backend(cfg, N=seq_len, d=cfg.dim_head, site="full",
                               causal=cfg.causal)
        log.info("attention backend: %s mode=%s seq_shards=%d (%s)",
                 sel.name, sel.mode, sel.seq_shards, sel.reason)
        pshapes = param_shapes(cfg)
        pshard = S.param_shardings(pshapes, mesh)
        oshard = opt_state_shardings(cfg, opt_cfg, pshapes, pshard, mesh)
        step_fn = jax.jit(
            build_train_step(cfg, opt_cfg),
            in_shardings=(pshard, oshard, None),
            out_shardings=(pshard, oshard, None),
            donate_argnums=(0, 1),
        )

        start_step = 0
        if mgr is not None and mgr.latest_step() is not None:
            ostates = jax.eval_shape(init_opt, pshapes)
            start_step, (params, opt_state) = mgr.restore(
                (pshapes, ostates), shardings=(pshard, oshard))
            log.info("restored checkpoint at step %d", start_step)
        else:
            params = jax.device_put(
                M.init_params(cfg, jax.random.PRNGKey(seed)), pshard)
            opt_state = jax.device_put(init_opt(params), oshard)

        loader = DataLoader(data_cfg, start_step=start_step)
        detector = StragglerDetector(
            registry=obs.registry if obs is not None else None)
        losses = []
        obs_compiled = obs is None
        with PreemptionHandler() as pre:
            try:
                for step, batch in loader:
                    if step >= steps:
                        break
                    t0 = time.time()
                    with tracer.span("train_step", step_num=step,
                                     compile_key="train_step"):
                        with tracer.span("input_batch"):
                            batch = device_put_batch(batch, mesh)
                        if not obs_compiled:
                            obs.record_compiled(step_fn, params, opt_state,
                                                batch)
                            obs_compiled = True
                        # returns once the step is enqueued; the loss's
                        # read-back is where the host waits on the device
                        with tracer.span("step_dispatch"):
                            params, opt_state, metrics = step_fn(
                                params, opt_state, batch)
                        with tracer.span("loss_readback"):
                            loss = float(metrics["loss"])
                    dt = time.time() - t0
                    detector.observe(dt)
                    if obs is not None:
                        obs.observe(dt=dt, tokens=global_batch * seq_len,
                                    loss=loss, first=step == start_step)
                    losses.append(loss)
                    if step % log_every == 0:
                        log.info("step %d loss %.4f gnorm %.3f (%.2fs)",
                                 step, loss,
                                 float(metrics["grad_norm"]),
                                 time.time() - t0)
                    if mgr is not None and step and step % ckpt_every == 0:
                        mgr.save(step + 1, (params, opt_state))
                    if pre.preempted:
                        log.warning("preempted — checkpointing at step %d",
                                    step)
                        if mgr is not None:
                            mgr.save(step + 1, (params, opt_state),
                                     blocking=True)
                        break
            finally:
                loader.close()
                if mgr is not None:
                    mgr.wait()
    return {"params": params, "opt_state": opt_state, "losses": losses,
            "stragglers": detector.stragglers}


def train_composed(cfg, *, steps: int, global_batch: int, seq_len: int,
                   mesh, n_microbatches: int, fsdp: bool = False,
                   ckpt_dir: str | None = None, ckpt_every: int = 50,
                   log_every: int = 10, seed: int = 0, opt_cfg=None,
                   obs: TrainObs | None = None):
    """Composed 3D-parallel training loop: seq-scan × pipeline × FSDP on
    one ``(data, pipe, seq)`` mesh (distributed/composed.py). Same
    data / checkpoint / fault-tolerance wiring as :func:`train`; the
    step itself is the single fully-manual shard_map step, so there is
    no ``ctx.use`` — the composed selector pins the mesh explicitly."""
    opt_cfg = opt_cfg or default_opt_config(cfg)
    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
    S_pipe = pipe_size(mesh)
    S_seq = seq_size(mesh)

    data_cfg = DataConfig(vocab=cfg.vocab, global_batch=global_batch,
                          seq_len=seq_len, seed=seed)

    sel = B.select_composed_scan(cfg, N=seq_len, d=cfg.dim_head,
                                 causal=cfg.causal, mesh=mesh)
    log.info("composed mesh %s: scan=%s chunk=%d microbatches=%d "
             "bubble=%.3f fsdp=%s (%s)",
             dict(mesh.shape), sel.scan, sel.chunk, n_microbatches,
             bubble_fraction(S_pipe, n_microbatches), fsdp, sel.reason)
    if obs is not None:
        obs.record_pipeline(S_pipe, n_microbatches)
        if S_seq > 1:
            # one-shot startup probe — never inside the step loop
            obs.record_seq_exchange(C.measure_seq_exchange(
                mesh, d=cfg.dim_head, heads=cfg.n_heads))
        else:
            obs.record_seq_exchange(
                {"seconds": 0.0, "bytes_per_device": 0, "rounds": 0})

    init_fn, step_fn, _ = C.build_composed_train_step(
        cfg, opt_cfg, mesh, global_batch=global_batch, seq_len=seq_len,
        n_microbatches=n_microbatches, fsdp=fsdp)

    with mesh:
        start_step = 0
        if mgr is not None and mgr.latest_step() is not None:
            split_shapes = jax.eval_shape(C._split_shapes_thunk(cfg, S_pipe))
            init_opt, _ = make_optimizer(opt_cfg)
            oshapes = jax.eval_shape(init_opt, split_shapes)
            pshard = C.composed_param_shardings(split_shapes, mesh,
                                                fsdp=fsdp)
            oshard = C.composed_opt_shardings(oshapes, pshard, mesh)
            start_step, (params, opt_state) = mgr.restore(
                (split_shapes, oshapes), shardings=(pshard, oshard))
            log.info("restored composed checkpoint at step %d", start_step)
        else:
            params, opt_state = init_fn(jax.random.PRNGKey(seed))

        loader = DataLoader(data_cfg, start_step=start_step)
        detector = StragglerDetector(
            registry=obs.registry if obs is not None else None)
        losses = []
        obs_compiled = obs is None
        with tracer.span("composed_schedule", stages=S_pipe, seq=S_seq,
                         data=mesh.shape["data"],
                         microbatches=n_microbatches,
                         bubble=bubble_fraction(S_pipe, n_microbatches)):
            pass
        with PreemptionHandler() as pre:
            try:
                for step, batch in loader:
                    if step >= steps:
                        break
                    t0 = time.time()
                    with tracer.span("train_step", step_num=step,
                                     compile_key="composed_step"):
                        with tracer.span("input_batch"):
                            batch = device_put_batch(batch, mesh)
                        if not obs_compiled:
                            obs.record_compiled(step_fn, params, opt_state,
                                                batch)
                            obs_compiled = True
                        with tracer.span("step_dispatch"):
                            params, opt_state, metrics = step_fn(
                                params, opt_state, batch)
                        with tracer.span("loss_readback"):
                            loss = float(metrics["loss"])
                    dt = time.time() - t0
                    detector.observe(dt)
                    if obs is not None:
                        obs.observe(dt=dt, tokens=global_batch * seq_len,
                                    loss=loss, first=step == start_step)
                    losses.append(loss)
                    if step % log_every == 0:
                        log.info("step %d loss %.4f gnorm %.3f (%.2fs)",
                                 step, loss,
                                 float(metrics["grad_norm"]), dt)
                    if mgr is not None and step and step % ckpt_every == 0:
                        mgr.save(step + 1, (params, opt_state))
                    if pre.preempted:
                        log.warning("preempted — checkpointing at step %d",
                                    step)
                        if mgr is not None:
                            mgr.save(step + 1, (params, opt_state),
                                     blocking=True)
                        break
            finally:
                loader.close()
                if mgr is not None:
                    mgr.wait()
    return {"params": params, "opt_state": opt_state, "losses": losses,
            "stragglers": detector.stragglers}


def main():
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="taylorshift-lra")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--d-model", type=int, default=0,
                    help="override width (CPU smoke runs)")
    ap.add_argument("--n-layers", type=int, default=0)
    ap.add_argument("--mesh", default="local",
                    choices=["local", "single", "multi"])
    ap.add_argument("--context-parallel", type=int, default=1,
                    help="size of the `seq` mesh axis: shards the causal "
                         "Taylor scan (and activations) over the sequence "
                         "(docs/sharding.md)")
    ap.add_argument("--pipeline-stages", type=int, default=1,
                    help="size of the `pipe` mesh axis: >1 switches to "
                         "the composed (data, pipe, seq) training path "
                         "(distributed/composed.py, docs/training.md)")
    ap.add_argument("--microbatches", type=int, default=0,
                    help="GPipe microbatches on the composed path "
                         "(default: 2x stages, capped at the per-data-"
                         "shard batch)")
    ap.add_argument("--fsdp", action="store_true",
                    help="composed path: shard stage weight matrices "
                         "over `data` with just-in-time all-gather "
                         "(ZeRO-3)")
    ap.add_argument("--metrics-file", default="",
                    help="write Prometheus text metrics here at exit")
    ap.add_argument("--trace", default="",
                    help="write a Chrome trace of train_step spans here")
    ap.add_argument("--profile-dir", default="", metavar="DIR",
                    help="capture a jax.profiler trace of the run into "
                         "DIR: device operations with the train_step "
                         "spans on the same clock (TensorBoard/Perfetto)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--restartable", action="store_true",
                    help="wrap in the fault-tolerant supervision loop")
    ap.add_argument("--no-kernels", action="store_true",
                    help="train through the pure-jnp reference attention "
                         "instead of the fused Pallas kernels (custom VJP)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.d_model:
        cfg = cfg.with_(d_model=args.d_model)
    if args.n_layers:
        cfg = cfg.with_(n_layers=args.n_layers)
    cfg = cfg.with_(max_seq_len=max(cfg.max_seq_len, args.seq))
    cfg = B.configure_for_training(cfg, use_kernels=not args.no_kernels)

    cp = args.context_parallel
    pp = args.pipeline_stages
    if pp > 1:
        mesh = (make_composed_mesh(pipe=pp, seq=cp)
                if args.mesh == "local"
                else make_production_mesh(multi_pod=args.mesh == "multi",
                                          seq=cp, pipe=pp))
    elif cp > 1:
        mesh = (make_seq_mesh(cp) if args.mesh == "local"
                else make_production_mesh(multi_pod=args.mesh == "multi",
                                          seq=cp))
    else:
        mesh = (make_local_mesh() if args.mesh == "local"
                else make_production_mesh(multi_pod=args.mesh == "multi"))

    obs = TrainObs() if (args.metrics_file or args.trace) else None
    if args.trace:
        tracer.enable()

    def go(_state=None):
        if pp > 1:
            b_loc = args.batch // mesh.shape["data"]
            mb = args.microbatches or max(1, min(2 * pp, b_loc))
            return train_composed(
                cfg, steps=args.steps, global_batch=args.batch,
                seq_len=args.seq, mesh=mesh, n_microbatches=mb,
                fsdp=args.fsdp, ckpt_dir=args.ckpt_dir or None, obs=obs)
        return train(cfg, steps=args.steps, global_batch=args.batch,
                     seq_len=args.seq, mesh=mesh,
                     ckpt_dir=args.ckpt_dir or None, obs=obs)

    with profile(args.profile_dir):
        if args.restartable:
            out = run_with_restarts(lambda: None, go)
        else:
            out = go()
    if args.trace:
        tracer.write(args.trace)
        tracer.disable()
        print(f"trace: {len(tracer.export()['traceEvents'])} events "
              f"-> {args.trace}")
    if args.metrics_file and obs is not None:
        obs.write(args.metrics_file)
        print(f"metrics exposition -> {args.metrics_file}")
    print(f"final loss: {np.mean(out['losses'][-10:]):.4f} "
          f"(first10 {np.mean(out['losses'][:10]):.4f}), "
          f"stragglers={out['stragglers']}")


if __name__ == "__main__":
    main()
