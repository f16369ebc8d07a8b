"""Serving CLI — thin front end over the continuous-batching engine.

Runs a mixed-arrival workload: requests with different prompt lengths
are submitted on a staggered schedule, share decode batches mid-flight,
and every prompt is absorbed through chunked prefill (state handoff via
``causal_taylorshift(initial_state=...)``) — no token-by-token prefill
loop remains in the serving path. With ``--check`` (default) each
request is re-run alone through the naive single-sequence baseline and
the tokens must match exactly at temperature 0 — including under
``--speculate K`` (greedy speculative decoding is exact; see
src/repro/spec/ and docs/serving.md).

  PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b \
      --reduced --d-model 128 --requests 4 --prompt-len 32 --gen 16 \
      --speculate 4 --drafter self

The model runs at its published widths unless ``--reduced`` asks for
the tiny CPU shape (``ModelConfig.reduced()``), which ``--d-model`` and
``--n-layers`` then override.

Observability (src/repro/obs/, docs/observability.md): ``--trace PATH``
writes a Chrome-trace/Perfetto JSON of every engine-step phase
(admission, prefix-cache lookup, prefill chunks, decode/draft/verify/
rollback, first dispatches tagged ``compile=true``); ``--metrics-file
PATH`` writes the Prometheus exposition (TTFT/ITL histograms,
prefix-cache and speculation counters) at exit and ``--metrics-port N``
serves it live on ``http://localhost:N/metrics``; ``--decision-log
PATH`` writes every ``select_backend`` record as JSONL — replaying
exactly how the engine's ServePlan and each trace-time attention site
were chosen. All of it observational: streams are bit-identical with
every flag on or off.

Fleet mode: ``--replica NAME`` names this process — it threads into
``EngineConfig.replica_id``, the ONE identity obs snapshots,
``ft.Membership`` and the router agree on — and ``--metrics-snapshot
PATH`` writes the mergeable ``repro.obs/v1`` snapshot at exit. Run N
replicas, then::

    python -m repro.obs --request req0 r0_trace.json r1_trace.json
    python -m repro.obs --merge-snapshots r0.snap r1.snap --prom fleet.prom
    python -m repro.obs.slo --check --snapshot r0.snap --snapshot r1.snap

Router mode (``--router``, serve/router.py): the same workload runs
against ``--replicas N`` in-process engine replicas behind the
prefix-aware router, with live migration on preemption
(``--migrate-on-preempt``, default on; ``--preempt-step K`` force-
preempts the busiest replica at fleet step K — the CI chaos check).
``--metrics-snapshot`` then writes the *merged* fleet snapshot (every
replica + the router's ``router_*``/``ft_*`` families), and ``--check``
still validates every stream against the naive baseline — migration
included, because migrated streams are bit-identical::

    python -m repro.launch.serve --reduced --router --replicas 2 \
        --preempt-step 6 --requests 4 --prefix-cache -1
"""

from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp

from repro.configs import SpecConfig, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.obs import decisions as OD
from repro.obs.trace import tracer
from repro.serve import Engine, EngineConfig, Request
from repro.tune import table as TT


def serve_metrics_http(engine: Engine, port: int):
    """Serve ``engine.render_metrics()`` on a daemon thread (Prometheus
    scrape target). Returns the server (``.shutdown()`` to stop)."""
    import threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class Handler(BaseHTTPRequestHandler):
        def do_GET(self):
            body = engine.render_metrics().encode()
            self.send_response(200)
            self.send_header("Content-Type",
                             "text/plain; version=0.0.4; charset=utf-8")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):    # no per-scrape stderr chatter
            pass

    srv = ThreadingHTTPServer(("127.0.0.1", port), Handler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv


def naive_generate(cfg, params, prompts: jnp.ndarray, *, gen_tokens: int,
                   cache_kind: str = "taylor", temperature: float = 0.0,
                   rng=None):
    """Token-by-token baseline (prefill AND decode through decode_step).

    Kept as the correctness oracle and the benchmark strawman; the
    engine's chunked prefill replaces this in the serving path.
    prompts: (B, P) int32. Returns (B, P + gen_tokens).
    """
    B, P = prompts.shape
    cache = M.init_decode_state(cfg, B, cache_len=P + gen_tokens + 1,
                                cache_kind=cache_kind, dtype=jnp.float32)
    step = jax.jit(lambda b, c: M.decode_step(params, cfg, b, c))

    logits = None
    for t in range(P):
        logits, cache = step({"tokens": prompts[:, t:t+1]}, cache)

    toks = [prompts]
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    for i in range(gen_tokens):
        if temperature > 0:
            rng, sub = jax.random.split(rng)
            cur = jax.random.categorical(sub, logits[:, -1] / temperature)
        else:
            cur = jnp.argmax(logits[:, -1], axis=-1)
        cur = cur[:, None].astype(jnp.int32)
        toks.append(cur)
        logits, cache = step({"tokens": cur}, cache)
    return jnp.concatenate(toks, axis=1)


def mixed_arrival_workload(cfg, n_requests: int, prompt_len: int, gen: int,
                           seed: int = 1, *, top_k: int = 0,
                           top_p: float = 1.0, shared_frac: float = 0.0):
    """Requests with staggered arrival steps and varied prompt lengths.

    ``shared_frac > 0`` makes every prompt open with the same
    ``shared_frac · prompt_len`` token prefix (a shared system prompt)
    followed by a per-request tail — the workload the prefix cache
    (``--prefix-cache``) exists for.
    """
    reqs, arrivals = [], []
    shared_len = int(prompt_len * shared_frac)
    shared = jax.random.randint(jax.random.PRNGKey(seed - 1),
                                (shared_len,), 0, cfg.vocab)
    for i in range(n_requests):
        plen = max(4, prompt_len - 5 * i)
        # tail of 0 is fine when a shared prefix exists (the repeated-
        # prompt limit at FRAC=1.0); prompts never exceed prompt_len
        tail_len = max(plen - shared_len, 0 if shared_len else plen)
        prompt = jax.random.randint(jax.random.PRNGKey(seed + i),
                                    (tail_len,), 0, cfg.vocab)
        toks = [*(int(t) for t in shared), *(int(t) for t in prompt)]
        reqs.append(Request(request_id=f"req{i}", prompt=toks,
                            max_new_tokens=gen, top_k=top_k, top_p=top_p))
        # ~half the requests arrive mid-flight, while earlier ones decode
        arrivals.append(0 if i < (n_requests + 1) // 2 else 2 * i)
    return reqs, arrivals


def run_workload(engine: Engine, reqs, arrivals):
    """Drive the engine with an arrival schedule keyed on step index."""
    pending = sorted(zip(arrivals, reqs), key=lambda p: p[0])
    while pending or not engine.idle:
        while pending and pending[0][0] <= engine.step_idx:
            engine.submit(pending.pop(0)[1])
        engine.step()
    return {r.request_id: engine.results[r.request_id] for r in reqs}


def run_router_workload(router, reqs, arrivals, *, preempt_step: int = 0):
    """Drive the fleet with the same arrival schedule, keyed on fleet
    steps. ``preempt_step > 0`` force-preempts the busiest replica once
    at that step — decoding streams migrate mid-flight (or replay,
    without ``migrate_on_preempt``) and, because migration is
    bit-identical, the caller's ``--check`` still holds."""
    pending = sorted(zip(arrivals, reqs), key=lambda p: p[0])
    step = 0
    while pending or not router.idle:
        while pending and pending[0][0] <= step:
            router.submit(pending.pop(0)[1])
        router.step()
        step += 1
        if step == preempt_step and len(router.replicas) > 1:
            victim = max(router.replicas,
                         key=lambda r: len(router.replicas[r].sequences))
            moved = router.preempt(victim)
            print(f"preempted {victim} at step {step}: "
                  f"{len(moved['migrated'])} migrated, "
                  f"{len(moved['resubmitted'])} resubmitted")
    return {r.request_id: router.results[r.request_id] for r in reqs}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny CPU shape (ModelConfig.reduced()) instead "
                         "of the published widths")
    ap.add_argument("--d-model", type=int, default=0,
                    help="override width (0 = keep)")
    ap.add_argument("--n-layers", type=int, default=0,
                    help="override depth (0 = keep)")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--token-budget", type=int, default=64)
    ap.add_argument("--cache", default="taylor",
                    choices=["taylor", "kv", "auto"],
                    help="decode-cache layout; 'auto' picks via the paper's "
                         "N1 memory crossover (select_serve_plan)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0,
                    help="per-request top-k sampling cut (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="per-request nucleus sampling mass (1.0 = off)")
    ap.add_argument("--shared-prefix", type=float, default=0.0,
                    metavar="FRAC", help="give every request a common "
                    "prompt prefix of this fraction of --prompt-len "
                    "(pair with --prefix-cache)")
    ap.add_argument("--prefix-cache", type=float, default=0.0, metavar="MB",
                    help="shared-prefix state cache byte budget in MB "
                         "(0 = off, <0 = unbounded); repeated prompt "
                         "prefixes resume from cached chunked-prefill "
                         "state (serve/prefix_cache.py)")
    ap.add_argument("--speculate", type=int, default=0, metavar="K",
                    help="speculative decoding with draft length <= K "
                         "(0 = one token per step)")
    ap.add_argument("--drafter", default="ngram", choices=["ngram", "self"],
                    help="draft source: prompt-lookup n-grams or the "
                         "model's own first --draft-layers blocks")
    ap.add_argument("--draft-layers", type=int, default=1,
                    help="self-drafter: number of leading blocks reused")
    ap.add_argument("--no-check", dest="check", action="store_false",
                    help="skip the per-request naive-baseline comparison")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome-trace JSON of every engine-step "
                         "phase (open in chrome://tracing or Perfetto)")
    ap.add_argument("--annotate-steps", action="store_true",
                    help="with --trace: also enter jax.profiler "
                         "StepTraceAnnotation per engine step (correlates "
                         "a simultaneous device profile)")
    ap.add_argument("--metrics-file", default=None, metavar="PATH",
                    help="write the Prometheus text exposition at exit")
    ap.add_argument("--metrics-snapshot", default=None, metavar="PATH",
                    help="write the mergeable repro.obs/v1 metrics "
                         "snapshot at exit (fleet aggregation / SLO "
                         "input: python -m repro.obs / repro.obs.slo)")
    ap.add_argument("--replica", default=None, metavar="NAME",
                    help="name this replica (EngineConfig.replica_id): "
                         "tags the trace's process track, the snapshot's "
                         "gauges and the fleet membership")
    ap.add_argument("--router", action="store_true",
                    help="serve through the prefix-aware router over "
                         "--replicas in-process engine replicas "
                         "(serve/router.py)")
    ap.add_argument("--replicas", type=int, default=2, metavar="N",
                    help="router mode: number of engine replicas")
    ap.add_argument("--no-migrate-on-preempt", dest="migrate_on_preempt",
                    action="store_false",
                    help="router mode: replay preempted streams from "
                         "scratch instead of live-migrating them")
    ap.add_argument("--preempt-step", type=int, default=0, metavar="K",
                    help="router mode: force-preempt the busiest replica "
                         "at fleet step K (0 = never) — exercises live "
                         "migration under --check")
    ap.add_argument("--metrics-port", type=int, default=0, metavar="PORT",
                    help="serve the exposition live on "
                         "http://localhost:PORT/metrics (0 = off)")
    ap.add_argument("--decision-log", default=None, metavar="PATH",
                    help="write every select_backend decision as JSONL")
    ap.add_argument("--tuning-table", default=None, metavar="PATH",
                    help="install a repro.tune calibration table: "
                         "select_backend uses its measured N0/N1 instead "
                         "of the analytic crossovers, and the Pallas "
                         "kernels pick its swept block shapes")
    ap.add_argument("--autotune", action="store_true",
                    help="run a quick calibration sweep on this backend "
                         "before serving and install the result (pair "
                         "with --tuning-table to also persist it)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.d_model:
        cfg = cfg.with_(d_model=args.d_model)
    if args.n_layers:
        cfg = cfg.with_(n_layers=args.n_layers)
    params = M.init_params(cfg, jax.random.PRNGKey(0))

    # calibration comes up FIRST: kernel block shapes resolve through
    # the installed table at trace time, and the engine's ServePlan
    # consults the measured crossovers when it picks the cache layout
    if args.autotune:
        from repro.tune.calibrate import calibrate
        table = calibrate([cfg.head_dim], quick=True, verbose=True)
        if args.tuning_table:
            table.save(args.tuning_table)
            print(f"calibration table -> {args.tuning_table}")
        TT.install(table)
    elif args.tuning_table:
        TT.install(TT.TuningTable.load(args.tuning_table))
        print(f"installed tuning table {args.tuning_table} "
              f"({len(TT.active().entries)} entries)")

    # observability switches come up BEFORE the engine exists so the
    # ServePlan's select_backend calls land in the decision log and the
    # first-dispatch (compile=true) spans land in the trace
    if args.replica:
        tracer.set_process_name(args.replica)
    if args.trace:
        tracer.enable(annotate_steps=args.annotate_steps)
    if args.decision_log:
        OD.log.enable()

    def econf(replica_id):
        return EngineConfig(
            n_slots=args.slots, prefill_chunk=args.prefill_chunk,
            token_budget=args.token_budget, cache_kind=args.cache,
            max_seq_len=args.prompt_len + args.gen + 1,
            temperature=args.temperature,
            prefix_cache_mb=args.prefix_cache,
            speculate_k=args.speculate,
            spec=SpecConfig(drafter=args.drafter,
                            draft_layers=args.draft_layers),
            replica_id=replica_id)

    router = None
    if args.router:
        from repro.serve.router import Router
        engines = [Engine(cfg, params, econf(f"r{i}"))
                   for i in range(max(args.replicas, 1))]
        router = Router(engines,
                        migrate_on_preempt=args.migrate_on_preempt)
        engine, plan = engines[0], engines[0].plan
    else:
        engine = Engine(cfg, params, econf(args.replica))
        plan = engine.plan
    print(f"serve plan: cache={plan.cache_kind} "
          f"prefill={plan.prefill.name} decode={plan.decode.name}"
          + (f" verify={plan.verify.name}" if plan.verify else "")
          + f" ({plan.reason})")
    metrics_srv = (serve_metrics_http(engine, args.metrics_port)
                   if args.metrics_port else None)
    reqs, arrivals = mixed_arrival_workload(
        cfg, args.requests, args.prompt_len, args.gen,
        top_k=args.top_k, top_p=args.top_p, shared_frac=args.shared_prefix)
    if router is not None:
        results = run_router_workload(router, reqs, arrivals,
                                      preempt_step=args.preempt_step)
        routed = {rid: int(c.value) for rid, c in
                  [(r, router._requests_c.labels(replica=r))
                   for r in sorted({*router.replicas,
                                    *(o for o in router._owner.values())})]}
        print(json.dumps({
            "replicas": sorted(router.replicas),
            "routed": routed,
            "migrations": int(router._migrations_c.value),
            "resubmissions": int(router._resub_c.value),
            "wire_bytes": int(router._wire_c.value),
            "epoch": router.membership.epoch}, indent=2))
    else:
        results = run_workload(engine, reqs, arrivals)
        summary = engine.stats.summary()
        print(json.dumps(summary, indent=2))
        shared = max((m.active_decoding for m in engine.stats.steps),
                     default=0)
        print(f"max sequences sharing a decode batch: {shared}")

    if args.trace:
        tracer.write(args.trace)
        tracer.disable()
        print(f"trace: {len(tracer.export()['traceEvents'])} events "
              f"-> {args.trace}")
    if args.metrics_file:
        from repro.obs import aggregate as OA
        body = (OA.render_snapshot(router.fleet_snapshot())
                if router is not None else engine.render_metrics())
        with open(args.metrics_file, "w") as f:
            f.write(body)
        print(f"metrics exposition -> {args.metrics_file}")
    if args.metrics_snapshot:
        from repro.obs import aggregate as OA
        snap = (router.fleet_snapshot() if router is not None
                else engine.snapshot_metrics())
        OA.save_snapshot(snap, args.metrics_snapshot)
        print(f"metrics snapshot -> {args.metrics_snapshot}")
    if args.decision_log:
        OD.log.write_jsonl(args.decision_log)
        OD.log.disable()
        print(f"decision log: {len(OD.log.records)} records "
              f"-> {args.decision_log}")
    if metrics_srv is not None:
        metrics_srv.shutdown()

    if args.check and args.temperature == 0.0:
        ok = True
        for r in reqs:
            prompts = jnp.asarray([r.prompt], jnp.int32)
            ref = naive_generate(cfg, params, prompts,
                                 gen_tokens=r.max_new_tokens,
                                 cache_kind=plan.cache_kind)
            ref_toks = [int(t) for t in ref[0, len(r.prompt):]]
            got = results[r.request_id].out_tokens
            match = got == ref_toks
            ok &= match
            print(f"{r.request_id}: P={len(r.prompt)} "
                  f"{'MATCH' if match else f'MISMATCH {got} != {ref_toks}'}")
        if not ok:
            raise SystemExit("engine output differs from naive baseline")
        print("all requests match the naive per-request baseline exactly")


if __name__ == "__main__":
    main()
