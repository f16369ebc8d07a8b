"""Serving cells: an open loop of requests through ``serve.Engine``.

Set-up makes the weights from the seed, builds the engine as the mix
configures it, and warms every shape the window can use: prefill chunks
of every power-of-two length up to the engine's chunk, each in the
pooled path (two slots together) and the singleton path (one slot), as
the last chunk of a prompt and as an inner one, and the decode step.

The window then submits each request when it is due (the generator and
the engine share this one thread; how late a submission ran is
recorded) and calls ``Engine.step()`` until the window's time is up. A
token counts as emitted when the step that produced it returns.
Metrics:

- ``serve_gen_tokens_per_s``: tokens emitted by steps that ended in the
  window, over the window (first due time to the end of the last step);
- ``itl_p95_ms``: 95th percentile of every gap between consecutive
  tokens of one request whose later token was emitted in the window;
- ``ttft_p90_s``, where the mix has ``drain_s``: 90th percentile, over
  the requests due in the window, of the time from each one's due time
  to its first token. After the window the engine is stepped, with no
  new submissions, until each of them has its first token or
  ``drain_s`` has passed; a request refused or not served by then ranks
  at the time from the first due time to the end of that drain, which
  no served request's time exceeds. The drain comes after the window,
  so no other metric counts it.

Each step's wall time and the main thread's CPU time are kept, and the
slowest steps are printed with the garbage collection that ran inside
them, so that a stall on the host can be told apart from the device.

Correctness: after the window the engine is freed and a sample of the
finished requests, drawn from the seed and always holding the one with
the most tokens, is run once through the plain reference over its
prompt and its served tokens. The number compared is the widest gap by
which a served token's reference logit lies below the reference's best
at that position (every request is greedy).
"""

from __future__ import annotations

import gc
import time
from collections import deque

import jax
import numpy as np

from bench import traffic_gen
from bench.weights import make_params

def build(run):
    from repro.models import model as M
    from repro.serve import Engine, EngineConfig

    cfg = run.program_config()
    shapes = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    params = make_params(shapes, seed=run.seed, d_model=cfg.d_model,
                         init=run.config.get("init"))
    econf = EngineConfig(**run.traffic["engine"])
    econf.max_seq_len = max(traffic_gen.longest_request(run.traffic),
                            2 * econf.prefill_chunk + 2)
    return cfg, params, Engine(cfg, params, econf)


def warm_lengths(chunk: int) -> list[int]:
    """Prompt lengths whose chunk plans hold every power-of-two chunk up
    to ``chunk``, inside a prompt (2 chunk - 1) and as its last chunk."""
    out, c = [2 * chunk - 1], chunk
    while c >= 2:
        out.append(c)
        c //= 2
    return out


def warm_up(engine, vocab: int) -> int:
    """Drive every prefill and decode shape the window can meet."""
    from repro.serve import Request

    rng = np.random.default_rng(0)
    n = 0
    lengths = warm_lengths(engine.econf.prefill_chunk)

    def prompt(length):
        return rng.integers(0, vocab, length, dtype=np.int32).tolist()

    groups = [[length, length] for length in lengths]
    groups += [[length] for length in lengths]
    for group in groups:
        for length in group:
            engine.submit(Request(f"warm{n}", prompt(length),
                                  max_new_tokens=3, temperature=0.0))
            n += 1
        while not engine.idle:
            engine.step()
    for rid in list(engine.results):
        engine.pop_result(rid)
    engine.reset_metrics()
    return n


class Window:
    """Bookkeeping of one window: submissions, steps and token times."""

    def __init__(self, arrivals):
        self.pending = deque(arrivals)
        self.due = {}                 # request id -> absolute due time
        self.lateness = []
        self.refused = []
        self.steps = []        # (t_start, t_end, decode, prefill, cpu_s)
        self.first = {}               # request id -> first token time
        self.times = {}               # request id -> token times
        self.tokens = {}              # request id -> emitted token ids
        self.requests = {}
        self.finished = set()

    def submit_due(self, engine, t0, now):
        from repro.serve import Request
        from repro.serve.request import QueueFullError

        while self.pending and t0 + self.pending[0].due_s <= now:
            a = self.pending.popleft()
            rid = f"r{a.index}"
            self.due[rid] = t0 + a.due_s
            self.requests[rid] = a
            try:
                engine.submit(Request(rid, a.prompt.tolist(),
                                      max_new_tokens=a.max_new_tokens,
                                      temperature=0.0))
            except QueueFullError:
                self.refused.append(rid)
            self.lateness.append(time.perf_counter() - (t0 + a.due_s))

    def step(self, engine, index):
        ts, cs = time.perf_counter(), time.thread_time()
        with jax.profiler.TraceAnnotation("bench.engine_step", step=index):
            m, events = engine.step()
        te = time.perf_counter()
        self.steps.append((ts, te, m.decode_tokens, m.prefill_tokens,
                           time.thread_time() - cs))
        for ev in events:
            self.times.setdefault(ev.request_id, []).append(te)
            self.tokens.setdefault(ev.request_id, []).append(ev.token)
            if ev.first:
                self.first[ev.request_id] = te
            if ev.finished:
                self.finished.add(ev.request_id)
                engine.pop_result(ev.request_id)


def settler(engine, traffic: dict):
    """Where the mix has ``"settle_trace": true``, a wait for the device
    (on the slot pool), so that the traced part opens and closes with the
    device idle and each program it holds was dispatched inside it; for
    any other mix None, and the traced part opens and closes as it falls."""
    if not traffic.get("settle_trace"):
        return None
    return lambda: jax.block_until_ready(engine.pool.cache)


def drive(engine, arrivals, run):
    """The measured window. Returns the Window."""
    w = Window(arrivals)
    t0 = time.perf_counter()
    t_end = t0 + run.seconds
    settle = settler(engine, run.traffic)
    step = 0
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        run.tracer.maybe_start(now - t0, settle)
        w.submit_due(engine, t0, now)
        if engine.idle:
            nxt = t0 + w.pending[0].due_s if w.pending else t_end
            with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                time.sleep(max(0.0, min(nxt, t_end) - now))
            continue
        w.step(engine, step)
        step += 1
    w.t0 = t0
    w.window_end = max(t_end, w.steps[-1][1]) if w.steps else t_end
    if run.tracer.active and settle is not None:
        settle()
    run.window_closed()
    return w


def drain(engine, w, cap_s: float) -> None:
    """Step on, with no new submissions, until every request due in the
    window has its first token, the engine is idle, or ``cap_s`` has
    passed."""
    t_cap = time.perf_counter() + cap_s
    refused = set(w.refused)
    waiting = [r for r in w.due if r not in refused]
    step = len(w.steps)
    while (any(r not in w.first for r in waiting) and not engine.idle
           and time.perf_counter() < t_cap):
        w.step(engine, step)
        step += 1
    w.drain_end = time.perf_counter()


def ttft_p90_s(w) -> tuple[float, int]:
    """90th percentile of due-to-first-token times, and the number of
    requests that never got one (each ranked above every served one)."""
    served = [w.first[r] - w.due[r] for r in w.due if r in w.first]
    unserved = len(w.due) - len(served)
    worst = w.drain_end - min(w.due.values())
    return float(np.percentile(served + [worst] * unserved, 90)), unserved


def metrics(w):
    t0, t1 = w.t0, w.window_end
    gen = sum(1 for ts in w.times.values() for t in ts if t0 <= t <= t1)
    itl = [b - a for ts in w.times.values() for a, b in zip(ts, ts[1:])
           if t0 <= b <= t1]
    out = {"serve_gen_tokens_per_s": gen / (t1 - t0)}
    if itl:
        out["itl_p95_ms"] = 1e3 * float(np.percentile(itl, 95))
    window = [s for s in w.steps if t0 <= s[1] <= t1]
    decode_only = [1e3 * (te - ts) for ts, te, d, p, _ in window
                   if d and not p]
    stats = {
        "requests_due": len(w.due), "refused": len(w.refused),
        "tokens_in_window": gen,
        "backlog_at_window_end": sum(1 for r in w.due
                                     if w.first.get(r, t1 + 1) > t1),
        "itl_samples": len(itl),
        "steps_in_window": len(window),
        "decode_only_steps": len(decode_only),
        "prefill_steps": sum(1 for s in window if s[3]),
        "window_s": t1 - t0,
        "generator_late_p50_ms": 1e3 * float(np.median(w.lateness))
        if w.lateness else 0.0,
        "generator_late_max_ms": 1e3 * max(w.lateness, default=0.0),
    }
    if decode_only:
        stats["decode_step_ms"] = float(np.median(decode_only))
    return out, stats


def window_flops(w, m, layer_flops=None):
    """Model FLOPs of the work that the window's steps finished."""
    from bench import flops as F

    total = 0
    for rid, times in w.times.items():
        a = w.requests[rid]
        p = len(a.prompt)
        for i, t in enumerate(times):
            if not (w.t0 <= t <= w.window_end):
                continue
            if i == 0:
                total += F.prefill_flops(m, 0, p, last_chunk=True,
                                         layer_flops=layer_flops)
            else:
                total += F.decode_flops(m, p + i - 1,
                                        layer_flops=layer_flops)
    return total


def check_sample(w, mix, seed):
    """Finished requests to compare: the longest, then others drawn from
    the seed until the sample holds ``min_tokens`` served tokens."""
    done = sorted(w.finished, key=lambda r: int(r[1:]))
    if not done:
        return []
    longest = max(done, key=lambda r: len(w.tokens[r]))
    rng = np.random.default_rng(traffic_gen.seed_sequence(seed, 3))
    rest = [done[i] for i in rng.permutation(len(done)) if done[i] != longest]
    want = mix["check"]
    sample, n_tok = [longest], len(w.tokens[longest])
    for r in rest:
        if n_tok >= want["min_tokens"] or len(sample) >= want["max_requests"]:
            break
        sample.append(r)
        n_tok += len(w.tokens[r])
    return sample


def logit_gaps(ref, params, m, w, sample, rounding):
    """Widest gap, below the float32 reference's best logit, of the token
    chosen at each served position: by the program (``rounding=None``)
    or by the reference at ``rounding`` (the control)."""
    worst, n = 0.0, 0
    for rid in sample:
        prompt = w.requests[rid].prompt
        out = np.asarray(w.tokens[rid], np.int32)
        seq = np.concatenate([prompt, out[:-1]])
        pos = np.arange(len(prompt) - 1, len(seq))
        lg = np.asarray(ref.logits_at(params, m, seq, pos))
        chosen = out
        if rounding is not None:
            chosen = np.asarray(
                ref.logits_at(params, m, seq, pos, rounding=rounding)
            ).argmax(-1)
        gap = lg.max(-1) - lg[np.arange(len(chosen)), chosen]
        worst = max(worst, float(gap.max()))
        n += len(chosen)
    return worst, n


def run(run):
    cfg, params, engine = build(run)
    warmed = warm_up(engine, cfg.vocab)
    arrivals = traffic_gen.serve_requests(run.traffic, seed=run.seed,
                                          seconds=run.seconds,
                                          vocab=cfg.vocab)
    run.setup_done()
    w = drive(engine, arrivals, run)
    e2e, stats = metrics(w)
    stats["slowest_steps"] = run.slowest([(ts, te, cpu) for ts, te, _, _, cpu
                                          in w.steps if te <= w.window_end],
                                         w.t0)
    stats["warm_requests"] = warmed
    failed = len(w.refused)
    if "drain_s" in run.traffic:
        drain(engine, w, run.traffic["drain_s"])
        e2e["ttft_p90_s"], unserved = ttft_p90_s(w)
        failed = unserved
        stats["drain_s"] = w.drain_end - w.window_end
        stats["unserved"] = unserved
    run.memory_peak()
    ref = run.reference()
    run.flops_in_window = window_flops(w, run.model,
                                       getattr(ref, "layer_flops", None))
    del engine
    gc.collect()

    t_check = time.perf_counter()
    sample = check_sample(w, run.traffic, run.seed)
    gap, n_tok = logit_gaps(ref, params, run.model, w, sample, None)
    stats["check_requests"] = len(sample)
    stats["check_tokens"] = n_tok
    stats["check_s"] = time.perf_counter() - t_check
    checks = {"logit_gap": gap}
    controls = {}
    if run.calibrate:
        controls["control_fp8"] = {"logit_gap": logit_gaps(
            ref, params, run.model, w, sample, "fp8")[0]}
    kinds = {i: ("prefill" if s[3] else "decode")
             for i, s in enumerate(w.steps)}
    return {"e2e": e2e, "stats": stats, "checks": checks,
            "controls": controls,
            "attempted": len(w.due), "failed": failed,
            "ok": bool(sample) and n_tok > 0,
            "step_kinds": kinds}
