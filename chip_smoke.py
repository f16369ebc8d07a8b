"""Bring-up smoke run of the main paths on a TPU, through their entry points.

    python chip_smoke.py              # one chip: serve + train phases
    python chip_smoke.py --chips 4    # four chips: seq-parallel training only

One process, no children. Phases, in order (one chip):

1. serve: stablelm-1.6b at its published widths (24L, d_model 2048,
   32 heads x 64, d_ff 5632, vocab 100352, bf16, seeded random weights)
   through ``serve.Engine``, driven by ``launch/serve.py``'s
   ``mixed_arrival_workload`` + ``run_workload``: 4 requests of ~512
   prompt tokens and 32 generated tokens over 2 slots. Then the logits of
   chunked prefill + 8 decode steps, through the functions the engine
   jits, are compared with ``models/model.py:forward`` in float32.
2. train: taylorshift-lra at paper widths (4L, d_model 512, 8 x 64)
   through ``launch/train.py:train()`` on the single-device mesh with the
   Pallas kernels on: 3 steps at N=2048 (direct kernels), then 3 at
   N=8192 (efficient kernel and its backward). The loss must be finite
   and the compiled step must hold ``tpu_custom_call``.

``--chips 4`` runs only taylorshift-lra at N=8192 on a 4-way ``seq``
mesh (jnp attention: the kernels are gated off on a multi-device mesh)
against the same batches on one device.

The times printed are smoke numbers, not a benchmark. The last line of
stdout is ``{"ok": true, "device": {...}}`` only if every phase passed;
without a TPU the script exits non-zero before any phase.
"""

from __future__ import annotations

import argparse
import gc
import json
import logging
import math
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent

# Serve check: the engine runs bf16 weights and activations, the
# reference float32 weights at "highest" matmul precision. bf16 keeps 8
# mantissa bits and the residual stream rounds once per layer. At this
# model's depth (24 layers, width 1024, on the CPU) bf16 logits of O(1)
# were off by at most 0.054, and weights rounded to fp8 (e4m3) by 0.188.
# On the TPU, float32 matmuls of the Taylor state run at one bf16 pass by
# default, which adds rounding of the same order. 0.15 passes bf16 with
# room for that and fails a precision step below it.
LOGIT_TOL = 0.15
# Four-chip check, relative to the one-device loss: the seq mesh runs
# jnp attention sharded 4 ways, the one-device run the Pallas kernels,
# both with bf16 activations, from identical weights and batches. Each
# bf16 value is off by at most 2^-8 = 3.9e-3 relative, and the loss is
# a mean over 4 x 8192 token losses whose rounding errors largely
# cancel, so half that bounds the gap over 3 steps with room to spare.
LOSS_RTOL = 2e-3


def _print(*a):
    print(*a, flush=True)


def _peak_bytes(dev) -> int | None:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def serve_phase(cfg, *, n_requests=4, prompt_len=512, gen=32, slots=2,
                chunk=128, n_decode_check=8, seed=0) -> dict:
    """Serve ``cfg`` through the engine and check its logits."""
    from repro.launch.serve import mixed_arrival_workload, run_workload
    from repro.models import model as M
    from repro.serve import Engine, EngineConfig

    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    engine = Engine(cfg, params, EngineConfig(
        n_slots=slots, prefill_chunk=chunk, token_budget=2 * chunk,
        max_seq_len=prompt_len + gen + 1, cache_kind="taylor"))
    reqs, arrivals = mixed_arrival_workload(cfg, n_requests, prompt_len,
                                            gen, seed=seed + 1)
    n_prompt = sum(len(r.prompt) for r in reqs)

    # the same workload twice: the first run compiles every shape, the
    # second replays it on the warm engine (no step recompiles)
    walls = []
    for _ in range(2):
        t0 = time.perf_counter()
        start = engine.step_idx
        results = run_workload(engine, reqs,
                               [a + start for a in arrivals])
        walls.append(time.perf_counter() - t0)
        done = {rid: len(s.out_tokens) for rid, s in results.items()}
        if sorted(done.values()) != [gen] * n_requests:
            raise AssertionError(f"requests did not finish with {gen} "
                                 f"tokens each: {done}")
        for r in reqs:
            engine.pop_result(r.request_id)

    # logits: chunked prefill into slot 0 of the engine's (now free) pool,
    # then teacher-forced decode steps, through the engine's own jits
    n_check = prompt_len + n_decode_check
    toks = jax.random.randint(jax.random.PRNGKey(seed + 7), (n_check,), 0,
                              cfg.vocab, jnp.int32)
    cache, slot = engine.pool.cache, 0
    got = []
    for c in range(0, prompt_len, chunk):
        lg, cache, _ = engine._slot_prefill_fn(toks[None, c:c + chunk],
                                               cache, jnp.int32(slot))
        got.append(lg[0].astype(jnp.float32))
    mask = jnp.arange(slots) == slot
    for i in range(prompt_len, n_check):
        step = jnp.zeros((slots, 1), jnp.int32).at[slot, 0].set(toks[i])
        lg, cache = engine._decode_fn(step, mask, cache)
        got.append(lg[slot].astype(jnp.float32))
    got = jnp.concatenate(got, axis=0)
    peak = _peak_bytes(jax.devices()[0])
    del engine, cache, lg
    gc.collect()    # the engine's jitted closures hold it in a cycle

    ref_cfg = cfg.with_(dtype="float32")
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)

    @jax.jit
    def reference(p, t):
        hidden, _ = M.forward(p, ref_cfg, {"tokens": t})
        return M.logits_from_hidden(p, ref_cfg, hidden)[0]

    with jax.default_matmul_precision("highest"):
        ref = reference(params, toks[None])
    diff = float(jnp.max(jnp.abs(got - ref)))
    scale = float(jnp.max(jnp.abs(ref)))
    n_gen = n_requests * gen
    return {
        "requests_done": n_requests,
        "first_run_s": walls[0], "warm_run_s": walls[1],
        "compile_s_est": walls[0] - walls[1],
        "gen_tok_per_s": n_gen / walls[1],
        "all_tok_per_s": (n_gen + n_prompt) / walls[1],
        "logit_max_abs_diff": diff, "logit_tol": LOGIT_TOL,
        "ref_logit_max_abs": scale,
        "peak_bytes_in_use": peak,
        "ok": diff < LOGIT_TOL,
    }


def train_phase(cfg, *, seq_len: int, batch=4, steps=3, mesh=None,
                need_kernels=True) -> dict:
    """``steps`` training steps of ``cfg`` through ``launch/train.py``."""
    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import TrainObs, train
    from repro.models import backend as B

    cfg = cfg.with_(max_seq_len=max(cfg.max_seq_len, seq_len))
    cfg = B.configure_for_training(cfg)
    obs = TrainObs()
    t0 = time.perf_counter()
    out = train(cfg, steps=steps, global_batch=batch, seq_len=seq_len,
                mesh=mesh or make_local_mesh(), obs=obs, log_every=1)
    wall = time.perf_counter() - t0
    losses = [float(x) for x in out["losses"]]
    tok_per_s = obs.tokens_per_sec.value      # steps after the first
    step_s = batch * seq_len / tok_per_s
    kernels = int(obs.kernel_calls.value)
    ok = (len(losses) == steps and all(math.isfinite(x) for x in losses)
          and (kernels > 0 or not need_kernels))
    return {"seq_len": seq_len, "batch": batch, "losses": losses,
            "wall_s": wall, "step_s": step_s,
            "setup_and_compile_s_est": wall - steps * step_s,
            "tok_per_s": tok_per_s,
            "kernel_calls": kernels, "ok": ok}


def one_chip() -> bool:
    from repro.configs import get_config

    dev = jax.devices()[0]
    serve = serve_phase(get_config("stablelm-1.6b"))
    _print("smoke serve stablelm-1.6b:", json.dumps(serve))
    lra = get_config("taylorshift-lra")
    train_results = [train_phase(lra, seq_len=n) for n in (2048, 8192)]
    for r in train_results:
        _print("smoke train taylorshift-lra:", json.dumps(r))
    _print("smoke peak_bytes_in_use:", _peak_bytes(dev))
    return serve["ok"] and all(r["ok"] for r in train_results)


def four_chips() -> bool:
    from repro.configs import get_config
    from repro.launch.mesh import make_seq_mesh

    devs = jax.devices()
    if len(devs) < 4:
        raise SystemExit(f"--chips 4 needs 4 devices, found {len(devs)}")
    lra = get_config("taylorshift-lra")
    seq = train_phase(lra, seq_len=8192, mesh=make_seq_mesh(4),
                      need_kernels=False)
    mem = [{"id": d.id, "bytes_in_use": (d.memory_stats() or {}).get(
        "bytes_in_use"), "peak_bytes_in_use": _peak_bytes(d)}
        for d in devs]
    one = jax.sharding.Mesh(np.asarray(devs[:1]).reshape(1, 1),
                            ("data", "model"))
    ref = train_phase(lra, seq_len=8192, mesh=one)
    gap = max(abs(a - b) / abs(b)
              for a, b in zip(seq["losses"], ref["losses"]))
    _print("smoke train taylorshift-lra seq x4:", json.dumps(seq))
    _print("smoke train taylorshift-lra one device:", json.dumps(ref))
    _print("smoke per-device memory after seq x4:", json.dumps(mem))
    _print("smoke loss max relative gap:", gap, "tol:", LOSS_RTOL)
    return seq["ok"] and ref["ok"] and gap < LOSS_RTOL


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    cache_dir = Path(enable_compile_cache())

    def entries() -> int:
        return len(list(cache_dir.glob("*"))) if cache_dir.is_dir() else 0

    _print(f"smoke device_kind={dev.device_kind} count={len(jax.devices())}"
           f" compile_cache={cache_dir} entries_at_start={entries()}")
    ok = four_chips() if args.chips == 4 else one_chip()
    _print(f"smoke compile_cache entries_at_end={entries()}")
    if not ok:
        _print("smoke: a phase failed its check")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
