"""Fixtures for the benchmark's CPU tests: a tiny copy of the benchmark.

``tiny_bench`` writes a BENCHMARK.json with one small training cell and
two small serving cells (the second judged on its time to first token,
with the drain after the window), their configurations, mixes and
limits, and
copies the real plain reference and metric readers, all under
``tmp_path``. The harness runs such a copy exactly as it runs the real
one, with the look for a chip skipped.
"""

from __future__ import annotations

import json
import shutil
import sys
import time
from argparse import Namespace
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_MODEL = {
    "family": "decoder", "n_layers": 2, "d_model": 64, "n_heads": 2,
    "n_kv_heads": 2, "head_dim": 32, "d_ff": 128, "vocab": 64,
    "norm": "ln", "dtype": "float32", "remat": False,
}
TINY_LRA = dict(TINY_MODEL, causal=False, act="gelu", gated_mlp=False,
                pos_embed="learned", max_seq_len=64, tie_embeddings=True)
TINY_LM = dict(TINY_MODEL, causal=True, act="silu", gated_mlp=True,
               pos_embed="rope", rope_theta=10000.0, max_seq_len=256,
               tie_embeddings=False)
OPTIMIZER = {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
             "weight_decay": 0.1, "grad_clip": 1.0, "warmup_steps": 100,
             "total_steps": 10000}


def write(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1))


def make_tiny_bench(root: Path, *, train_limits=None, serve_limits=None):
    base = root / "bench"
    shutil.copytree(ROOT / "bench" / "metrics", base / "metrics")
    (base / "configs").mkdir(parents=True)
    shutil.copy(ROOT / "bench" / "configs" / "taylor_lm.py",
                base / "configs" / "taylor_lm.py")
    write(base / "configs" / "tiny-lra.json",
          {"name": "tiny-lra", "model": TINY_LRA, "optimizer": OPTIMIZER,
           "reference": "taylor_lm"})
    write(base / "configs" / "tiny-lm.json",
          {"name": "tiny-lm", "model": TINY_LM, "reference": "taylor_lm"})
    write(base / "traffic" / "tiny-train.json",
          {"kind": "train", "seq_len": 64, "global_batch": 4,
           "kernels": False, "check_steps": 3})
    write(base / "traffic" / "tiny-serve.json",
          {"kind": "serve",
           "arrival": {"process": "poisson", "rate_per_s": 4.0},
           "prompt_tokens": {"dist": "lognormal", "median": 20,
                             "sigma": 0.5, "min": 8, "max": 40},
           "output_tokens": {"dist": "uniform", "min": 4, "max": 12},
           "engine": {"n_slots": 2, "cache_kind": "taylor",
                      "prefill_chunk": 16, "token_budget": 32},
           "check": {"min_tokens": 20, "max_requests": 4}})
    write(base / "traffic" / "tiny-prefill.json",
          {"kind": "serve",
           "arrival": {"process": "poisson", "rate_per_s": 3.0},
           "prompt_tokens": {"dist": "exponential", "median": 40,
                             "min": 1, "max": 96},
           "output_tokens": {"dist": "exponential", "median": 3,
                             "min": 1, "max": 8},
           "schedule_seed": 0,
           "engine": {"n_slots": 2, "cache_kind": "taylor",
                      "prefill_chunk": 16, "token_budget": 32},
           "check": {"min_tokens": 12, "max_requests": 6},
           "drain_s": 60, "settle_trace": True})
    write(base / "limits" / "tiny-prefill.json", serve_limits or
          {"logit_gap": 1e-3})
    write(base / "limits" / "tiny-train.json", train_limits or
          {"loss_gap": 1e-3, "grad_gap": 1e-2, "update_gap": 1e-2})
    write(base / "limits" / "tiny-serve.json", serve_limits or
          {"logit_gap": 1e-3})
    def e2e(name, unit, better, cells):
        return {"name": name, "unit": unit, "better": better, "bound": 0.05,
                "source": "host_clock", "workloads": cells}

    def layer(name, unit, layer_name, moves, cell):
        return {"name": name, "unit": unit, "better": "higher",
                "source": "host_clock", "layer": layer_name, "moves": moves,
                "workloads": [cell]}

    bench = {
        "command": ["python3", "bench/run.py"],
        "paths": ["bench", "tests/bench"],
        "run_seconds": 2,
        "configs": [],
        "workloads": [
            {"name": "tiny-train", "config": "tiny-lra",
             "traffic": "tiny-train", "chips": 1, "why": "test"},
            {"name": "tiny-serve", "config": "tiny-lm",
             "traffic": "tiny-serve", "chips": 1, "why": "test"},
            {"name": "tiny-prefill", "config": "tiny-lm",
             "traffic": "tiny-prefill", "chips": 1, "why": "test"}],
        "end_to_end": [
            e2e("train_tokens_per_s", "tokens/s", "higher", ["tiny-train"]),
            e2e("serve_gen_tokens_per_s", "tokens/s", "higher",
                ["tiny-serve"]),
            e2e("itl_p95_ms", "ms", "lower", ["tiny-serve", "tiny-prefill"]),
            e2e("ttft_p90_s", "s", "lower", ["tiny-prefill"]),
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            layer("mfu.train", "%", "model step", "train_tokens_per_s",
                  "tiny-train"),
            layer("decode_step_ms", "ms", "engine", "serve_gen_tokens_per_s",
                  "tiny-serve"),
            layer("prefill_ms_per_1k_tokens", "ms", "model step",
                  "ttft_p90_s", "tiny-prefill")],
    }
    write(root / "BENCHMARK.json", bench)
    return root / "BENCHMARK.json"


@pytest.fixture
def tiny_bench(tmp_path):
    return make_tiny_bench(tmp_path)


def run_cell(bench_path: Path, cell: str, *, seed=5, seconds=2, trace=0,
             calibrate=0):
    from bench.harness import execute

    args = Namespace(workload=cell, seed=seed, seconds=seconds, trace=trace,
                     calibrate=calibrate)
    return execute(args, time.time(), bench_path=bench_path,
                   require_chip=False)
