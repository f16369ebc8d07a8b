"""granite-4.0-h (Mamba2 and Taylor GQA layers, an MLP after every mixer,
the muP multipliers) through the engine's normal path against its plain
reference, ``bench/configs/granite_hybrid.py``, at a tiny granite-shaped
size on seeded weights; and the configuration as the harness builds and
counts it."""

import json

import jax
import numpy as np
import pytest

from conftest import ROOT
from bench import flops as F
from bench.harness import Run, load_module
from bench.weights import make_params

DATA = ROOT / "tests" / "bench" / "data"
REF = load_module(ROOT / "bench" / "configs" / "granite_hybrid.py")
# Both sides compute in float32. The engine absorbs Mamba2 in chunked SSD
# form and attention in the Taylor state's efficient form, the reference
# runs the per-token recurrence and the direct form, so they differ by
# float32 rounding in a different order: ~1e-6 of the logits' scale here.
# A state not handed from one chunk to the next, or the gate put after
# the norm, moves the logits by more than a tenth of that scale.
TOL = 1e-4


def granite_config() -> dict:
    return json.loads((DATA / "tiny-granite.json").read_text())


def build(config: dict, seed: int = 2**31 + 7):
    from repro.models import model as M

    run = Run({}, {"name": "t"}, config, {}, 0, 1, False, False, 0.0, 1)
    cfg = run.program_config()
    shapes = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    return cfg, make_params(shapes, seed=seed, d_model=cfg.d_model,
                            init=config.get("init"))


def recording_engine(cfg, params, **econf):
    """An engine whose prefill and decode programs record, for every row
    they compute for a live request, (request, first position, logits)."""
    from repro.serve import Engine, EngineConfig

    eng = Engine(cfg, params, EngineConfig(**econf))
    rows = []

    def prefill_rows(logits, slots):
        for row, slot in slots:
            s = eng._slots[slot]
            rows.append((s.request_id, s.consumed, np.asarray(logits[row])))

    pool, single, decode = (eng._pool_prefill_fn, eng._slot_prefill_fn,
                            eng._decode_fn)

    def pool_fn(toks, mask, cache):
        out = pool(toks, mask, cache)
        prefill_rows(out[0], [(i, i) for i in np.flatnonzero(mask)])
        return out

    def single_fn(toks, cache, slot):
        out = single(toks, cache, slot)
        prefill_rows(out[0], [(0, int(slot))])
        return out

    def decode_fn(toks, mask, cache):
        out = decode(toks, mask, cache)
        for i in np.flatnonzero(mask):
            s = eng._slots[i]
            at = len(s.request.prompt) + len(s.out_tokens) - 1
            rows.append((s.request_id, at, np.asarray(out[0][i])))
        return out

    eng._pool_prefill_fn, eng._slot_prefill_fn = pool_fn, single_fn
    eng._decode_fn = decode_fn
    return eng, rows


def serve_against_reference(cfg, params, config):
    """Serve three requests (two prefilled together in the pooled path,
    one alone in the singleton path, all then decoding in one batch) and
    return the largest gap of any recorded logit row from the reference's
    logits at its positions, over the reference's largest logit."""
    from repro.serve import Request

    eng, rows = recording_engine(cfg, params, n_slots=3, prefill_chunk=8,
                                 token_budget=64, max_seq_len=64)
    rng = np.random.default_rng(0)
    prompts = {"a": 21, "b": 21, "c": 11}
    prompts = {r: rng.integers(0, cfg.vocab, n).tolist()
               for r, n in prompts.items()}
    eng.submit(Request("a", prompts["a"], max_new_tokens=6))
    eng.submit(Request("b", prompts["b"], max_new_tokens=6))
    eng.step()
    eng.submit(Request("c", prompts["c"], max_new_tokens=6))
    for _ in eng.run():
        pass
    worst, scale = 0.0, 0.0
    for rid, prompt in prompts.items():
        fed = prompt + eng.results[rid].out_tokens[:-1]
        ref = np.asarray(REF.logits_at(params, config["model"], fed,
                                       np.arange(len(fed))))
        scale = max(scale, float(np.abs(ref).max()))
        for r, at, lg in rows:
            if r == rid:
                worst = max(worst, float(np.abs(lg - ref[at:at + len(lg)])
                                         .max()))
    return worst / scale, rows


@pytest.fixture(scope="module")
def granite():
    config = granite_config()
    cfg, params = build(config)
    return config, cfg, params


def test_engine_logits_match_reference(granite):
    config, cfg, params = granite
    gap, rows = serve_against_reference(cfg, params, config)
    # the pooled, singleton and decode programs all ran
    assert {len(lg) for _, _, lg in rows} >= {8, 1}
    assert gap < TOL, gap


@pytest.mark.parametrize("fault", ["state_dropped", "gate_after_norm"])
def test_planted_fault_fails_the_comparison(granite, monkeypatch, fault):
    """The tolerance above is tight enough to catch each fault."""
    from repro.models import layers as L
    from repro.models import mamba2 as M2

    config, cfg, params = granite
    if fault == "state_dropped":
        mixer = M2._mixer
        monkeypatch.setattr(M2, "_mixer", lambda p, c, x, tail, h0: mixer(
            p, c, x, jax.numpy.zeros_like(tail), None))
    else:
        monkeypatch.setattr(M2, "_gated_norm", lambda p, c, y, z, dt: (
            L.rmsnorm(p["norm"], y, c.rms_norm_eps)
            * jax.nn.silu(z)).astype(dt))
    gap, _ = serve_against_reference(cfg, params, config)
    assert gap > 10 * TOL, gap


def test_published_config_builds_the_preset():
    """The benchmark's configuration file and the registry's preset are
    one model, and the file keeps the catalog's numbers."""
    from repro.configs import get_config

    config = json.loads((ROOT / "bench" / "configs" /
                         "granite-4.0-h-micro.json").read_text())
    run = Run({}, {"name": "t"}, config, {}, 0, 1, False, False, 0.0, 1)
    assert run.program_config() == get_config("granite-4.0-h-micro")
    pub = config["published"]
    m = config["model"]
    assert (m["n_layers"], m["d_model"], m["d_ff"], m["vocab"]) == (
        pub["num_hidden_layers"], pub["hidden_size"],
        pub["intermediate_size"], pub["vocab_size"])
    kinds = ["global" if t == "attention" else t for t in pub["layer_types"]]
    assert F.layer_kinds(m) == kinds
    assert (m["embedding_multiplier"], m["residual_multiplier"],
            m["logits_divisor"], m["rms_norm_eps"]) == (
        pub["embedding_multiplier"], pub["residual_multiplier"],
        pub["logits_scaling"], pub["rms_norm_eps"])
    assert {k: config[k] for k in pub} == pub
    assert config["reduced"] == []


def test_tiny_config_builds_from_its_rules(granite):
    config, cfg, params = granite
    assert cfg.layer_pattern == ("mamba", "mamba", "global")
    mamba = params["groups"][0]["mamba"]
    assert mamba["conv_b"].shape == (8, 2 * 32 + 2 * 8)   # 8 periods
    assert np.all(np.asarray(mamba["dt_bias"]) == np.float32(-4.6))
    assert np.allclose(np.exp(np.asarray(mamba["A_log"][0])),
                       np.linspace(1, 16, 8), rtol=1e-6)
    assert set(params["groups"][0]) == {"norm1", "mamba", "norm2", "mlp"}


def test_layer_flops_against_a_hand_count():
    """One token through one published Mamba2 layer and its MLP."""
    m = json.loads((ROOT / "bench" / "configs" /
                    "granite-4.0-h-micro.json").read_text())["model"]
    in_proj = 2 * 2048 * (4096 + 4096 + 2 * 128 + 64)   # z, x, B and C, dt
    out_proj = 2 * 4096 * 2048
    conv = 2 * 4 * (4096 + 2 * 128)
    ssd = 2 * 64 * 128 * 64 + 2 * 64 * 128 * 64        # update, read-out
    mlp = 2 * 3 * 2048 * 8192
    per_token = in_proj + out_proj + conv + ssd + mlp
    assert per_token == 154_437_632
    assert REF.layer_flops(m, "mamba", 0, 1) == per_token
    assert REF.layer_flops(m, "mamba", 5000, 128) == 128 * per_token
    # the whole model for one decoded token: 36 such layers, 4 global
    # layers that bench/flops.py counts itself, and the read-out
    attn = F.attn_flops_causal(99, 1, 64, 32, 8)
    dense = 2 * (2048 * 2048 * 2 + 2048 * 512 * 2 + 3 * 2048 * 8192)
    assert F.decode_flops(m, 99, REF.layer_flops) == (
        36 * per_token + 4 * (dense + attn) + 2 * 2048 * 100352)


@pytest.fixture(scope="module")
def hybrid_bench(tmp_path_factory):
    """The tiny benchmark with a tiny granite cell beside its own."""
    import shutil

    from conftest import make_tiny_bench, write

    root = tmp_path_factory.mktemp("tiny-hybrid")
    path = make_tiny_bench(root)
    shutil.copy(ROOT / "bench" / "configs" / "granite_hybrid.py",
                root / "bench" / "configs" / "granite_hybrid.py")
    write(root / "bench" / "configs" / "tiny-granite.json", granite_config())
    mix = json.loads((root / "bench" / "traffic" / "tiny-prefill.json")
                     .read_text())
    write(root / "bench" / "traffic" / "tiny-hybrid.json",
          dict(mix, engine=dict(mix["engine"], n_slots=3),
               output_tokens={"dist": "uniform", "min": 8, "max": 24},
               check={"min_tokens": 80, "max_requests": 6}))
    write(root / "bench" / "limits" / "tiny-hybrid.json", {"logit_gap": 1e-3})
    bench = json.loads(path.read_text())
    bench["workloads"].append({"name": "tiny-hybrid",
                               "config": "tiny-granite",
                               "traffic": "tiny-hybrid", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] in ("itl_p95_ms", "ttft_p90_s"):
            m["workloads"].append("tiny-hybrid")
    write(path, bench)
    return path


def test_hybrid_cell_is_correct_and_its_control_is_not(hybrid_bench):
    from conftest import run_cell

    out = run_cell(hybrid_bench, "tiny-hybrid", seed=2**31 + 31, seconds=2,
                   calibrate=1)
    line = out["line"]
    assert line["correct"] is True, out["checks"]
    assert line["failed"] == 0 and out["stats"]["unserved"] == 0
    assert set(line["metrics"]) == {"ttft_p90_s", "itl_p95_ms", "setup_s"}
    assert out["controls"]["control_fp8"]["correct"] is False
    assert out["stats"]["compiles_in_window"] == 0


def test_decode_state_reader_sums_the_span_args(monkeypatch):
    from types import SimpleNamespace

    from bench import trace_reduce as TR

    reader = load_module(ROOT / "bench" / "metrics" /
                         "decode_state_mib.serve_hybrid.py")
    E = TR.Event
    state = (("ssm_state_bytes", 3 * 2**20), ("taylor_state_bytes", 2**20))
    threads = [[E("engine_step", 0, 100),
                E("decode_batch", 1, 2, args=(("slots", 2),) + state),
                E("decode_batch", 3, 4, args=(("slots", 8),) + state),
                E("prefill_batch", 5, 6, args=(("ssm_state_bytes", 1),
                                               ("taylor_state_bytes", 1)))]]
    monkeypatch.setattr(reader.S, "load", lambda r: threads)
    assert reader.read(SimpleNamespace()) == 4.0
    # a program whose spans carry no state arguments reports nothing
    bare = [[E("decode_batch", 1, 2, args=(("slots", 2),))]]
    monkeypatch.setattr(reader.S, "load", lambda r: bare)
    assert reader.read(SimpleNamespace()) is None
