"""Configurations with SSM and expert layers build through the harness:
nested sections become the program's dataclasses, leaves the built-in
table does not name take the configuration's ``init`` rules, and the
FLOP count raises on a layer kind nobody counts. The weights of the
committed configurations are those the first benchmark drew."""

import hashlib
import json

import jax
import numpy as np
import pytest

from conftest import ROOT
from bench import flops as F
from bench.harness import Run
from bench.weights import make_params

DATA = ROOT / "tests" / "bench" / "data"
SMALL = {"n_layers": 2, "d_model": 64, "n_heads": 2, "n_kv_heads": 2,
         "head_dim": 32, "d_ff": 128, "vocab": 64, "max_seq_len": 64}
# sha256 over (path, bytes) of every leaf, seed 2**31 + 77, computed with
# the weights module as the first benchmark had it
DIGESTS = {
    "stablelm-1.6b":
        "f302d514d22162e9afade2cb6c3bc193d6c3cabb9c9f60b3337e9f626a8de222",
    "taylorshift-lra":
        "00d82abfc9d75d84d62c8c8b3f072ac60ca30d6ec6a07beeacf232c262630826",
}


def run_for(config: dict) -> Run:
    return Run({}, {"name": "t"}, config, {}, 0, 1, False, False, 0.0, 1)


def tiny(name: str) -> dict:
    return json.loads((DATA / f"{name}.json").read_text())


def build(config: dict, seed: int = 2**31 + 77):
    from repro.models import model as M

    cfg = run_for(config).program_config()
    shapes = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    return cfg, make_params(shapes, seed=seed, d_model=cfg.d_model,
                            init=config.get("init"))


def digest(params) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(DIGESTS))
def test_committed_weights_unchanged(name):
    config = json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                        .read_text())
    config["model"] = dict(config["model"], **SMALL)
    _, params = build(config)
    assert digest(params) == DIGESTS[name]


def test_mamba_config_builds_from_its_rules():
    from repro.configs.base import SSMConfig

    cfg, params = build(tiny("tiny-mamba"))
    assert cfg.layer_pattern == ("mamba", "global")
    assert cfg.ssm == SSMConfig(state=16, head_dim=16, expansion=2,
                                conv_width=4, n_groups=1, chunk=8)
    mamba = params["groups"][0]["mamba"]
    heads = 2 * 64 // 16
    want = np.log(np.linspace(1.0, 16.0, heads, dtype=np.float32))
    a_log = np.asarray(mamba["A_log"])
    assert a_log.shape == (2, heads)          # two periods, stacked
    np.testing.assert_allclose(a_log, np.broadcast_to(want, a_log.shape),
                               rtol=1e-6)
    assert np.all(np.asarray(mamba["D"]) == 1.0)
    assert np.all(np.asarray(mamba["dt_bias"]) == 0.0)
    conv = np.asarray(mamba["conv_w"])
    assert 0.05 < conv.std() < 0.15


def test_moe_config_builds_from_its_rules():
    from repro.configs.base import MoEConfig, TaylorConfig

    cfg, params = build(tiny("tiny-moe"))
    assert cfg.moe == MoEConfig(n_experts=4, top_k=2, capacity_factor=2.0,
                                n_shared_experts=1)
    assert cfg.taylor == TaylorConfig(chunk=16)
    moe = params["groups"][0]["moe"]
    for leaf, fan_in in (("w_up", 64), ("w_gate", 64), ("w_down", 128)):
        x = np.asarray(moe[leaf])
        assert abs(x.std() * np.sqrt(fan_in) - 1.0) < 0.1, leaf


@pytest.mark.parametrize("name,leaf", [("tiny-mamba", "A_log"),
                                       ("tiny-moe", "w_down")])
def test_a_leaf_without_a_rule_raises(name, leaf):
    config = tiny(name)
    del config["init"][leaf]
    with pytest.raises(ValueError, match=f"no initialisation rule.*{leaf}"):
        build(config)


def test_built_in_rules_cannot_be_overridden():
    config = tiny("tiny-mamba")
    config["init"]["w"] = {"rule": "zeros"}
    with pytest.raises(ValueError, match="cannot be overridden"):
        build(config)


@pytest.mark.parametrize("section", [None, "ssm", "moe", "taylor"])
def test_unknown_config_key_raises(section):
    config = tiny("tiny-moe")
    m = config["model"]
    if section is None:
        m["no_such_key"] = 1
    else:
        m[section] = dict(m.get(section, {}), no_such_key=1)
    with pytest.raises(ValueError, match="no_such_key"):
        run_for(config).program_config()


def test_existing_layers_keep_their_weights_beside_new_kinds():
    # the same seed draws the same numbers for the attention half of a
    # hybrid, whatever rules its mamba leaves take
    a, pa = build(tiny("tiny-mamba"))
    config = tiny("tiny-mamba")
    config["init"]["conv_w"] = {"rule": "constant", "value": 0.5}
    _, pb = build(config)
    attn_a = pa["groups"][1]["attn"]
    attn_b = pb["groups"][1]["attn"]
    for x, y in zip(jax.tree.leaves(attn_a), jax.tree.leaves(attn_b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert np.all(np.asarray(pb["groups"][0]["mamba"]["conv_w"]) == 0.5)


def lm(**kw):
    m = json.loads((ROOT / "bench" / "configs" / "stablelm-1.6b.json")
                   .read_text())["model"]
    return dict(m, **kw)


def test_flops_raise_on_an_uncounted_kind():
    m = lm(layer_pattern=["mamba", "global"])
    with pytest.raises(ValueError, match="'mamba'"):
        F.decode_flops(m, 99)
    with pytest.raises(ValueError, match="'mamba'"):
        F.prefill_flops(m, 0, 128, last_chunk=True)
    with pytest.raises(ValueError, match="'global_moe'"):
        F.train_step_flops(lm(layer_pattern=["global_moe"]), batch=1,
                           seq_len=64)


def test_flops_use_the_references_layer_flops():
    calls = []

    def layer_flops(m, kind, n_past, n_new):
        calls.append((kind, n_past, n_new))
        return 1000 * n_new

    plain = lm(n_layers=2)
    hybrid = lm(n_layers=4, layer_pattern=["mamba", "global"])
    # two global layers as counted alone, plus two mamba layers from the
    # reference, for the same 128 tokens after 256
    want = F.prefill_flops(plain, 256, 128, last_chunk=False) + 2 * 128_000
    assert F.prefill_flops(hybrid, 256, 128, last_chunk=False,
                           layer_flops=layer_flops) == want
    assert calls == [("mamba", 256, 128)] * 2
    calls.clear()
    # training: per sequence, batch times, forward and backward
    want = F.train_step_flops(plain, batch=3, seq_len=64) + 3 * 2 * 3 * 64_000
    assert F.train_step_flops(hybrid, batch=3, seq_len=64,
                              layer_flops=layer_flops) == want
    assert calls == [("mamba", 0, 64)] * 2


@pytest.mark.parametrize("name", ["stablelm-1.6b", "taylorshift-lra"])
def test_existing_counts_unchanged_by_the_pattern(name):
    m = json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                   .read_text())["model"]
    assert "layer_pattern" not in m
    explicit = dict(m, layer_pattern=["global"])
    for mm in (m, explicit):
        assert F.train_step_flops(mm, batch=2, seq_len=512) == 3 * (
            2 * 2 * 512 * F.dense_params_per_layer(m) * m["n_layers"]
            + 2 * F.attn_flops_noncausal(512, m["head_dim"], m["n_heads"],
                                         m["n_kv_heads"]) * m["n_layers"]
            + 2 * 2 * 512 * m["d_model"] * m["vocab"])
        assert F.decode_flops(mm, 99, layer_flops=lambda *a: 1 / 0) == (
            2 * F.dense_params_per_layer(m) * m["n_layers"]
            + F.attn_flops_causal(99, 1, m["head_dim"], m["n_heads"],
                                  m["n_kv_heads"]) * m["n_layers"]
            + 2 * m["d_model"] * m["vocab"])
