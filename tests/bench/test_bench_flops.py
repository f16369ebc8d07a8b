"""bench/flops.py against counts worked by hand for one layer."""

import json

import pytest

from conftest import ROOT
from bench import flops as F


def model(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())["model"]


def test_taylor_state_flops_d64():
    # d (d+1) (d+3) = 64 * 65 * 67
    assert F.taylor_state_flops(64) == 278_720


def test_lra_layer_forward_by_hand():
    m = model("taylorshift-lra")
    # q, k, v, o: 4 x 512 x 512; MLP up and down: 2 x 512 x 1024
    assert F.dense_params_per_layer(m) == 4 * 512 * 512 + 2 * 512 * 1024
    n = 8192
    # efficient: 8192 tokens x E(64) x (8 query heads + 8 key heads)
    eff = 8192 * 278_720 * 16
    assert eff < 4 * n * n * 64 * 8           # cheaper than direct here
    assert F.attn_flops_noncausal(n, 64, 8, 8) == eff
    one_layer = 2 * n * 2_097_152 + eff
    m1 = dict(m, n_layers=1)
    assert F.lm_flops(m1, tokens=n, attn=eff, logits=n) == (
        one_layer + 2 * n * 512 * 32)


def test_lra_short_sequence_takes_direct_form():
    # N=1024 < N0(64): 4 N^2 d h is the smaller count
    assert F.attn_flops_noncausal(1024, 64, 8, 8) == 4 * 1024 ** 2 * 64 * 8


def test_stablelm_layer_by_hand():
    m = model("stablelm-1.6b")
    # q, k, v, o: 4 x 2048 x 2048; gated MLP: 3 x 2048 x 5632
    per = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert F.dense_params_per_layer(m) == per
    # decode after 99 tokens: 100 keys, direct 4*100*64 per head (25,600)
    # is cheaper than E(64) = 278,720; 32 query heads
    attn = 4 * 100 * 64 * 32
    assert F.attn_flops_causal(99, 1, 64, 32, 32) == attn
    m1 = dict(m, n_layers=1)
    assert F.decode_flops(m1, 99) == 2 * per + attn + 2 * 2048 * 100352
    # after 4000 tokens the state form is cheaper: E(64) x (32 + 32)
    assert F.attn_flops_causal(3999, 1, 64, 32, 32) == 278_720 * 64


def test_causal_prompt_counts_each_prefix_once():
    # 4 tokens from scratch: query i sees i keys, 1+2+3+4 = 10 pairs
    assert F.attn_flops_causal(0, 4, 8, 1, 1) == 4 * 10 * 8


def test_train_step_is_three_forwards():
    m = model("taylorshift-lra")
    fwd = F.lm_flops(m, tokens=2 * 8192,
                     attn=2 * F.attn_flops_noncausal(8192, 64, 8, 8),
                     logits=2 * 8192)
    assert F.train_step_flops(m, batch=2, seq_len=8192) == 3 * fwd


@pytest.mark.parametrize("backward,flops,nbytes", [
    (False, 2 * 8192 * 278_720 * 10, 4 * 8192 * 64 * 10 * 2),
    (True, 4 * 8192 * 278_720 * 10, 7 * 8192 * 64 * 10 * 2),
])
def test_efficient_kernel_work(backward, flops, nbytes):
    w = F.efficient_attention_call(8192, 64, heads=10, dtype_bytes=2,
                                   backward=backward)
    assert w == {"flops": flops, "bytes": nbytes}


def test_least_seconds_names_the_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert F.least_seconds({"flops": 1000, "bytes": 5}, peaks) == (
        10.0, "flops")
    assert F.least_seconds({"flops": 10, "bytes": 50}, peaks) == (
        5.0, "bytes")
