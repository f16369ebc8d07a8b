"""The traffic generator: seeded, inside its ranges, same work per seed."""

import json

import numpy as np
import pytest

from conftest import ROOT
from bench import traffic_gen as G

BIG_SEED = 2**31 + 12345
# a lognormal mix beside the committed ones, so each distribution is run
LOGNORMAL = {
    "kind": "serve", "arrival": {"process": "poisson", "rate_per_s": 0.8},
    "prompt_tokens": {"dist": "lognormal", "median": 2032, "sigma": 0.35,
                      "min": 1024, "max": 4032},
    "output_tokens": {"dist": "uniform", "min": 8, "max": 32},
    "schedule_seed": 0}
MIXES = ["lmsys-chat", "azure-code", "lognormal"]


def mix(name):
    if name == "lognormal":
        return dict(LOGNORMAL)
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json")
                      .read_text())


def as_tuple(reqs):
    return [(r.due_s, r.prompt.tolist(), r.max_new_tokens) for r in reqs]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a = G.serve_requests(mix(name), seed=BIG_SEED, seconds=30, vocab=1000)
    b = G.serve_requests(mix(name), seed=BIG_SEED, seconds=30, vocab=1000)
    assert as_tuple(a) == as_tuple(b)
    c = G.serve_requests(mix(name), seed=BIG_SEED + 1, seconds=30,
                         vocab=1000)
    assert as_tuple(a) != as_tuple(c)


@pytest.mark.parametrize("name", MIXES)
def test_lengths_inside_ranges(name):
    m = mix(name)
    reqs = G.serve_requests(m, seed=7, seconds=60, vocab=1000)
    p, o = m["prompt_tokens"], m["output_tokens"]
    for r in reqs:
        assert p["min"] <= len(r.prompt) <= p["max"]
        assert o["min"] <= r.max_new_tokens <= o["max"]
        assert r.prompt.min() >= 0 and r.prompt.max() < 1000


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_does_the_same_work(name):
    m = mix(name)
    runs = [G.serve_requests(m, seed=s, seconds=60, vocab=1000)
            for s in (1, 2, BIG_SEED)]
    sizes = [sorted((len(r.prompt), r.max_new_tokens) for r in reqs)
             for reqs in runs]
    assert sorted(len(r.prompt) for r in runs[0]) == sorted(
        len(r.prompt) for r in runs[1])
    assert len({len(s) for s in sizes}) == 1
    gaps = [np.sort(np.diff([r.due_s for r in reqs])) for reqs in runs]
    # the same gaps in another order, one of them left out as the last
    assert abs(runs[0][-1].due_s - runs[1][-1].due_s) < 60 / len(runs[0]) * 8
    assert all(g.min() > 0 for g in gaps)


@pytest.mark.parametrize("name", MIXES)
def test_rate_and_window(name):
    m = mix(name)
    reqs = G.serve_requests(m, seed=3, seconds=40, vocab=1000)
    rate = m["arrival"]["rate_per_s"]
    assert len(reqs) == int(np.ceil(rate * 40))
    assert reqs[0].due_s == 0.0
    assert all(a.due_s < b.due_s for a, b in zip(reqs, reqs[1:]))
    assert reqs[-1].due_s < 40


@pytest.mark.parametrize("name", MIXES)
def test_schedule_seed_fixes_the_order(name):
    m = mix(name)
    a = G.serve_requests(m, seed=11, seconds=30, vocab=1000)
    b = G.serve_requests(m, seed=BIG_SEED, seconds=30, vocab=1000)
    assert [(r.due_s, len(r.prompt), r.max_new_tokens) for r in a] == [
        (r.due_s, len(r.prompt), r.max_new_tokens) for r in b]
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in b]
    free = {k: v for k, v in m.items() if k != "schedule_seed"}
    c = G.serve_requests(free, seed=11, seconds=30, vocab=1000)
    d = G.serve_requests(free, seed=12, seconds=30, vocab=1000)
    assert [len(r.prompt) for r in c] != [len(r.prompt) for r in d]


def test_negative_seed_refused():
    with pytest.raises(ValueError):
        G.seed_sequence(-1)


def test_exponential_lengths_keep_the_published_mean():
    spec = {"dist": "exponential", "mean": 214.5, "min": 1, "max": 10**6}
    q = G.quantiles(spec, 2000)
    assert abs(q.mean() - 214.5) / 214.5 < 0.01
    assert q.min() >= 1 and np.all(np.diff(q) >= 0)


def test_exponential_lengths_keep_the_published_median():
    spec = {"dist": "exponential", "median": 1500, "min": 1, "max": 10**6}
    q = G.quantiles(spec, 2001)
    assert q[1000] == 1500
    assert abs(q.mean() - 1500 / np.log(2)) / (1500 / np.log(2)) < 0.01


def test_long_prompt_mix_fits_the_context():
    # prompts are clamped to stablelm-2's 4096 positions less the
    # longest output the mix can ask for
    m = mix("azure-code")
    assert G.longest_request(m) == 4096
