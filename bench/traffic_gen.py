"""The one traffic generator: reads a mix's parameters, returns requests.

A serving mix (``"kind": "serve"``) is an open loop: requests arrive on
a schedule fixed in the mix, whether or not the system keeps up. Every
seed gets the same set of sizes and gaps: the sizes are the quantiles
``(i + 1/2) / n`` of the mix's distribution, clamped to its range, and
the gaps are those of an exponential distribution with the mix's mean
rate. Their order is drawn from the mix's ``schedule_seed`` where it
has one (every run then serves the same schedule), else from the run's
seed. The run's seed draws the token ids. So runs with different seeds
do the same amount of work, and the same seed gives the same requests.

Parameters of a serving mix::

    {"kind": "serve",
     "arrival": {"process": "poisson", "rate_per_s": 0.5},
     "prompt_tokens": {"dist": "exponential", "mean": 69.5,
                       "min": 1, "max": 2048},
     "output_tokens": {...},
     "schedule_seed": 0,
     "engine": {...},
     "check": {"min_tokens": 300, "max_requests": 24}}

``dist`` is ``"exponential"`` (``mean``, or ``median``: the mean is then
median / ln 2), ``"lognormal"`` (``median``, ``sigma``) or ``"uniform"``
(between ``min`` and ``max``); every length is clamped to ``[min, max]``.

A mix with ``"drain_s"`` is judged on its time to first token: after
the window the engine steps on, with no new submissions, until every
request due in the window has its first token or ``drain_s`` seconds
have passed. A mix with ``"settle_trace": true`` is traced between two
waits for the device, so that the traced part holds the device time of
every program dispatched in it (``prefill_ms_per_1k_tokens`` needs it).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass(frozen=True)
class Arrival:
    index: int
    due_s: float          # offset from the window's start
    prompt: np.ndarray    # int32 token ids
    max_new_tokens: int


def seed_sequence(seed: int, *salt: int) -> np.random.SeedSequence:
    """A numpy seed sequence from any non-negative whole number."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return np.random.SeedSequence([seed, *salt])


def quantiles(spec: dict, n: int) -> np.ndarray:
    """The ``n`` mid-quantiles of a length distribution, as ints."""
    kind = spec["dist"]
    u = (np.arange(n) + 0.5) / n
    if kind == "uniform":
        x = spec["min"] + u * (spec["max"] - spec["min"])
    elif kind == "exponential":
        mean = spec["mean"] if "mean" in spec else spec["median"] / math.log(2)
        x = -mean * np.log1p(-u)
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """The ``n`` mid-quantiles of an exponential gap of mean 1/rate."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def n_requests(mix: dict, seconds: float) -> int:
    """Requests due in a window of ``seconds``: the mean rate times it."""
    arr = mix["arrival"]
    if arr["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    return max(1, math.ceil(arr["rate_per_s"] * seconds))


def serve_requests(mix: dict, *, seed: int, seconds: float,
                   vocab: int) -> list[Arrival]:
    """The requests of one run, in order of their due times."""
    n = n_requests(mix, seconds)
    order = np.random.default_rng(
        seed_sequence(mix.get("schedule_seed", seed), 4))
    prompts = order.permutation(quantiles(mix["prompt_tokens"], n))
    outputs = order.permutation(quantiles(mix["output_tokens"], n))
    gaps = order.permutation(exponential_gaps(mix["arrival"]["rate_per_s"],
                                              n))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    rng = np.random.default_rng(seed_sequence(seed, 1))
    return [Arrival(i, float(due[i]),
                    rng.integers(0, vocab, int(prompts[i]), dtype=np.int32),
                    int(outputs[i]))
            for i in range(n)]


def longest_request(mix: dict) -> int:
    """Prompt plus output tokens of the longest request the mix can make."""
    return int(mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"])
