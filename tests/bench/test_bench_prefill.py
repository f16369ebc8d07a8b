"""The long-prompt serving cell: after the window the engine steps on
until every request due in it has its first token, and a request that
never gets one ranks above every served one in ``ttft_p90_s``. Tiny
shapes, on the CPU."""

from types import SimpleNamespace

import numpy as np
import pytest

from conftest import make_tiny_bench, run_cell
from bench.drivers import serve as D


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return make_tiny_bench(tmp_path_factory.mktemp("tiny"))


def test_drain_reaches_every_first_token(bench):
    out = run_cell(bench, "tiny-prefill", seed=2**31 + 21, seconds=2)
    line, stats = out["line"], out["stats"]
    assert line["correct"] is True, out["checks"]
    assert set(line["metrics"]) == {"ttft_p90_s", "itl_p95_ms", "setup_s"}
    assert line["attempted"] == 6 and line["failed"] == 0
    assert stats["unserved"] == 0 and stats["drain_s"] >= 0
    assert line["metrics"]["ttft_p90_s"]["value"] > 0
    assert stats["compiles_in_window"] == 0


def test_other_serving_cells_do_not_drain(bench):
    out = run_cell(bench, "tiny-serve", seed=2**31 + 22, seconds=2)
    assert "ttft_p90_s" not in out["line"]["metrics"]
    assert "unserved" not in out["stats"]


def test_never_served_request_fails_and_ranks_last(bench, monkeypatch):
    from repro.serve import engine

    orig = engine.Engine.submit

    def submit(self, request):
        if request.request_id != "r0":       # r0 is lost on its way in
            return orig(self, request)

    monkeypatch.setattr(engine.Engine, "submit", submit)
    out = run_cell(bench, "tiny-prefill", seed=2**31 + 23, seconds=2)
    line = out["line"]
    assert out["stats"]["unserved"] == 1
    assert line["failed"] == 1 and line["attempted"] == 6
    assert line["correct"] is True


def window(due, first, drain_end):
    return SimpleNamespace(due=due, first=first, drain_end=drain_end)


def test_ttft_ranks_an_unserved_request_above_every_served_one():
    due = {f"r{i}": float(i) for i in range(10)}
    served = {f"r{i}": i + 0.1 * i for i in range(1, 10)}   # waits 0.1..0.9
    p90, unserved = D.ttft_p90_s(window(due, served, drain_end=12.0))
    assert unserved == 1
    # r0 ranks at 12 s (first due time to the drain's end), above all
    waits = sorted(served[r] - due[r] for r in served)
    assert p90 == pytest.approx(float(np.percentile(waits + [12.0], 90)))
    assert p90 > max(waits)
    # served, the same request would not have moved the tail that far
    all_served = dict(served, r0=0.05)
    p90_served, none = D.ttft_p90_s(window(due, all_served, drain_end=12.0))
    assert none == 0 and p90_served < p90


@pytest.mark.parametrize("traffic, waits", [({"settle_trace": True}, True),
                                             ({"drain_s": 60}, False),
                                             ({}, False)])
def test_only_a_mix_that_asks_waits_for_the_device(traffic, waits):
    pool = SimpleNamespace(cache={"state": np.zeros(2)})
    settle = D.settler(SimpleNamespace(pool=pool), traffic)
    assert (settle is not None) == waits
    if waits:
        settle()


def test_traced_run_reads_nothing_without_a_device_plane(bench):
    # the CPU's profile has no TPU modules: the prefill reader is silent
    out = run_cell(bench, "tiny-prefill", seed=2**31 + 24, seconds=2,
                   trace=1)
    assert out["line"]["correct"] is True
    assert "prefill_ms_per_1k_tokens" not in out["line"]["metrics"]


def test_prefill_reader_counts_its_spans_and_programs(monkeypatch):
    from conftest import ROOT
    from bench import trace_reduce as TR
    from bench.harness import load_module

    reader = load_module(ROOT / "bench" / "metrics" /
                         "prefill_ms_per_1k_tokens.py")
    E = TR.Event
    threads = [[E("engine_step", 0, 100),
                E("prefill_batch", 1, 2, args=(("slots", 2), ("chunk", 128))),
                E("prefill_chunk", 3, 4, args=(("chunk", 64),)),
                E("decode_batch", 5, 6)]]
    ms = 1e6
    trace = TR.Trace((0, 100 * ms), modules={"/device:TPU:0": [
        E("jit__lambda(17)", 10 * ms, 13 * ms),        # pooled chunk
        E("jit__slot_prefill(5)", 20 * ms, 21 * ms),   # singleton chunk
        E("jit_run(9)", 30 * ms, 35 * ms),             # decode: not counted
        E("jit__lambda(17)", 99 * ms, 101 * ms)]})     # past the window
    monkeypatch.setattr(reader.S, "load", lambda r: threads)
    # 2 x 128 + 64 = 320 tokens in 4 ms of prefill programs
    assert reader.read(SimpleNamespace(trace=trace)) == pytest.approx(
        4 / 320 * 1000)
    assert reader.read(SimpleNamespace(trace=None)) is None
