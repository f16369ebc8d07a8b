"""``engine_host_ms.serve_decode``: the program's own spans in the trace.

The reader's arithmetic on ``Event`` lists built here, then a whole
traced run of the tiny serving cell on the CPU, in which the program's
spans reach the harness's profile, and the same reader against a
program without spans (as the parent of the change that added them).
"""

import json
from types import SimpleNamespace

import pytest
from conftest import ROOT, make_tiny_bench, run_cell

from bench import spans as S
from bench.harness import load_module
from bench.trace_reduce import Event

READER = load_module(ROOT / "bench" / "metrics" /
                     "engine_host_ms.serve_decode.py")
MS = 1_000_000      # ns


def step(t0, parts, total_ms=10):
    """An ``engine_step`` at ``t0`` ms holding ``parts`` [(name, start
    offset ms, duration ms)]."""
    out = [Event("engine_step", t0 * MS, (t0 + total_ms) * MS)]
    out += [Event(n, (t0 + a) * MS, (t0 + a + d) * MS) for n, a, d in parts]
    return out


DECODE = [("admit", 0, 0.1), ("plan", 0.1, 0.1), ("decode_batch", 0.2, 9),
          ("decode_dispatch", 0.2, 1), ("decode_readback", 1.2, 7),
          ("emit", 8.2, 1), ("record_step", 9.3, 0.2)]


def test_median_of_step_less_readback():
    events = (step(0, DECODE) + step(20, DECODE, total_ms=12)
              + step(40, DECODE, total_ms=14))
    # 10 - 7, 12 - 7, 14 - 7
    assert READER.host_ms([events]) == pytest.approx(5.0)


def test_prefill_steps_and_other_threads_left_out():
    prefill = DECODE + [("prefill_batch", 9.4, 0.5)]
    main = step(0, DECODE) + step(20, prefill, total_ms=30)
    # a decode_batch on another thread is not inside this thread's step
    other = [Event("engine_step", 100 * MS, 110 * MS)]
    assert READER.host_ms([main, other]) == pytest.approx(3.0)
    assert READER.host_ms([other]) is None
    assert READER.host_ms([]) is None


def test_children_are_of_the_same_list():
    events = step(0, DECODE)
    names = [e.name for e in S.children(events[0], events)]
    assert names == [n for n, _, _ in DECODE]


def test_untraced_reading_reports_nothing():
    r = SimpleNamespace(trace=None, run=SimpleNamespace(tracer=None))
    assert READER.read(r) is None


def add_metric(root):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append(
        {"name": "engine_host_ms.serve_decode", "unit": "ms",
         "better": "lower", "source": "device_trace", "layer": "engine",
         "moves": "serve_gen_tokens_per_s", "workloads": ["tiny-serve"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def test_traced_tiny_serve_reads_the_engine_spans(tmp_path, monkeypatch):
    from bench import harness

    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")
    bench = make_tiny_bench(tmp_path)
    add_metric(tmp_path)
    out = run_cell(bench, "tiny-serve", seconds=2, trace=1)
    value = out["line"]["metrics"]["engine_host_ms.serve_decode"]["value"]
    assert 0 < value < 1e3 * out["stats"]["window_s"]


def test_program_without_spans_reports_nothing(tmp_path, monkeypatch):
    """As the parent program runs the new reader: the engine opens no
    span, the metric is left out of the line and nothing raises."""
    from bench import harness
    from repro.obs import trace

    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")
    monkeypatch.setattr(trace.Tracer, "span",
                        lambda self, name, **kw: trace._NULL)
    bench = make_tiny_bench(tmp_path)
    add_metric(tmp_path)
    out = run_cell(bench, "tiny-serve", seconds=2, trace=1)
    assert "engine_host_ms.serve_decode" not in out["line"]["metrics"]
    assert out["line"]["correct"] is True
