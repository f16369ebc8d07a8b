"""The trace reduction, on a small trace recorded on a TPU v5e.

``data/sample.xplane.pb`` was written by ``data/record_sample_trace.py``:
five 2048x2048 bf16 matrix products (one fusion each, ~92 us on the
chip), each inside ``bench.train_step`` and followed by a 20 ms sleep
inside ``bench.wait_arrival``, all inside ``bench.traced_window``.
"""

from pathlib import Path

import pytest

from bench import trace_reduce as TR

SAMPLE = Path(__file__).parent / "data" / "sample.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    return TR.load(str(SAMPLE))


def test_planes_and_window(trace):
    assert list(trace.ops) == ["/device:TPU:0"]
    # five rounds of ~21 ms
    assert 0.09 < trace.window_s < 0.13
    assert all(e.name.startswith("bench.") for e in trace.host)
    steps = [e.step for e in trace.host if e.name == "bench.train_step"]
    assert steps == sorted(steps) and set(steps) <= set(range(5))
    assert len(steps) >= 4


def test_matmul_fusions_counted(trace):
    ops = TR.op_seconds(trace)
    fusion = ops["%fusion fusion"]
    # four or five products of ~91.5 us fall inside the window
    assert 4 * 90e-6 < fusion < 5 * 93e-6
    assert sum(ops.values()) == pytest.approx(TR.busy_s(trace), rel=0.05)


def test_busy_and_idle(trace):
    out = TR.reduce(trace)
    assert out["devices"] == 1
    assert 3.5e-4 < out["busy_s"] < 5e-4
    gaps = dict(out["idle_gaps"])
    # the sleeps are the idle time, named by their annotation
    assert out["idle_gaps"][0][0] == "bench.wait_arrival"
    assert 0.08 < gaps["bench.wait_arrival"] < 0.11
    assert sum(gaps.values()) == pytest.approx(
        out["window_s"] - out["busy_s"], rel=1e-6)


def test_complete_modules(trace):
    steps = TR.complete_modules(trace, r"jit__lambda")
    assert 4 <= len(steps) <= 5
    inside = TR.op_seconds(trace, r"fusion", within=steps)
    assert inside["%fusion fusion"] == pytest.approx(
        TR.op_seconds(trace)["%fusion fusion"])


def test_labels():
    name = ('%checkpoint.39 = (f32[64,8192,64]{2,1,0:T(8,128)}) custom-call('
            'f32[64,8192,64]{2,1,0:T(8,128)} %convert.146), '
            'custom_call_target="tpu_custom_call", operand_layout_constraints'
            '={f32[64,8192,64]{2,1,0}}')
    assert TR.op_label(name) == ("%checkpoint.39 custom-call:tpu_custom_call",
                                 "custom-call:tpu_custom_call")
    assert TR.op_label("%while.7 = (s32[]{:T(128)}, bf16[8]{0}) while((s32[]"
                       "{:T(128)}) %tuple.153)")[1] == "while"


def test_relabel_names_steps_by_kind():
    from bench.harness import relabel

    e = TR.Event("bench.engine_step", 0, 1, 3)
    assert relabel(e, {3: "decode"}).name == "bench.engine_step[decode]"
    assert relabel(e, {}).name == "bench.engine_step"
    assert relabel(TR.Event("bench.wait_arrival", 0, 1), {3: "x"}).name == (
        "bench.wait_arrival")


def test_merge_clips_and_joins():
    ev = [TR.Event("a", 0, 10), TR.Event("b", 5, 20), TR.Event("c", 30, 40)]
    assert TR.merge(ev, 2, 35) == [(2, 20), (30, 35)]
