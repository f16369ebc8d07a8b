"""The training cell's check: sound runs pass, the control and each fault
that a one-chip training cell can have fail. Tiny shapes, on the CPU."""

import pytest

from conftest import make_tiny_bench, run_cell


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return make_tiny_bench(tmp_path_factory.mktemp("tiny"))


def test_sound_run_is_correct(bench):
    out = run_cell(bench, "tiny-train", seconds=1)
    line = out["line"]
    assert line["correct"] is True
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["checks"]) == {"loss_gap", "grad_gap", "update_gap"}
    assert list(line)[-1] == "checks"
    assert out["stats"]["compiles_in_window"] == 0


def test_control_and_half_batch_fail_the_limits(bench):
    out = run_cell(bench, "tiny-train", seconds=1, calibrate=1)
    assert out["line"]["correct"] is True
    controls = out["controls"]
    assert set(controls) == {"control_fp8", "fault_half_batch"}
    for who, verdict in controls.items():
        assert set(verdict["checks"]) == set(out["checks"]), who
        assert verdict["correct"] is False, (who, verdict)
    assert out["stats"]["controls"] == controls


def _wrap_step(monkeypatch, wrap):
    from repro.launch import steps

    orig = steps.build_train_step

    def broken(cfg, opt_cfg, **kw):
        return wrap(orig(cfg, opt_cfg, **kw))

    monkeypatch.setattr(steps, "build_train_step", broken)


def test_state_left_unchanged_is_not_correct(bench, monkeypatch):
    def wrap(step):
        def f(params, opt_state, batch):
            _, _, metrics = step(params, opt_state, batch)
            return params, opt_state, metrics
        return f

    _wrap_step(monkeypatch, wrap)
    line = run_cell(bench, "tiny-train", seconds=1)["line"]
    assert line["correct"] is False


def test_half_batch_is_not_correct(bench, monkeypatch):
    def wrap(step):
        def f(params, opt_state, batch):
            half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            return step(params, opt_state, half)
        return f

    _wrap_step(monkeypatch, wrap)
    line = run_cell(bench, "tiny-train", seconds=1)["line"]
    assert line["correct"] is False
