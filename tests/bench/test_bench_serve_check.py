"""The serving cell's check: sound runs pass, the control and an altered
token fail. Tiny shapes, on the CPU."""

import pytest

from conftest import make_tiny_bench, run_cell


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return make_tiny_bench(tmp_path_factory.mktemp("tiny"))


def test_sound_run_is_correct(bench):
    out = run_cell(bench, "tiny-serve", seed=2**31 + 9, seconds=2)
    line = out["line"]
    assert line["correct"] is True
    assert line["attempted"] == 8 and line["failed"] == 0
    assert out["stats"]["check_tokens"] >= 20
    assert out["stats"]["compiles_in_window"] == 0
    assert list(line)[-1] == "checks"


def test_control_fails_the_limit(bench):
    out = run_cell(bench, "tiny-serve", seconds=2, calibrate=1)
    assert out["line"]["correct"] is True
    verdict = out["controls"]["control_fp8"]
    assert set(verdict["checks"]) == {"logit_gap"}
    assert verdict["correct"] is False, verdict


def test_altered_token_is_not_correct(bench, monkeypatch):
    from repro.serve import engine

    orig = engine.Engine._emit

    def emit(self, seq, token, *, first=False):
        if len(seq.out_tokens) == 2:
            token = (token + 1) % self.cfg.vocab
        return orig(self, seq, token, first=first)

    monkeypatch.setattr(engine.Engine, "_emit", emit)
    line = run_cell(bench, "tiny-serve", seconds=2)["line"]
    assert line["correct"] is False
