"""The empirical-calibration subsystem (src/repro/tune/).

Pins the tuning-table contract: the JSON schema round-trips and the
validator rejects malformed documents; ``lookup`` resolves
most-specific-first over the (H, site) wildcard axes; ``install``
makes the table the process-global override source for BOTH prongs
(``select_backend`` provenance and the ``core.taylor`` crossover
hook) and ``uninstall`` restores the analytic Eq. (7)/(9) world
exactly; ``kernel_blocks`` serves calibrated Pallas block shapes with
per-field defaults. The calibration sweeps themselves are covered by
the CI ``autotune`` job (``python -m repro.tune --calibrate --quick``)
— timing real kernels has no place in a unit suite.
"""

import jax
import pytest

from repro.core import taylor as T
from repro.tune.table import (SCHEMA, TuneEntry, TuningTable,
                              validate_table)
from repro.tune import table as TU


@pytest.fixture
def clean_install():
    """Guarantee no table leaks into (or out of) a test."""
    TU.uninstall()
    yield
    TU.uninstall()


def _table(*entries, backend=None):
    return TuningTable(backend=backend or jax.default_backend(),
                       entries=list(entries))


# ---------------------------------------------------------------------------
# Schema round-trip + validation
# ---------------------------------------------------------------------------

def test_doc_round_trip(tmp_path):
    t = _table(TuneEntry(d=16, n0=385.0, n1=226.0, block_q=64, block_k=64),
               TuneEntry(d=32, H=8, site="decode", n0=900.5),
               backend="cpu")
    t.meta["note"] = "round-trip"
    doc = t.to_doc()
    assert doc["schema"] == SCHEMA
    assert validate_table(doc) == []
    back = TuningTable.from_doc(doc)
    assert back.backend == t.backend
    assert back.entries == t.entries
    assert back.meta == t.meta
    path = tmp_path / "tuning.json"
    t.save(str(path))
    assert TuningTable.load(str(path)).entries == t.entries


def test_from_doc_rejects_invalid():
    with pytest.raises(ValueError, match="invalid tuning table"):
        TuningTable.from_doc({"schema": "nope", "backend": "cpu",
                              "entries": []})


@pytest.mark.parametrize("mutate,needle", [
    (lambda d: d.update(schema="repro.tune/v0"), "schema"),
    (lambda d: d.pop("backend"), "backend"),
    (lambda d: d.pop("entries"), "entries"),
    (lambda d: d["entries"][0].update(d=0), "positive int"),
    (lambda d: d["entries"][0].update(H=-1), "H must be"),
    (lambda d: d["entries"][0].update(site="verifyy"), "site"),
    (lambda d: d["entries"][0].update(n0=-3.0), "n0 must be"),
    (lambda d: d["entries"][0].update(block_q=96), "power of two"),
    (lambda d: d["entries"][0].update(bogus=1), "unknown fields"),
    (lambda d: d["entries"].__setitem__(
        0, {"d": 16, "H": None, "site": "*", "n0": None, "n1": None,
            "block_q": None, "block_k": None, "source": "measured"}),
     "overrides nothing"),
])
def test_validate_rejects(mutate, needle):
    doc = _table(TuneEntry(d=16, n0=300.0), backend="cpu").to_doc()
    mutate(doc)
    problems = validate_table(doc)
    assert problems and any(needle in p for p in problems), problems


# ---------------------------------------------------------------------------
# Lookup precedence (most-specific-first over the wildcard axes)
# ---------------------------------------------------------------------------

def test_lookup_precedence_ranks():
    t = _table(TuneEntry(d=16, n0=100.0),                        # rank 0
               TuneEntry(d=16, site="decode", n0=300.0),         # rank 1
               TuneEntry(d=16, H=8, n0=200.0),                   # rank 2
               TuneEntry(d=16, H=8, site="decode", n0=400.0))    # rank 3
    assert t.lookup(d=16, H=8, site="decode").n0 == 400.0
    assert t.lookup(d=16, H=8, site="prefill").n0 == 200.0
    assert t.lookup(d=16, H=4, site="decode").n0 == 300.0
    assert t.lookup(d=16, H=4, site="full").n0 == 100.0
    assert t.lookup(d=16).n0 == 100.0          # bare: wildcard row only
    assert t.lookup(d=32) is None              # unmeasured head dim


def test_concrete_H_never_matches_other_H():
    t = _table(TuneEntry(d=16, H=8, n0=200.0))
    assert t.lookup(d=16, H=4) is None
    assert t.lookup(d=16) is None              # H=None request, concrete row


# ---------------------------------------------------------------------------
# Process-global installation (both prongs) + platform strictness
# ---------------------------------------------------------------------------

def test_install_wires_crossover_hook(clean_install):
    d = 16
    analytic = T.crossover_n0(d)
    assert T.effective_n0(d) == pytest.approx(analytic)
    TU.install(_table(TuneEntry(d=d, n0=analytic * 2, n1=50.0)))
    assert TU.active() is not None
    assert T.effective_n0(d) == pytest.approx(analytic * 2)
    assert T.effective_n1(d) == pytest.approx(50.0)
    # sparse table: unmeasured head dims stay analytic
    assert T.effective_n0(32) == pytest.approx(T.crossover_n0(32))
    TU.uninstall()
    assert TU.active() is None
    assert T.effective_n0(d) == pytest.approx(analytic)


def test_install_moves_pick_mode_threshold(clean_install):
    d, analytic = 16, T.crossover_n0(16)
    n_mid = int(analytic) + 64
    assert T.pick_mode(n_mid, d) == "efficient"
    TU.install(_table(TuneEntry(d=d, n0=float(n_mid + 128))))
    assert T.pick_mode(n_mid, d) == "direct"   # measured threshold moved


def test_install_rejects_foreign_platform(clean_install):
    t = _table(TuneEntry(d=16, n0=300.0), backend="not-a-platform")
    with pytest.raises(ValueError, match="calibrated on"):
        TU.install(t)
    assert TU.active() is None
    TU.install(t, strict=False)                # explicit force works
    assert TU.active() is t


# ---------------------------------------------------------------------------
# Calibrated Pallas block shapes
# ---------------------------------------------------------------------------

def test_kernel_blocks_defaults_and_overrides(clean_install):
    assert TU.kernel_blocks(16) == (128, 128)
    TU.install(_table(TuneEntry(d=16, block_q=64, block_k=32),
                      TuneEntry(d=32, n0=900.0)))      # no blocks measured
    assert TU.kernel_blocks(16) == (64, 32)
    assert TU.kernel_blocks(16, default=256) == (64, 32)
    assert TU.kernel_blocks(32) == (128, 128)          # entry, no blocks
    assert TU.kernel_blocks(64) == (128, 128)          # no entry at all
    TU.uninstall()
    assert TU.kernel_blocks(16) == (128, 128)


def test_block_sweep_compiled_times_lane_aligned_blocks_only(monkeypatch):
    """Compiled, the efficient kernels hold a token block in lanes, so
    the sweep times only blocks that are a multiple of 128 or all of N."""
    import importlib
    from repro.kernels import ops
    C = importlib.import_module("repro.tune.calibrate")
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    timed = []
    monkeypatch.setattr(C, "_time_best",
                        lambda fn, *a, reps: timed.append(fn) or 1.0)
    assert C.sweep_kernel_blocks(16, n=256) == (128, 128)
    assert len(timed) == 2                     # direct + efficient, once
