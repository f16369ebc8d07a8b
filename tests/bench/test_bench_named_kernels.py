"""Kernel names in a device trace, and ``roofline.taylor_eff_bwd_train``.

``data/named.xplane.pb`` was written by ``data/record_named_trace.py``
on a TPU v5e: three steps of a jitted ``train_step`` running the
efficient-form attention forward and backward, rematerialised as in the
model's training, over 4 rows of 2048 tokens at head dim 64. Every
Pallas kernel shows under the name its call site gives it.
"""

import re
from pathlib import Path
from types import SimpleNamespace

import pytest
from conftest import ROOT

from bench import flops as F
from bench import trace_reduce as TR
from bench.harness import load_module
from bench.peaks import peaks_for

DATA = Path(__file__).parent / "data"
READER = load_module(ROOT / "bench" / "metrics" /
                     "roofline.taylor_eff_bwd_train.py")
PEAKS = peaks_for("TPU v5 lite")
FWD = ["taylor_eff_fwd_amod", "taylor_eff_fwd_readout"]
BWD = ["taylor_eff_bwd_amod_da", "taylor_eff_bwd_readout_dv",
       "taylor_eff_bwd_symquad_dk", "taylor_eff_bwd_symquad_dq"]


@pytest.fixture(scope="module")
def trace():
    return TR.load(str(DATA / "named.xplane.pb"))


def reading(trace):
    run = SimpleNamespace(model={"n_heads": 2, "n_layers": 1,
                                 "head_dim": 64},
                          traffic={"global_batch": 2, "seq_len": 2048})
    return SimpleNamespace(trace=trace, peaks=PEAKS, run=run)


def kernel_labels(trace):
    return [k for k in TR.op_seconds(trace) if k.endswith("tpu_custom_call")]


def test_every_kernel_is_named(trace):
    labels = kernel_labels(trace)
    # the forward outside the rematerialised block runs under jax.grad's
    # linearisation, and its name says so: %jvp_taylor_eff_fwd_amod_.N
    names = sorted({re.sub(r"^(transpose_)?(jvp_)?", "", re.match(
        r"%(\w+)\.\d+ ", k).group(1)).rstrip("_") for k in labels})
    assert names == sorted(FWD + BWD)
    # forward, rematerialised forward, backward: eight launches a step
    assert len(labels) == 8
    assert not [k for k in labels
                if re.search(r"checkpoint|closed_call|rematted", k)]


def test_roofline_reads_the_backward_kernels(trace):
    steps = TR.complete_modules(trace, r"train_step")
    assert len(steps) >= 2
    bwd_s = sum(v for k, v in TR.op_seconds(trace, within=steps).items()
                if "taylor_eff_bwd_" in k)
    least, bound = F.least_seconds(F.efficient_attention_call(
        2048, 64, heads=4, dtype_bytes=2, backward=True), PEAKS)
    assert bound == "flops"
    value = READER.read(reading(trace))
    assert value == pytest.approx(100.0 * len(steps) * least / bwd_s)
    assert 0 < value < 100


def op(name, start, end):
    """An ``XLA Ops`` event of a named kernel, as the chip's trace has it."""
    return TR.Event(f"%{name} = (f32[4,2048,64]{{2,1,0}}) custom-call("
                    f"f32[4,2048,64]{{2,1,0}} %p), custom_call_target="
                    f'"tpu_custom_call"', start, end)


def test_roofline_arithmetic_on_built_events():
    """Two complete steps of 10 ms of backward kernels each; the forward's
    kernel, a third step cut by the window and a fusion do not count."""
    ms = 1_000_000
    ops, modules = [], []
    for i, t in enumerate((0, 20, 40)):
        modules.append(TR.Event("jit_train_step(1)", t * ms,
                                (t + 18) * ms))
        ops += [op(f"taylor_eff_fwd_amod.{i}", t * ms, (t + 5) * ms),
                op(f"taylor_eff_bwd_symquad_dq.{i}", (t + 5) * ms,
                   (t + 11) * ms),
                op(f"transpose_jvp_taylor_eff_bwd_amod_da__.{i}",
                   (t + 11) * ms, (t + 15) * ms),
                TR.Event(f"%fusion.{i} = f32[4]{{0}} fusion(f32[4]{{0}} %p)",
                         (t + 15) * ms, (t + 17) * ms)]
    trace = TR.Trace((0, 50 * ms), {"/device:TPU:0": ops},
                     {"/device:TPU:0": modules})
    least, _ = F.least_seconds(F.efficient_attention_call(
        2048, 64, heads=4, dtype_bytes=2, backward=True), PEAKS)
    assert READER.read(reading(trace)) == pytest.approx(
        100.0 * 2 * least / 0.020)


def test_roofline_silent_on_unnamed_kernels():
    """A program whose kernels carry no names (the parent's) reports
    nothing, and an untraced run nothing either."""
    assert READER.read(reading(TR.load(str(DATA / "sample.xplane.pb")))) \
        is None
    assert READER.read(reading(None)) is None
