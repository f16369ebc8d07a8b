"""The tracer's spans in a captured ``jax.profiler`` trace.

Whenever a profile is being captured, every span of ``obs/trace.py``
also enters a ``jax.profiler.TraceAnnotation`` of the same name, so the
engine's and the training loop's phases land on the profiler's clock
beside the device's operations. With neither the buffer nor a capture
on, a span is the shared no-op.
"""

import glob
import warnings

import jax
import pytest

from repro.obs.trace import _NULL, Tracer, profile, tracer


def host_events(log_dir) -> list:
    """``(name, start_ns, end_ns, args)`` of the host's events, by start."""
    path = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)[0]
    warnings.filterwarnings("ignore", "builtin type event_stats",
                            DeprecationWarning)
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats)) for e in line.events)
    return sorted(out, key=lambda e: e[1])


def inside(outer, events, name) -> list:
    return [e for e in events if e[0] == name
            and outer[1] <= e[1] and e[2] <= outer[2]]


def test_span_is_null_with_buffer_off_and_no_capture():
    tr = Tracer()
    assert tr.span("x", slots=2) is _NULL
    assert tr.span("y", compile_key=("k", 1)) is _NULL


def test_spans_enter_a_captured_profile(tmp_path):
    tr = Tracer()                       # buffer off: the profile alone
    with profile(str(tmp_path)):
        with tr.span("outer", step_num=3, requests=["a"]) as sp:
            sp.set("admitted", 2)
            with tr.span("inner"):
                pass
            tr.instant("marker", request="a")
    assert tr.events == []
    events = host_events(tmp_path)
    (outer,) = [e for e in events if e[0] == "outer"]
    # scalar args ride on the annotation, lists do not
    assert outer[3] == {"step_num": 3, "admitted": 2}
    assert [e[0] for e in events if outer[1] <= e[1] and e[2] <= outer[2]
            and e is not outer] == ["inner", "marker"]


def test_buffer_and_profile_together(tmp_path):
    tr = Tracer()
    tr.enable()
    with profile(str(tmp_path)):
        with tr.span("both", compile_key="k"):
            pass
    assert [(e["name"], e["ph"]) for e in tr.events] == [("both", "B"),
                                                        ("both", "E")]
    assert tr.events[0]["args"] == {"compile": True}
    assert [e[0] for e in host_events(tmp_path) if e[0] == "both"] == [
        "both"]


def test_profile_without_directory_is_a_no_op():
    with profile(None):
        assert Tracer().span("x") is _NULL
    with profile(""):
        pass


@pytest.mark.parametrize("name", ["bench.engine_step", "bench.x"])
def test_reserved_names_refused(name):
    tr = Tracer()
    tr.enable()
    with pytest.raises(ValueError, match="reserved"):
        tr.span(name)


# ---------------------------------------------------------------------------
# The engine's phases in a profile
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine_profile(tmp_path_factory):
    """A few decode steps of a reduced engine under a captured profile,
    warmed first so nothing compiles inside it."""
    from repro.configs import get_config
    from repro.models import model as M
    from repro.serve import Engine, EngineConfig, Request

    cfg = get_config("stablelm-1.6b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, EngineConfig(
        n_slots=2, prefill_chunk=8, token_budget=24, max_seq_len=48))

    def serve(tag):
        for i in range(2):
            eng.submit(Request(f"{tag}{i}", [3, 1, 4, 1 + i],
                               max_new_tokens=4))
        for _ in eng.run():
            pass

    serve("warm")
    log = tmp_path_factory.mktemp("engine_profile")
    assert not tracer.enabled
    with profile(str(log)):
        serve("r")
    return host_events(log)


def decode_steps(events):
    steps = [e for e in events if e[0] == "engine_step"]
    return [s for s in steps if inside(s, events, "decode_batch")
            and not inside(s, events, "prefill_batch")]


def test_engine_step_phases_nest_in_order(engine_profile):
    steps = decode_steps(engine_profile)
    assert len(steps) >= 2
    for step in steps:
        names = [e[0] for e in engine_profile
                 if step[1] <= e[1] and e[2] <= step[2] and e is not step]
        top = [n for n in names if n in (
            "admit", "plan", "decode_batch", "record_step")]
        assert top == ["admit", "plan", "decode_batch", "record_step"]
        (batch,) = inside(step, engine_profile, "decode_batch")
        phases = [e[0] for e in engine_profile
                  if batch[1] <= e[1] and e[2] <= batch[2]
                  and e[0] in ("decode_dispatch", "decode_readback", "emit")]
        assert phases == ["decode_dispatch", "decode_readback", "emit"]


def test_engine_step_carries_its_number(engine_profile):
    nums = [e[3]["step_num"] for e in engine_profile
            if e[0] == "engine_step"]
    assert nums == sorted(nums) and len(set(nums)) == len(nums)
    admit = [e for e in engine_profile if e[0] == "admit"]
    assert {"admitted"} <= set(admit[0][3])


def test_no_program_span_is_a_harness_name(engine_profile):
    assert not [e for e in engine_profile if e[0].startswith("bench.")]


# ---------------------------------------------------------------------------
# Training: the step's phases and the tokens-per-second rate
# ---------------------------------------------------------------------------

def tiny_lra():
    from repro.configs import get_config

    return get_config("taylorshift-lra").with_(
        d_model=32, n_layers=1, n_heads=2, n_kv_heads=2, d_ff=64,
        vocab=64, max_seq_len=33, remat=False, dtype="float32")


def test_train_step_phases_in_profile_and_buffer(tmp_path):
    from repro.launch.train import TrainObs, train

    obs = TrainObs()
    tracer.clear()
    tracer.enable()
    try:
        with profile(str(tmp_path)):
            train(tiny_lra(), steps=3, global_batch=2, seq_len=32,
                  log_every=100, obs=obs)
    finally:
        tracer.disable()
    doc = tracer.export()
    tracer.clear()
    begun = [e["name"] for e in doc["traceEvents"] if e["ph"] == "B"]
    assert begun == ["train_step", "input_batch", "step_dispatch",
                     "loss_readback"] * 3
    events = host_events(tmp_path)
    steps = [e for e in events if e[0] == "train_step"]
    assert [s[3]["step_num"] for s in steps] == [0, 1, 2]
    for step in steps:
        assert [e[0] for e in events
                if step[1] <= e[1] and e[2] <= step[2] and e[0] in (
                    "input_batch", "step_dispatch", "loss_readback")] == [
            "input_batch", "step_dispatch", "loss_readback"]


def test_tokens_per_second_leaves_out_the_first_step():
    from repro.launch.train import TrainObs

    obs = TrainObs()
    obs.observe(dt=30.0, tokens=100, loss=1.0, first=True)
    assert obs.tokens_per_sec.value == 0
    obs.observe(dt=1.0, tokens=100, loss=1.0)
    obs.observe(dt=3.0, tokens=100, loss=1.0)
    # 200 tokens over 4 s: the rate of the window, not of the last step
    assert obs.tokens_per_sec.value == pytest.approx(50.0)
    assert obs.steps_total.value == 3
