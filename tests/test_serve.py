"""Serving engine: chunked prefill, continuous batching, slot pool.

The invariants the engine's correctness rests on:
  * chunked prefill + recurrent decode ≡ token-by-token decode loop;
  * batching is invisible: staggered arrivals sharing decode batches
    produce exactly the tokens each request gets when run alone;
  * a released slot carries nothing into its next occupant.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.serve import naive_generate
from repro.models import model as M
from repro.serve import Engine, EngineConfig, QueueFullError, Request
from repro.serve.prefill import plan_chunks

SEQ = 24


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("stablelm-1.6b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompt(cfg, n, seed):
    return [int(t) for t in jax.random.randint(
        jax.random.PRNGKey(seed), (n,), 0, cfg.vocab)]


# ---------------------------------------------------------------------------
# Chunked prefill ≡ token-by-token
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("cache_kind", ["taylor", "kv"])
@pytest.mark.parametrize("chunks", [(SEQ,), (16, 8), (8, 8, 8), (13, 11)])
def test_prefill_chunk_logit_equivalent(setup, cache_kind, chunks):
    """prefill_chunk over any chunking must reproduce the logits of the
    teacher-forced single-token loop (the old serve.py prefill)."""
    cfg, params = setup
    assert sum(chunks) == SEQ
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, SEQ), 0, cfg.vocab)

    cache = M.init_decode_state(cfg, 1, cache_len=SEQ + 4,
                                cache_kind=cache_kind, dtype=jnp.float32)
    outs = []
    for t in range(SEQ):
        lg, cache = M.decode_step(params, cfg, {"tokens": tokens[:, t:t+1]},
                                  cache)
        outs.append(lg)
    lg_loop = jnp.concatenate(outs, axis=1)

    c2 = M.init_decode_state(cfg, 1, cache_len=SEQ + 4,
                             cache_kind=cache_kind, dtype=jnp.float32)
    outs, lo = [], 0
    for c in chunks:
        lg, c2 = M.prefill_chunk(params, cfg,
                                 {"tokens": tokens[:, lo:lo+c]}, c2)
        outs.append(lg)
        lo += c
    lg_chunked = jnp.concatenate(outs, axis=1)

    np.testing.assert_allclose(np.asarray(lg_loop), np.asarray(lg_chunked),
                               rtol=1e-4, atol=1e-4)

    # and decode continues identically from either state
    nxt = jnp.full((1, 1), 3, jnp.int32)
    lg_a, _ = M.decode_step(params, cfg, {"tokens": nxt}, cache)
    lg_b, _ = M.decode_step(params, cfg, {"tokens": nxt}, c2)
    np.testing.assert_allclose(np.asarray(lg_a), np.asarray(lg_b),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.slow
def test_engine_matches_naive_baseline(setup):
    """Engine generation (chunked prefill + pooled decode) == naive
    token-by-token generation, exactly, at temperature 0."""
    cfg, params = setup
    prompt = _prompt(cfg, 19, seed=3)
    eng = Engine(cfg, params, EngineConfig(
        n_slots=2, prefill_chunk=8, token_budget=32, max_seq_len=64))
    out = eng.generate([Request("r", prompt, max_new_tokens=8)])["r"]
    ref = naive_generate(cfg, params, jnp.asarray([prompt], jnp.int32),
                         gen_tokens=8)
    assert out == [int(t) for t in ref[0, len(prompt):]]


# ---------------------------------------------------------------------------
# Continuous batching
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_staggered_arrivals_match_solo_runs(setup):
    """Requests admitted mid-flight share decode batches with running
    sequences yet produce exactly the solo-run tokens."""
    cfg, params = setup
    prompts = {f"r{i}": _prompt(cfg, 10 + 3 * i, seed=10 + i)
               for i in range(3)}
    reqs = {rid: Request(rid, p, max_new_tokens=6)
            for rid, p in prompts.items()}

    eng = Engine(cfg, params, EngineConfig(
        n_slots=3, prefill_chunk=8, token_budget=24, max_seq_len=64))
    eng.submit(reqs["r0"])
    shared = 0
    arrivals = {3: "r1", 5: "r2"}
    while not eng.idle or arrivals:
        due = [s for s in arrivals if s <= eng.step_idx]
        for s in due:
            eng.submit(reqs[arrivals.pop(s)])
        m, _ = eng.step()
        shared = max(shared, m.active_decoding)
    assert shared >= 2, "late arrivals never joined a shared decode batch"

    for rid, p in prompts.items():
        solo = Engine(cfg, params, EngineConfig(
            n_slots=1, prefill_chunk=8, token_budget=24, max_seq_len=64))
        want = solo.generate([Request(rid, p, max_new_tokens=6)])[rid]
        assert eng.results[rid].out_tokens == want, rid


def test_engine_rejects_unsupported_patterns():
    """Local-window (ring cache) and shared-attention SSM blocks have no
    chunked-prefill state handoff yet: the engine must refuse them up
    front rather than silently prefilling their windows as global
    context."""
    for arch in ("gemma3-1b", "zamba2-7b"):
        cfg = get_config(arch).reduced()
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        with pytest.raises(NotImplementedError):
            Engine(cfg, params, EngineConfig(n_slots=1, max_seq_len=64))


@pytest.fixture(scope="module")
def granite():
    cfg = get_config("granite-4.0-h-micro").reduced()
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


def test_engine_serves_mamba2_hybrid(granite):
    """granite's pattern (Mamba2 and global attention) is served, and each
    span reports the recurrent state its program reads."""
    cfg, params = granite
    eng = Engine(cfg, params, EngineConfig(n_slots=2, prefill_chunk=8,
                                           max_seq_len=64))
    out = eng.generate([Request("a", _prompt(cfg, 13, 1), max_new_tokens=3)])
    assert len(out["a"]) == 3
    by_kind = eng.pool.state_bytes()
    assert by_kind["ssm"] and by_kind["conv"] and by_kind["taylor"]
    assert eng._pool_state["ssm_state_bytes"] == (by_kind["ssm"]
                                                  + by_kind["conv"])
    assert eng._slot_state["taylor_state_bytes"] * 2 == by_kind["taylor"]
    gauge = eng.stats.registry.get("serve_pool_state_bytes")
    assert {c.labels["kind"]: c.value for c in gauge.children} == by_kind


@pytest.mark.parametrize("knob,value", [("speculate_k", 2),
                                        ("prefix_cache_mb", 1.0)])
def test_engine_refuses_rollback_and_prefix_reuse_with_ssm(granite, knob,
                                                           value):
    """Verify's rollback cannot take drafted tokens back out of an SSM
    state, and the prefix cache keeps no SSM state: both are refused at
    construction, not discovered mid-stream."""
    cfg, params = granite
    with pytest.raises(ValueError, match="Mamba2"):
        Engine(cfg, params, EngineConfig(n_slots=1, max_seq_len=64,
                                         **{knob: value}))


def test_admission_backpressure(setup):
    cfg, params = setup
    eng = Engine(cfg, params, EngineConfig(n_slots=1, max_queue=2,
                                           max_seq_len=64))
    for i in range(2):
        eng.submit(Request(f"q{i}", _prompt(cfg, 4, seed=i)))
    with pytest.raises(QueueFullError):
        eng.submit(Request("q2", _prompt(cfg, 4, seed=9)))


# ---------------------------------------------------------------------------
# Slot pool hygiene
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_slot_reuse_does_not_leak_state(setup):
    """A slot that served a long request must serve a later request
    identically to a fresh engine — and is zeroed right at release."""
    cfg, params = setup
    eng = Engine(cfg, params, EngineConfig(
        n_slots=1, prefill_chunk=8, token_budget=16, max_seq_len=64))
    p1 = _prompt(cfg, 21, seed=40)
    eng.generate([Request("a", p1, max_new_tokens=5)])
    leftovers = sum(float(jnp.sum(jnp.abs(x)))
                    for x in jax.tree.leaves(eng.pool.gather(0)))
    assert leftovers == 0.0, "released slot not zero-reset"

    p2 = _prompt(cfg, 9, seed=41)
    reused = eng.generate([Request("b", p2, max_new_tokens=5)])["b"]
    fresh_eng = Engine(cfg, params, EngineConfig(
        n_slots=1, prefill_chunk=8, token_budget=16, max_seq_len=64))
    fresh = fresh_eng.generate([Request("b", p2, max_new_tokens=5)])["b"]
    assert reused == fresh


@pytest.mark.slow
def test_engine_restart_mid_stream(setup):
    """Kill an engine mid-generation and restart from scratch: the fresh
    engine must produce exactly the clean-run tokens (no state survives
    outside the engine), and the abandoned engine's partial output must
    be a prefix of the clean run (greedy decode is deterministic)."""
    cfg, params = setup
    prompts = {"r0": _prompt(cfg, 17, seed=50), "r1": _prompt(cfg, 9, seed=51)}
    mk = lambda: Engine(cfg, params, EngineConfig(
        n_slots=2, prefill_chunk=8, token_budget=24, max_seq_len=64))

    clean = mk().generate(
        [Request(rid, p, max_new_tokens=8) for rid, p in prompts.items()])

    crashed = mk()
    for rid, p in prompts.items():
        crashed.submit(Request(rid, p, max_new_tokens=8))
    for _ in range(4):           # mid-stream: prefill done, decode underway
        crashed.step()
    partial = {rid: list(s.out_tokens)
               for rid, s in crashed.sequences.items()}
    assert any(partial.values()), "restart happened before any token"
    for rid, toks in partial.items():
        assert toks == clean[rid][:len(toks)], rid

    restarted = mk()             # the old engine is simply dropped
    out = restarted.generate(
        [Request(rid, p, max_new_tokens=8) for rid, p in prompts.items()])
    assert out == clean


def test_restart_released_slots_are_reset(setup):
    """After a mid-stream abandon, finishing the remaining work through
    the same pool must leave every slot zeroed once drained — the
    release path, not scatter, is what guarantees a clean slot."""
    cfg, params = setup
    eng = Engine(cfg, params, EngineConfig(
        n_slots=2, prefill_chunk=8, token_budget=24, max_seq_len=64))
    eng.submit(Request("a", _prompt(cfg, 12, seed=60), max_new_tokens=4))
    eng.submit(Request("b", _prompt(cfg, 7, seed=61), max_new_tokens=4))
    for _ in range(3):
        eng.step()
    for _ in eng.run():          # drain to idle
        pass
    assert eng.idle
    for slot in range(eng.pool.n_slots):
        leftovers = sum(float(jnp.sum(jnp.abs(x)))
                        for x in jax.tree.leaves(eng.pool.gather(slot)))
        assert leftovers == 0.0, f"slot {slot} not zero-reset"


def test_long_prefill_does_not_starve_decode(setup):
    """Scheduler starvation: while a long prompt prefills, every
    DECODING sequence still gets exactly one token per step, and prefill
    work per step stays within the token budget (modulo the one-chunk
    minimum that guarantees progress)."""
    cfg, params = setup
    budget, chunk = 12, 4
    eng = Engine(cfg, params, EngineConfig(
        n_slots=2, prefill_chunk=chunk, token_budget=budget, max_seq_len=64))

    # short request reaches DECODING first
    eng.submit(Request("short", _prompt(cfg, 4, seed=70), max_new_tokens=24))
    eng.step()
    assert eng.sequences["short"].out_tokens, "short prompt not prefilled"

    # long prompt needs many chunked-prefill steps under this budget
    eng.submit(Request("long", _prompt(cfg, 40, seed=71), max_new_tokens=2))
    decode_starved = []
    while ("long" in eng.sequences
           and not eng.sequences["long"].prefill_done
           and "short" in eng.sequences):
        before = len(eng.sequences["short"].out_tokens)
        m, _ = eng.step()
        after = len(eng.sequences["short"].out_tokens) \
            if "short" in eng.sequences else before + 1
        decode_starved.append(after - before == 0)
        # decode goes first; prefill spends at most the leftover budget,
        # except the guaranteed first chunk
        assert m.prefill_tokens <= max(budget - m.decode_tokens, chunk)
    assert decode_starved, "long prefill finished before any shared step"
    assert not any(decode_starved), \
        "a decoding sequence was starved during a long prefill"
    for _ in eng.run():
        pass
    assert eng.results["short"].out_tokens and eng.results["long"].out_tokens


# ---------------------------------------------------------------------------
# Sequence-parallel serving (multi-device CI job)
# ---------------------------------------------------------------------------

@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs a multi-device host platform "
                           "(XLA_FLAGS=--xla_force_host_platform_"
                           "device_count=8)")
def test_prefill_state_parity_under_seq_mesh(setup):
    """The engine's chunked prefill must produce the same TaylorState —
    and then the same tokens — whether the model runs under a
    `seq`-sharded mesh (sequence-parallel causal scan + boundary-state
    exchange) or on a single device."""
    from repro.distributed import ctx
    from repro.launch.mesh import make_seq_mesh
    from repro.serve.request import SequenceStatus

    cfg, params = setup
    # 19 = 2×8 + 2 + 1: full chunks split over the seq axis, the
    # power-of-two tail falls back to the sequential scan
    prompt = _prompt(cfg, 19, seed=77)

    def prefilled_state_and_tokens(use_mesh):
        eng = Engine(cfg, params, EngineConfig(
            n_slots=1, prefill_chunk=8, token_budget=32, max_seq_len=64))
        eng.submit(Request("r", prompt, max_new_tokens=4))
        while ("r" in eng.sequences
               and eng.sequences["r"].status != SequenceStatus.DECODING):
            eng.step()
        state = jax.tree.map(lambda x: np.asarray(x), eng.pool.gather(0))
        for _ in eng.run():
            pass
        return state, eng.results["r"].out_tokens

    mesh = make_seq_mesh()
    with mesh, ctx.use(mesh):
        st_mesh, toks_mesh = prefilled_state_and_tokens(True)
    st_ref, toks_ref = prefilled_state_and_tokens(False)

    flat_m = jax.tree_util.tree_flatten_with_path(st_mesh)[0]
    flat_r = jax.tree_util.tree_flatten_with_path(st_ref)[0]
    for (path, a), (_, b) in zip(flat_m, flat_r):
        np.testing.assert_allclose(
            a, b, rtol=1e-5, atol=1e-5,
            err_msg="/".join(str(p) for p in path))
    assert toks_mesh == toks_ref


def test_observability_is_purely_observational(setup):
    """The §4.6 contract: tracing, metrics resets, and the decision log
    never leak into scheduling/selection/sampling. A greedy 2-request
    session with reset_metrics() mid-run AND tracing/decision-logging
    toggled mid-run streams bit-identical tokens vs an uninstrumented
    engine."""
    from repro.obs import decisions as OD
    from repro.obs.trace import tracer

    cfg, params = setup
    prompts = {f"r{i}": _prompt(cfg, 12 + 5 * i, seed=40 + i)
               for i in range(2)}
    ecfg = EngineConfig(n_slots=2, prefill_chunk=8, token_budget=24,
                        max_seq_len=64)

    def session(instrumented):
        eng = Engine(cfg, params, ecfg)
        for rid, p in prompts.items():
            eng.submit(Request(rid, p, max_new_tokens=8))
        step = 0
        while not eng.idle:
            if instrumented:          # toggle everything mid-stream
                if step == 1:
                    tracer.enable()
                    OD.log.enable()
                if step == 3:
                    eng.reset_metrics()
                if step == 5:
                    tracer.disable()
                    OD.log.disable()
            eng.step()
            step += 1
        return {rid: eng.results[rid].out_tokens for rid in prompts}

    plain = session(instrumented=False)
    try:
        traced = session(instrumented=True)
    finally:                          # never leak global switches
        tracer.disable()
        tracer.clear()
        OD.log.disable()
        OD.log.records.clear()
    assert traced == plain


def test_plan_chunks():
    assert plan_chunks(24, 8) == [8, 8, 8]
    assert plan_chunks(21, 8) == [8, 8, 4, 1]
    assert plan_chunks(5, 8) == [4, 1]
    assert plan_chunks(1, 128) == [1]
    # bounded retrace surface: only powers of two below the chunk size
    for n in range(1, 70):
        for c in plan_chunks(n, 16):
            assert c == 16 or (c & (c - 1)) == 0
        assert sum(plan_chunks(n, 16)) == n
