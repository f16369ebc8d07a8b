"""Substrate tests: optimizer, checkpoint, data pipeline, fault tolerance."""

import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import DataConfig, DataLoader, listops_like, lm_synthetic
from repro.distributed.ft import (PreemptionHandler, StragglerDetector,
                                  elastic_remesh, run_with_restarts)
from repro.optim import (OptConfig, adamw_init, adamw_update,
                         clip_by_global_norm, cosine_schedule, lamb_init,
                         lamb_update)


# ---------------------------------------------------------------------------
# Optimizers
# ---------------------------------------------------------------------------

def quad_params():
    return {"w": jnp.array([3.0, -2.0, 1.0]), "b": jnp.zeros((2, 2))}


def quad_loss(p):
    return jnp.sum(p["w"] ** 2) + jnp.sum((p["b"] - 1.0) ** 2)


class TestOptimizers:
    @pytest.mark.parametrize("name", ["adamw", "lamb"])
    def test_converges_on_quadratic(self, name):
        cfg = OptConfig(name=name, lr=0.1, warmup_steps=0, total_steps=200,
                        weight_decay=0.0)
        params = quad_params()
        state = (adamw_init if name == "adamw" else lamb_init)(cfg, params)
        update = adamw_update if name == "adamw" else lamb_update
        for _ in range(150):
            grads = jax.grad(quad_loss)(params)
            params, state, _ = update(cfg, params, grads, state)
        assert float(quad_loss(params)) < 0.05

    def test_grad_clip(self):
        g = {"a": jnp.full((10,), 100.0)}
        clipped, norm = clip_by_global_norm(g, 1.0)
        assert float(norm) > 100
        n2 = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(clipped)))
        np.testing.assert_allclose(float(n2), 1.0, rtol=1e-4)

    def test_cosine_schedule(self):
        cfg = OptConfig(lr=1.0, warmup_steps=10, total_steps=100)
        assert float(cosine_schedule(cfg, jnp.asarray(0))) == 0.0
        np.testing.assert_allclose(
            float(cosine_schedule(cfg, jnp.asarray(10))), 1.0, rtol=1e-5)
        assert float(cosine_schedule(cfg, jnp.asarray(100))) < 1e-3

    def test_bf16_moments_and_stochastic_rounding(self):
        cfg = OptConfig(moment_dtype="bfloat16", master=False,
                        stochastic_round=True, lr=0.05, warmup_steps=0,
                        weight_decay=0.0)
        params = {"w": jnp.ones((64, 64), jnp.bfloat16)}
        state = adamw_init(cfg, params)
        assert state["mu"]["w"].dtype == jnp.bfloat16
        assert "master" not in state
        for i in range(20):
            grads = {"w": jnp.full((64, 64), 0.5, jnp.bfloat16)}
            params, state, _ = adamw_update(cfg, params, grads, state,
                                            rng=jax.random.PRNGKey(i))
        assert params["w"].dtype == jnp.bfloat16
        assert float(jnp.mean(params["w"].astype(jnp.float32))) < 1.0

    def test_stochastic_rounding_unbiased(self):
        """Mean of many SR casts approximates the fp32 value better than
        round-to-nearest can for sub-ulp increments."""
        from repro.optim.optimizers import _stochastic_round_bf16
        x = jnp.full((20000,), 1.0 + 1e-3, jnp.float32)  # between bf16 ulps
        r = _stochastic_round_bf16(x, jax.random.PRNGKey(0))
        mean = float(jnp.mean(r.astype(jnp.float32)))
        assert abs(mean - (1.0 + 1e-3)) < 5e-4
        deterministic = float(jnp.mean(x.astype(jnp.bfloat16)
                                       .astype(jnp.float32)))
        assert abs(mean - 1.001) < abs(deterministic - 1.001) + 1e-4

    def test_zero1_master_fp32(self):
        cfg = OptConfig()
        params = {"w": jnp.ones((4, 4), jnp.bfloat16)}
        state = adamw_init(cfg, params)
        assert state["master"]["w"].dtype == jnp.float32


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------

class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        tree = {"a": jnp.arange(10.0), "b": {"c": jnp.ones((3, 3),
                                                           jnp.bfloat16)}}
        mgr.save(5, tree, blocking=True)
        step, out = mgr.restore(tree)
        assert step == 5
        np.testing.assert_array_equal(np.asarray(out["a"]),
                                      np.arange(10.0))
        assert out["b"]["c"].dtype == jnp.bfloat16

    def test_atomicity_tmp_never_visible(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, {"x": jnp.zeros(4)}, blocking=True)
        assert not any(d.endswith(".tmp") for d in os.listdir(tmp_path))

    def test_retention_gc(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2)
        for s in range(5):
            mgr.save(s, {"x": jnp.zeros(2)}, blocking=True)
        steps = sorted(d for d in os.listdir(tmp_path))
        assert len(steps) == 2
        assert mgr.latest_step() == 4

    def test_restore_across_shardings(self, tmp_path):
        """Checkpoint taken under one mesh restores under another —
        the elastic-rescale path."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        mgr = CheckpointManager(str(tmp_path))
        tree = {"w": jnp.arange(16.0).reshape(4, 4)}
        mgr.save(1, tree, blocking=True)
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        sh = {"w": NamedSharding(mesh, P(None, None))}
        _, out = mgr.restore(tree, shardings=sh)
        np.testing.assert_array_equal(np.asarray(out["w"]),
                                      np.arange(16.0).reshape(4, 4))

    def test_async_save_overlaps(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, {"x": jnp.zeros((256, 256))})
        # returns before the write necessarily finished; wait() must block
        mgr.wait()
        assert mgr.latest_step() == 1


# ---------------------------------------------------------------------------
# Data pipeline
# ---------------------------------------------------------------------------

class TestData:
    def test_determinism_and_step_addressing(self):
        cfg = DataConfig(vocab=100, global_batch=4, seq_len=16, seed=7)
        b1 = lm_synthetic(cfg, 3)
        b2 = lm_synthetic(cfg, 3)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
        b3 = lm_synthetic(cfg, 4)
        assert not np.array_equal(b1["tokens"], b3["tokens"])

    def test_host_sharding_disjoint(self):
        full = DataConfig(vocab=50, global_batch=8, seq_len=8, n_hosts=1)
        h0 = DataConfig(vocab=50, global_batch=8, seq_len=8, n_hosts=2,
                        host_id=0)
        h1 = DataConfig(vocab=50, global_batch=8, seq_len=8, n_hosts=2,
                        host_id=1)
        assert lm_synthetic(h0, 0)["tokens"].shape[0] == 4
        assert not np.array_equal(lm_synthetic(h0, 0)["tokens"],
                                  lm_synthetic(h1, 0)["tokens"])

    def test_loader_prefetch_order(self):
        cfg = DataConfig(vocab=10, global_batch=2, seq_len=4)
        loader = DataLoader(cfg, start_step=5)
        try:
            steps = [next(loader)[0] for _ in range(3)]
            assert steps == [5, 6, 7]
        finally:
            loader.close()

    def test_listops_labels_valid(self):
        cfg = DataConfig(vocab=16, global_batch=16, seq_len=64,
                         kind="listops")
        b = listops_like(cfg, 0)
        assert b["label"].min() >= 0 and b["label"].max() <= 9
        assert b["tokens"].max() <= 15

    @settings(max_examples=10, deadline=None)
    @given(step=st.integers(0, 1000), seed=st.integers(0, 100))
    def test_generator_purity(self, step, seed):
        cfg = DataConfig(vocab=64, global_batch=2, seq_len=8, seed=seed)
        np.testing.assert_array_equal(lm_synthetic(cfg, step)["tokens"],
                                      lm_synthetic(cfg, step)["tokens"])


# ---------------------------------------------------------------------------
# Fault tolerance
# ---------------------------------------------------------------------------

class TestFaultTolerance:
    def test_straggler_detector(self):
        det = StragglerDetector(threshold=2.0)
        for _ in range(10):
            det.observe(1.0)
        assert det.stragglers == 0
        assert det.observe(5.0)
        assert det.stragglers == 1

    def test_run_with_restarts_recovers(self):
        calls = {"n": 0}

        def run_fn(_):
            calls["n"] += 1
            if calls["n"] < 3:
                raise RuntimeError("simulated worker failure")
            return "done"

        assert run_with_restarts(lambda: None, run_fn) == "done"
        assert calls["n"] == 3

    def test_run_with_restarts_gives_up(self):
        def run_fn(_):
            raise RuntimeError("poison pill")

        with pytest.raises(RuntimeError):
            run_with_restarts(lambda: None, run_fn, max_failures=2)

    def test_elastic_remesh(self):
        mesh = elastic_remesh(model_parallel=1)
        assert mesh.axis_names == ("data", "model")
        assert mesh.size >= 1

    def test_preemption_handler_flags(self):
        h = PreemptionHandler(signals=())
        with h:
            assert not h.preempted
            h._handle(15, None)
            assert h.preempted


# ---------------------------------------------------------------------------
# Persistent compilation cache placement (launch/compile_cache.py)
# ---------------------------------------------------------------------------

class TestCompileCache:
    @pytest.fixture
    def updates(self, monkeypatch):
        calls = []
        monkeypatch.setattr(jax.config, "update",
                            lambda *a: calls.append(a))
        return calls

    def test_env_dir_is_left_to_jax(self, monkeypatch, tmp_path, updates):
        from repro.launch.compile_cache import enable_compile_cache
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert updates == []

    def test_default_dir_is_fixed_in_the_checkout(self, monkeypatch,
                                                  updates):
        from pathlib import Path

        from repro.launch.compile_cache import enable_compile_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(Path(__file__).resolve().parents[1] / ".jax_cache")
        assert enable_compile_cache() == want
        assert updates == [("jax_compilation_cache_dir", want)]


def test_chip_smoke_refuses_to_run_without_a_tpu():
    """No TPU: non-zero exit before any phase, and no result line."""
    import subprocess
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, str(root / "chip_smoke.py")],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


# ---------------------------------------------------------------------------
# End-to-end: tiny train run with checkpoint/restart (the full FT loop)
# ---------------------------------------------------------------------------

class TestTrainLoop:
    @pytest.mark.slow
    def test_loss_decreases_and_restart_resumes(self, tmp_path):
        from repro.configs import get_config
        from repro.launch.train import train

        cfg = get_config("taylorshift-lra").with_(
            d_model=32, n_layers=1, n_heads=2, n_kv_heads=2, d_ff=64,
            vocab=64, max_seq_len=33, remat=False, dtype="float32")
        out = train(cfg, steps=30, global_batch=4, seq_len=32,
                    ckpt_dir=str(tmp_path), ckpt_every=10, log_every=100)
        assert np.mean(out["losses"][-5:]) < np.mean(out["losses"][:5])

        # restart: resumes from the latest checkpoint, not step 0
        out2 = train(cfg, steps=35, global_batch=4, seq_len=32,
                     ckpt_dir=str(tmp_path), ckpt_every=10, log_every=100)
        assert len(out2["losses"]) <= 15  # resumed at >= step 21


class TestGradAccumulation:
    @pytest.mark.slow
    def test_microbatched_grads_match_full_batch(self):
        """M-way gradient accumulation == single big batch (same math)."""
        import jax
        import jax.numpy as jnp
        from repro.configs import get_config
        from repro.launch.steps import build_train_step
        from repro.optim import OptConfig, make_optimizer

        cfg = get_config("taylorshift-lra").with_(
            d_model=32, n_layers=1, n_heads=2, n_kv_heads=2, d_ff=64,
            vocab=64, max_seq_len=17, remat=False, dtype="float32")
        opt_cfg = OptConfig(lr=1e-2, warmup_steps=0, weight_decay=0.0)
        init_opt, _ = make_optimizer(opt_cfg)
        from repro.models import model as M
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        batch = {
            "tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 16),
                                         0, 64),
            "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 16),
                                         0, 64),
        }
        outs = {}
        for m in (1, 4):
            step = build_train_step(cfg, opt_cfg, microbatches=m)
            p2, _, metrics = step(params, init_opt(params), batch)
            outs[m] = (metrics["loss"], p2)
        import numpy as np
        np.testing.assert_allclose(float(outs[1][0]), float(outs[4][0]),
                                   rtol=1e-5)
        for a, b in zip(jax.tree.leaves(outs[1][1]),
                        jax.tree.leaves(outs[4][1])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)
