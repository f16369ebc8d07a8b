"""Pallas kernel validation: shape/dtype sweeps, allclose vs ref.py
oracles, run in interpret mode on CPU (kernel bodies execute in Python)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.kernels

from repro.core import taylor as T
from repro.kernels import ops
from repro.kernels import ref
from repro.kernels.taylor_efficient import (_amod_call, _lane_block,
                                            _pick_chunk_factor,
                                            _readout_call, build_vhat)


def rand(key, b, h, n, d, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    return tuple(jax.random.normal(k, (b, h, n, d), dtype) for k in ks)


class TestDirectKernel:
    @pytest.mark.parametrize("n,d,bq,bk", [
        (64, 8, 16, 16),
        (128, 16, 32, 64),
        (96, 32, 32, 32),     # n not divisible by 64
        (128, 64, 128, 128),  # single block
    ])
    def test_matches_ref(self, n, d, bq, bk):
        q, k, v = rand(jax.random.PRNGKey(n + d), 2, 2, n, d)
        y = ops.taylor_attention_kernel(q, k, v, mode="direct", block_q=bq,
                                        block_k=bk, interpret=True)
        y_ref = ref.direct_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("n,d", [(64, 8), (128, 16)])
    def test_causal_matches_ref(self, n, d):
        q, k, v = rand(jax.random.PRNGKey(7), 1, 2, n, d)
        y = ops.taylor_attention_kernel(q, k, v, causal=True, block_q=32,
                                        block_k=32, interpret=True)
        y_ref = ref.direct_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=2e-4, atol=2e-4)

    def test_bf16_inputs(self):
        q, k, v = rand(jax.random.PRNGKey(9), 1, 1, 64, 16, jnp.bfloat16)
        y = ops.taylor_attention_kernel(q, k, v, mode="direct", interpret=True)
        assert y.dtype == jnp.bfloat16
        y_ref = ref.direct_ref(q, k, v)
        np.testing.assert_allclose(
            np.asarray(y, np.float32), np.asarray(y_ref, np.float32),
            rtol=0.08, atol=0.08)

    def test_tau_vector(self):
        q, k, v = rand(jax.random.PRNGKey(11), 2, 4, 64, 8)
        tau = jnp.array([0.5, 1.0, 2.0, 3.0]).reshape(1, 4, 1, 1)
        y = ops.taylor_attention_kernel(q, k, v, tau=tau, mode="direct",
                                        interpret=True)
        y_ref = ref.direct_ref(q, k, v, tau=tau)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=2e-4, atol=2e-4)


class TestEfficientKernel:
    @pytest.mark.parametrize("n,d", [(64, 8), (128, 16), (64, 32), (256, 64)])
    def test_matches_ref(self, n, d):
        q, k, v = rand(jax.random.PRNGKey(n * d), 2, 2, n, d)
        y = ops.taylor_attention_kernel(q, k, v, mode="efficient",
                                        block_q=32, block_k=32,
                                        interpret=True)
        y_ref = ref.efficient_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=3e-4, atol=3e-4)

    @pytest.mark.parametrize("mode,causal", [("direct", False),
                                             ("direct", True),
                                             ("efficient", False)])
    def test_prime_n_pads_instead_of_block1(self, mode, causal):
        """Prime N must not degrade the grid to block size 1: ops pads N
        up to the block multiple and masks the padded keys."""
        from repro.kernels.ops import _good_block
        n, d = 61, 8
        assert _good_block(n, 16) == (16, 64)
        assert _good_block(1021, 128) == (128, 1024)
        q, k, v = rand(jax.random.PRNGKey(61), 1, 2, n, d)
        y = ops.taylor_attention_kernel(q, k, v, mode=mode, causal=causal,
                                        block_q=16, block_k=16,
                                        interpret=True)
        y_ref = ref.direct_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=3e-4, atol=3e-4)

    def test_direct_equals_efficient_kernels(self):
        """The paper's core identity, at the kernel level."""
        q, k, v = rand(jax.random.PRNGKey(3), 1, 2, 128, 16)
        yd = ops.taylor_attention_kernel(q, k, v, mode="direct",
                                         interpret=True)
        ye = ops.taylor_attention_kernel(q, k, v, mode="efficient",
                                         interpret=True)
        np.testing.assert_allclose(np.asarray(yd), np.asarray(ye),
                                   rtol=3e-4, atol=3e-4)

    @staticmethod
    def _phase_operands(d, n, m_valid):
        """α-scaled normalized q, k as ops.py prepares them, keys past
        ``m_valid`` zero-padded, V̂ built from the first ``m_valid``."""
        q, k, v = rand(jax.random.PRNGKey(d + m_valid), 1, 3, n, d)
        qs, ks = ops._prep(q, k, 1.0)
        qs, ks = qs[0], ks[0] * (jnp.arange(n) < m_valid)[None, :, None]
        return q[0], k[0], v[0], qs, ks, build_vhat(v[0], m_valid)

    @pytest.mark.parametrize("d", [8, 16, 32])
    @pytest.mark.parametrize("n,m_valid", [(64, 64), (64, 61)])
    def test_amod_phase(self, d, n, m_valid):
        """Phase A in isolation against the ⊠-product oracle; the kernel
        returns A_modᵀ, (BH, d+1, d²)."""
        _, _, v, _, ks, vh = self._phase_operands(d, n, m_valid)
        a = _amod_call(ks, vh, cf=_pick_chunk_factor(d), block_k=32,
                       interpret=True, name="taylor_eff_fwd_amod")
        assert a.shape == (3, d + 1, d * d)
        a_ref = ref.amod_ref(ks[:, :m_valid], v[:, :m_valid])
        np.testing.assert_allclose(np.asarray(jnp.swapaxes(a, 1, 2)),
                                   np.asarray(a_ref), rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("d", [8, 16, 32])
    @pytest.mark.parametrize("n,m_valid", [(64, 64), (64, 61)])
    @pytest.mark.parametrize("form", ["divided", "raw"])
    def test_readout_phase(self, form, d, n, m_valid):
        """Phase B in isolation, on A_modᵀ from phase A: divided with the
        output scale, as the forward entry runs it, against Algorithm 1;
        raw with coefs (1, 1, 1), as the dV̂ backward runs it, against
        the bilinear form at HIGHEST."""
        q, k, v, qs, ks, vh = self._phase_operands(d, n, m_valid)
        a = _amod_call(ks, vh, cf=_pick_chunk_factor(d), block_k=32,
                       interpret=True, name="taylor_eff_fwd_amod")
        kv = jnp.einsum("bnd,bnf->bdf", ks, vh)
        s0 = jnp.sum(vh, axis=1, keepdims=True)
        divide = form == "divided"
        y = _readout_call(qs, a, kv, s0, cf=_pick_chunk_factor(d),
                          block_q=32, n_keys=m_valid, out_scale=divide,
                          out_dtype=jnp.float32, interpret=True,
                          name="taylor_eff_fwd_readout",
                          coefs=None if divide else (1.0, 1.0, 1.0),
                          divide=divide)
        if divide:
            want = T.efficient_taylorshift(q, k[:, :m_valid], v[:, :m_valid])
            tol = dict(rtol=3e-4, atol=3e-4)
        else:
            hi = dict(precision="highest")
            want = (jnp.einsum("bne,bfe->bnf", T.boxtimes(qs, qs), a, **hi)
                    + jnp.einsum("bnd,bdf->bnf", qs, kv, **hi) + s0)
            tol = dict(rtol=1e-5, atol=1e-5 * float(jnp.max(jnp.abs(want))))
        assert y.shape == want.shape
        np.testing.assert_allclose(np.asarray(y), np.asarray(want), **tol)

    @pytest.mark.parametrize("d,budget,expect_fit", [
        (64, 8 << 20, True), (128, 8 << 20, True), (256, 8 << 20, True),
    ])
    def test_chunk_factor_fits_vmem(self, d, budget, expect_fit):
        cf = _pick_chunk_factor(d, budget)
        assert d % cf == 0
        assert cf * d * (d + 1) * 4 <= budget


class TestKernelVmemFootprint:
    """Structural check: claimed VMEM working set fits a v5e core (~16MB)."""

    @pytest.mark.parametrize("d", [64, 128, 144, 256, 288])
    def test_efficient_tiles_fit(self, d):
        cf = _pick_chunk_factor(d)
        block_k = _lane_block(8192, 128, d, cf)
        tile = cf * d * (d + 1) * 4           # A_mod accumulator
        k2 = block_k * cf * d * 4             # expanded K chunk
        inputs = block_k * (2 * d + 1) * 4
        assert tile + k2 + inputs < 15 * 1024 * 1024, (d, cf)

    @pytest.mark.parametrize("n,block,d,want", [
        (8192, 128, 64, 512),   # the training cell: (4096, 512) fp32 ⊠ operand
        (640, 128, 64, 128),    # 256 does not divide N
        (8192, 256, 32, 512),   # never past 512
        (8192, 128, 128, 128),  # (8192, 256) operand beside the tile: over
        (64, 32, 8, 32),        # interpret mode's small blocks stay
    ])
    def test_lane_block(self, n, block, d, want):
        assert _lane_block(n, block, d, _pick_chunk_factor(d)) == want

    @pytest.mark.parametrize("d", [64, 128, 256])
    def test_direct_tiles_fit(self, d):
        bq = bk = 128
        total = (2 * bq * d + 2 * bk * d + bq * bk + bq) * 4
        assert total < 15 * 1024 * 1024


class TestAutoMode:
    def test_auto_picks_direct_below_crossover(self):
        q, k, v = rand(jax.random.PRNGKey(13), 1, 1, 32, 16)
        y_auto = ops.taylor_attention_kernel(q, k, v, mode="auto",
                                             interpret=True)
        y_dir = ops.taylor_attention_kernel(q, k, v, mode="direct",
                                            interpret=True)
        np.testing.assert_allclose(np.asarray(y_auto), np.asarray(y_dir),
                                   rtol=1e-6)

    def test_auto_picks_efficient_above_crossover(self):
        d = 4  # N0(4) = 87.7 ⇒ N=128 is beyond the crossover
        assert T.crossover_n0(d) < 128
        q, k, v = rand(jax.random.PRNGKey(14), 1, 1, 128, d)
        y = ops.taylor_attention_kernel(q, k, v, mode="auto", interpret=True)
        y_ref = ref.efficient_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=3e-4, atol=3e-4)


class TestDecodeKernel:
    """Fused decode-step kernel vs the core recurrent oracle."""

    @pytest.mark.parametrize("d", [8, 16, 32])
    def test_matches_decode_step(self, d):
        from repro.kernels.taylor_decode import taylor_decode_kernel
        bh, n_steps = 3, 6
        key = jax.random.PRNGKey(d)
        state_k = T.TaylorState.zeros((bh,), d)
        state_r = T.TaylorState.zeros((bh,), d)
        for t in range(n_steps):
            kk = jax.random.fold_in(key, t)
            q, k, v = (jax.random.normal(s, (bh, 1, d))
                       for s in jax.random.split(kk, 3))
            yk, state_k = taylor_decode_kernel(state_k, q, k, v, tau=1.3,
                                               interpret=True)
            yr, state_r = T.taylor_decode_step(state_r, q, k, v, tau=1.3)
            np.testing.assert_allclose(np.asarray(yk), np.asarray(yr),
                                       rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(np.asarray(state_k.s2),
                                   np.asarray(state_r.s2),
                                   rtol=1e-4, atol=1e-4)

    def test_long_rollout_stable(self):
        from repro.kernels.taylor_decode import taylor_decode_kernel
        d, bh = 8, 1
        state = T.TaylorState.zeros((bh,), d)
        key = jax.random.PRNGKey(0)
        for t in range(40):
            kk = jax.random.fold_in(key, t)
            q, k, v = (jax.random.normal(s, (bh, 1, d))
                       for s in jax.random.split(kk, 3))
            y, state = taylor_decode_kernel(state, q, k, v, interpret=True)
        assert bool(jnp.all(jnp.isfinite(y)))
        assert int(state.n) == 40


class TestInterpretMode:
    """One predicate decides compiled vs interpreted kernels."""

    @pytest.mark.parametrize("platform,interpret", [("cpu", True),
                                                    ("tpu", False)])
    def test_follows_platform(self, monkeypatch, platform, interpret):
        monkeypatch.setattr(ops.jax, "default_backend", lambda: platform)
        assert ops.interpret_mode() is interpret

    def test_other_platform_raises(self, monkeypatch):
        monkeypatch.setattr(ops.jax, "default_backend", lambda: "gpu")
        with pytest.raises(RuntimeError, match="'gpu'"):
            ops.interpret_mode()
