"""Gradient parity: every equivalence in this repo, under jax.grad.

The forward suites (test_taylor_core / test_kernels) prove direct ≡
efficient ≡ causal-chunked and kernels ≡ jnp reference. Training through
the fused path additionally requires those identities to hold for the
*cotangents* — the custom VJPs (kernels/taylor_grad.py, the chunked-scan
VJP in core/taylor.py) are hand-written, so nothing but these tests
keeps them honest.

All kernel tests run the Pallas bodies in interpret mode on CPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import taylor as T
from repro.kernels import ops, ref
from repro.kernels.taylor_efficient import (_amod_call, _pick_chunk_factor,
                                            build_vhat)
from repro.kernels.taylor_grad import _sym_quad_call

jax.config.update("jax_enable_x64", False)


def rand_qkvw(key, b, h, n, d):
    ks = jax.random.split(key, 4)
    return tuple(jax.random.normal(k, (b, h, n, d)) for k in ks)


def assert_grads_close(g1, g2, *, rtol=1e-4, atol=1e-4, msg=""):
    for name, a, b in zip("qkvt", g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=rtol, atol=atol,
                                   err_msg=f"{msg} grad wrt {name}")


# ---------------------------------------------------------------------------
# Core (pure-jnp) parity: direct ≡ efficient ≡ causal-chunked under grad
# ---------------------------------------------------------------------------

class TestCoreGradParity:
    @pytest.mark.parametrize("n,d", [(32, 8), (64, 16)])
    def test_direct_vs_efficient(self, n, d):
        q, k, v, w = rand_qkvw(jax.random.PRNGKey(n + d), 2, 2, n, d)
        fd = lambda q, k, v, t: jnp.sum(
            T.direct_taylorshift(q, k, v, tau=t) * w)
        fe = lambda q, k, v, t: jnp.sum(
            T.efficient_taylorshift(q, k, v, tau=t) * w)
        gd = jax.grad(fd, argnums=(0, 1, 2, 3))(q, k, v, 1.3)
        ge = jax.grad(fe, argnums=(0, 1, 2, 3))(q, k, v, 1.3)
        assert_grads_close(gd, ge, msg="direct vs efficient")

    @pytest.mark.parametrize("chunk", [4, 8, 32])
    def test_causal_chunked_vs_direct(self, chunk):
        """The chunked scan's recompute-based custom VJP must reproduce
        autodiff of the masked direct oracle."""
        q, k, v, w = rand_qkvw(jax.random.PRNGKey(chunk), 2, 2, 32, 8)
        fc = lambda q, k, v, t: jnp.sum(
            T.causal_taylorshift(q, k, v, tau=t, chunk=chunk) * w)
        fd = lambda q, k, v, t: jnp.sum(
            T.causal_direct_taylorshift(q, k, v, tau=t) * w)
        gc = jax.grad(fc, argnums=(0, 1, 2, 3))(q, k, v, 0.9)
        gd = jax.grad(fd, argnums=(0, 1, 2, 3))(q, k, v, 0.9)
        assert_grads_close(gc, gd, msg=f"causal chunk={chunk}")

    def test_causal_gqa_broadcast(self):
        """GQA lead dims: cotangents must reduce over the broadcast
        group axis, matching autodiff of the broadcast reference."""
        b, kv, g, n, d = 2, 2, 3, 32, 8
        key = jax.random.PRNGKey(31)
        q = jax.random.normal(key, (b, kv, g, n, d))
        k = jax.random.normal(jax.random.fold_in(key, 1), (b, kv, 1, n, d))
        v = jax.random.normal(jax.random.fold_in(key, 2), (b, kv, 1, n, d))
        w = jax.random.normal(jax.random.fold_in(key, 3), (b, kv, g, n, d))
        fc = lambda q, k, v: jnp.sum(
            T.causal_taylorshift(q, k, v, chunk=8) * w)
        fr = lambda q, k, v: jnp.sum(T.causal_direct_taylorshift(
            q, jnp.broadcast_to(k, q.shape), jnp.broadcast_to(v, q.shape))
            * w)
        gc = jax.grad(fc, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
        assert_grads_close(gc, gr, rtol=5e-4, atol=5e-4, msg="causal GQA")

    def test_causal_initial_state_chain(self):
        """Gradients flow through the TaylorState handoff: two chained
        chunked calls ≡ one big call (prefill-style training)."""
        d = 8
        key = jax.random.PRNGKey(7)
        q, k, v, _ = rand_qkvw(key, 1, 2, 16, d)

        def f_chain(q, k, v):
            y1, st = T.causal_taylorshift(q[:, :, :8], k[:, :, :8],
                                          v[:, :, :8], chunk=4,
                                          return_state=True)
            y2 = T.causal_taylorshift(q[:, :, 8:], k[:, :, 8:], v[:, :, 8:],
                                      chunk=4, initial_state=st)
            return jnp.sum(jnp.concatenate([y1, y2], 2) ** 2)

        f_whole = lambda q, k, v: jnp.sum(
            T.causal_taylorshift(q, k, v, chunk=4) ** 2)
        gc = jax.grad(f_chain, argnums=(0, 1, 2))(q, k, v)
        gw = jax.grad(f_whole, argnums=(0, 1, 2))(q, k, v)
        assert_grads_close(gc, gw, rtol=5e-4, atol=5e-4, msg="state chain")

    @settings(max_examples=20, deadline=None)
    @given(
        n=st.integers(4, 64),
        d=st.sampled_from([2, 4, 8]),
        tau=st.floats(0.25, 4.0),
        chunk=st.sampled_from([2, 4, 8, 16]),
        gqa=st.booleans(),
        seed=st.integers(0, 2**31 - 1),
    )
    def test_grad_equivalence_property(self, n, d, tau, chunk, gqa, seed):
        """Random (N, d, τ, chunk) incl. GQA shapes: ∇direct ≡ ∇efficient
        and ∇causal-chunked ≡ ∇causal-direct."""
        key = jax.random.PRNGKey(seed)
        kshape = (1, 1, 1, n, d) if gqa else (1, 2, n, d)
        qshape = (1, 1, 3, n, d) if gqa else (1, 2, n, d)
        q = jax.random.normal(key, qshape)
        k = jax.random.normal(jax.random.fold_in(key, 1), kshape)
        v = jax.random.normal(jax.random.fold_in(key, 2), kshape)
        kb = jnp.broadcast_to(k, q.shape)
        vb = jnp.broadcast_to(v, q.shape)

        fd = lambda q, k, v: jnp.sum(
            T.direct_taylorshift(q, k, v, tau=tau) ** 2)
        fe = lambda q, k, v: jnp.sum(
            T.efficient_taylorshift(q, k, v, tau=tau) ** 2)
        assert_grads_close(jax.grad(fd, argnums=(0, 1, 2))(q, kb, vb),
                           jax.grad(fe, argnums=(0, 1, 2))(q, kb, vb),
                           rtol=5e-4, atol=5e-4, msg="prop direct/efficient")

        c = min(chunk, n)
        while n % c:
            c -= 1
        fc = lambda q, k, v: jnp.sum(
            T.causal_taylorshift(q, k, v, tau=tau, chunk=max(c, 1)) ** 2)
        # reference broadcasts k/v inside, so its cotangents reduce to
        # the same GQA shapes the chunked path returns
        fr = lambda q, k, v: jnp.sum(T.causal_direct_taylorshift(
            q, jnp.broadcast_to(k, q.shape),
            jnp.broadcast_to(v, q.shape), tau=tau) ** 2)
        gc = jax.grad(fc, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
        assert_grads_close(gc, gr, rtol=1e-3, atol=1e-3, msg="prop causal")


# ---------------------------------------------------------------------------
# Pallas kernel custom VJPs vs autodiff of the jnp reference
# ---------------------------------------------------------------------------

@pytest.mark.kernels
class TestKernelVJP:
    """Acceptance grid: N ∈ {64, 127, 256}, d ∈ {16, 32}, causal and
    non-causal, ≤1e-4 rtol at fp32. N=127 is prime — a regression for the
    `_good_block` pad-and-mask path (padded queries/keys must contribute
    exactly zero cotangent)."""

    # d=32 and N=256 rows run in the `grad-parity` CI job (which selects
    # `slow` too) rather than the fast default gate.
    N_GRID = [64, 127, pytest.param(256, marks=pytest.mark.slow)]
    D_GRID = [16, pytest.param(32, marks=pytest.mark.slow)]

    @pytest.mark.parametrize("n", N_GRID)
    @pytest.mark.parametrize("d", D_GRID)
    @pytest.mark.parametrize("causal", [False, True])
    def test_direct_kernel_grads_match_ref(self, n, d, causal):
        q, k, v, w = rand_qkvw(jax.random.PRNGKey(n * d), 1, 2, n, d)
        fk = lambda q, k, v, t: jnp.sum(ops.taylor_attention_kernel(
            q, k, v, tau=t, mode="direct", causal=causal,
            block_q=32, block_k=32, interpret=True) * w)
        fr = lambda q, k, v, t: jnp.sum(
            ref.direct_ref(q, k, v, tau=t, causal=causal) * w)
        gk = jax.grad(fk, argnums=(0, 1, 2, 3))(q, k, v, 1.3)
        gr = jax.grad(fr, argnums=(0, 1, 2, 3))(q, k, v, 1.3)
        assert_grads_close(gk, gr, msg=f"direct n={n} d={d} causal={causal}")

    @pytest.mark.parametrize("n", N_GRID)
    @pytest.mark.parametrize("d", D_GRID)
    def test_efficient_kernel_grads_match_ref(self, n, d):
        q, k, v, w = rand_qkvw(jax.random.PRNGKey(n * d + 1), 1, 2, n, d)
        fk = lambda q, k, v, t: jnp.sum(ops.taylor_attention_kernel(
            q, k, v, tau=t, mode="efficient",
            block_q=32, block_k=32, interpret=True) * w)
        fr = lambda q, k, v, t: jnp.sum(
            ref.direct_ref(q, k, v, tau=t) * w)
        gk = jax.grad(fk, argnums=(0, 1, 2, 3))(q, k, v, 1.3)
        gr = jax.grad(fr, argnums=(0, 1, 2, 3))(q, k, v, 1.3)
        assert_grads_close(gk, gr, msg=f"efficient n={n} d={d}")

    def test_good_block_pad_mask_grads(self):
        """Tiny prime N with aggressive padding (61 -> 64 at block 16):
        the pad-and-mask regression, under grad, for both kernels."""
        n, d = 61, 8
        q, k, v, w = rand_qkvw(jax.random.PRNGKey(61), 1, 2, n, d)
        for mode, causal in [("direct", False), ("direct", True),
                             ("efficient", False)]:
            fk = lambda q, k, v: jnp.sum(ops.taylor_attention_kernel(
                q, k, v, mode=mode, causal=causal,
                block_q=16, block_k=16, interpret=True) * w)
            fr = lambda q, k, v: jnp.sum(
                ref.direct_ref(q, k, v, causal=causal) * w)
            gk = jax.grad(fk, argnums=(0, 1, 2))(q, k, v)
            gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
            assert_grads_close(gk, gr, msg=f"pad-mask {mode} causal={causal}")

    def test_value_only_grad_bf16_values(self):
        """bf16 v: cotangent dtype must match the primal (custom_vjp
        contract), and the fp32-internal grads stay close to ref."""
        q, k, v, w = rand_qkvw(jax.random.PRNGKey(5), 1, 1, 64, 16)
        vb = v.astype(jnp.bfloat16)
        fk = lambda v: jnp.sum(ops.taylor_attention_kernel(
            q, k, v, mode="direct", interpret=True).astype(jnp.float32) * w)
        g = jax.grad(fk)(vb)
        assert g.dtype == jnp.bfloat16
        fr = lambda v: jnp.sum(
            ref.direct_ref(q, k, v).astype(jnp.float32) * w)
        gr = jax.grad(fr)(vb)
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(gr, np.float32),
                                   rtol=0.05, atol=0.05)

    @settings(max_examples=8, deadline=None)
    @given(
        n=st.integers(5, 80),
        d=st.sampled_from([4, 8, 16]),
        mode=st.sampled_from(["direct", "efficient"]),
        seed=st.integers(0, 2**31 - 1),
    )
    def test_kernel_vjp_property(self, n, d, mode, seed):
        """Custom-VJP ≡ autodiff-of-reference for random shapes incl.
        non-divisible N (interpret mode)."""
        q, k, v, w = rand_qkvw(jax.random.PRNGKey(seed), 1, 1, n, d)
        fk = lambda q, k, v: jnp.sum(ops.taylor_attention_kernel(
            q, k, v, mode=mode, block_q=16, block_k=16, interpret=True) * w)
        fr = lambda q, k, v: jnp.sum(ref.direct_ref(q, k, v) * w)
        gk = jax.grad(fk, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(fr, argnums=(0, 1, 2))(q, k, v)
        assert_grads_close(gk, gr, rtol=5e-4, atol=5e-4,
                           msg=f"prop {mode} n={n} d={d}")


# ---------------------------------------------------------------------------
# The efficient backward's symmetric-quadratic kernel, alone
# ---------------------------------------------------------------------------

def _sym_quad_operands(site, n, d, n_valid):
    """Both call sites' real operands, α-scaled rows zero-padded past
    ``n_valid`` as ops.py pads them: the dq site gives (q, ĝ, A_mod(k, v̂)),
    the dk site (k, v̂, dA) with dA = ½ amod(q, ĝ)."""
    ks = jax.random.split(jax.random.PRNGKey(n * d + n_valid), 4)
    valid = (jnp.arange(n) < n_valid)[None, :, None]
    q, k = (T.l2_normalize(jax.random.normal(kk, (2, n, d)))
            * d ** 0.25 * valid for kk in ks[:2])
    vh = build_vhat(jax.random.normal(ks[2], (2, n, d)), n_valid)
    ghat = jax.random.normal(ks[3], (2, n, d + 1)) * valid
    cf = _pick_chunk_factor(d)

    def amod(x, u):
        return _amod_call(x, u, cf=cf, block_k=16, interpret=True,
                          name="taylor_eff_fwd_amod")
    if site == "dq":
        return q, ghat, amod(k, vh)
    return k, vh, 0.5 * amod(q, ghat)


@pytest.mark.kernels
class TestSymQuadKernel:
    """``_sym_quad_call`` returns (M_i + M_iᵀ) x_i, M_i = mat(A u_i), as
    2 M_i x_i: exact only because A is symmetric in its row pair, which
    the second test pins for ``_amod_call``'s output."""

    @pytest.mark.parametrize("d", [8, 16, 32])
    @pytest.mark.parametrize("n,n_valid", [(64, 64), (64, 61)])
    @pytest.mark.parametrize("site", ["dq", "dk"])
    def test_matches_einsum(self, site, n, n_valid, d):
        x, u, a = _sym_quad_operands(site, n, d, n_valid)
        out = _sym_quad_call(x, u, a, cf=_pick_chunk_factor(d), block_q=16,
                             interpret=True,
                             name=f"taylor_eff_bwd_symquad_{site}")
        m = jnp.einsum("zfe,znf->zne", a, u,
                       precision="highest").reshape(2, n, d, d)
        want = jnp.einsum("znab,znb->zna", m + jnp.swapaxes(m, -1, -2), x,
                          precision="highest")
        scale = float(jnp.max(jnp.abs(want)))
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-5, atol=1e-5 * scale)
        assert bool(jnp.all(out[:, n_valid:] == 0.0))

    @pytest.mark.parametrize("d", [8, 16, 32])
    @pytest.mark.parametrize("site", ["dq", "dk"])
    def test_amod_rows_symmetric(self, site, d):
        """The kernel's precondition: A_mod(k, v̂) and amod(q, ĝ) equal
        their π(a, b) ↔ π(b, a) row permutation bit for bit (``_amod_call``
        returns Aᵀ, so the pair indexes its columns)."""
        _, _, a = _sym_quad_operands(site, 64, d, 61)
        cols = a.reshape(2, d + 1, d, d)
        assert bool(jnp.all(cols == jnp.swapaxes(cols, 2, 3)))


# ---------------------------------------------------------------------------
# Training-route integration: model grads through the fused path
# ---------------------------------------------------------------------------

@pytest.mark.kernels
class TestModelTrainRoute:
    @pytest.mark.slow
    def test_classifier_grads_kernel_vs_reference(self):
        """use_kernel=True must give the same classifier loss gradients
        as the pure-jnp route (the paper's §5 training setting)."""
        import dataclasses

        from repro.configs import get_config
        from repro.models import classifier as C

        base = get_config("taylorshift-lra").with_(
            d_model=32, n_layers=2, n_heads=2, n_kv_heads=2, d_ff=64,
            vocab=16, max_seq_len=33, remat=False, dtype="float32")
        batch = {
            "tokens": jax.random.randint(jax.random.PRNGKey(0), (2, 32),
                                         0, 16),
            "label": jnp.array([1, 7]),
        }
        params = C.classifier_init(base, 10, jax.random.PRNGKey(1))

        def grads(cfg):
            return jax.value_and_grad(
                lambda p: C.classifier_loss(p, cfg, batch))(params)

        cfg_k = base.with_(taylor=dataclasses.replace(base.taylor,
                                                      use_kernel=True))
        loss_r, g_r = grads(base)
        loss_k, g_k = grads(cfg_k)
        np.testing.assert_allclose(float(loss_k), float(loss_r), rtol=1e-5)
        flat_r = jax.tree.leaves(g_r)
        flat_k = jax.tree.leaves(g_k)
        for a, b in zip(flat_k, flat_r):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# Backward peak memory: linear-memory training claim (§4.2, trained)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.kernels
class TestBackwardMemoryScaling:
    """XLA temp-buffer bytes of the compiled backward must grow
    sub-quadratically in N for the efficient custom-VJP path while the
    jnp reference grows ~N² (benchmarks/train_step_memory.py reports the
    full sweep)."""

    @staticmethod
    def _bwd_temp_bytes(loss_fn, n, d):
        s = jax.ShapeDtypeStruct((1, 2, n, d), jnp.float32)
        c = jax.jit(jax.grad(loss_fn, argnums=(0, 1, 2))).lower(s, s, s) \
            .compile()
        return int(c.memory_analysis().temp_size_in_bytes)

    def test_efficient_backward_subquadratic(self):
        import math
        d, n_lo, n_hi = 16, 128, 512

        def loss_ref(q, k, v):
            return jnp.sum(T.direct_taylorshift(q, k, v) ** 2)

        def loss_eff(q, k, v):
            return jnp.sum(ops.taylor_attention_kernel(
                q, k, v, mode="efficient", interpret=True) ** 2)

        growth = math.log(n_hi / n_lo)
        s_ref = math.log(self._bwd_temp_bytes(loss_ref, n_hi, d)
                         / self._bwd_temp_bytes(loss_ref, n_lo, d)) / growth
        s_eff = math.log(self._bwd_temp_bytes(loss_eff, n_hi, d)
                         / self._bwd_temp_bytes(loss_eff, n_lo, d)) / growth
        assert s_ref > 1.5, f"reference backward unexpectedly cheap: {s_ref}"
        assert s_eff < 1.3, f"efficient backward not sub-quadratic: {s_eff}"


# ---------------------------------------------------------------------------
# l2_normalize safe-norm regression
# ---------------------------------------------------------------------------

class TestL2NormalizeGrad:
    def test_zero_vector_grad_is_zero(self):
        """Regression: the naive x/(||x||+eps) formulation gives a
        spurious O(1/sqrt(eps)) (or NaN) gradient for an all-zero row;
        the safe-norm double-where must give exactly zero."""
        g = jax.grad(lambda x: jnp.sum(T.l2_normalize(x)))(jnp.zeros((3, 4)))
        assert bool(jnp.all(g == 0.0)), np.asarray(g)

    def test_zero_row_in_batch(self):
        """A zero row must not poison the gradients of its neighbors."""
        x = jnp.stack([jnp.zeros(4), jnp.arange(1.0, 5.0)])
        g = jax.grad(lambda x: jnp.sum(T.l2_normalize(x) ** 2))(x)
        assert bool(jnp.all(jnp.isfinite(g)))
        assert bool(jnp.all(g[0] == 0.0))

    def test_forward_still_normalizes(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 16))
        n = jnp.linalg.norm(T.l2_normalize(x), axis=-1)
        np.testing.assert_allclose(np.asarray(n), np.ones(8), rtol=1e-5)

    def test_grad_finite_everywhere(self):
        for scale in (1e-18, 1e-6, 1.0, 1e6):
            x = jax.random.normal(jax.random.PRNGKey(1), (4, 8)) * scale
            g = jax.grad(lambda x: jnp.sum(T.l2_normalize(x)))(x)
            assert bool(jnp.all(jnp.isfinite(g))), scale

    def test_normalize_qk_grad_with_zero_rows(self):
        """Through the full attention entry: a zero q row (e.g. fully
        masked padding token) must not produce non-finite grads."""
        q, k, v, w = rand_qkvw(jax.random.PRNGKey(3), 1, 1, 16, 8)
        q = q.at[:, :, 0].set(0.0)
        f = lambda q, k, v: jnp.sum(T.efficient_taylorshift(q, k, v) * w)
        g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        for t in g:
            assert bool(jnp.all(jnp.isfinite(t)))
