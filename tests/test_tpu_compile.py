"""Compile the Pallas kernels of the main paths for a TPU v5e.

The TPU compiler is installed with jax, and it compiles for a chip that
is described rather than attached (``jax.experimental.topologies``), so
these tests catch what interpret mode cannot: block shapes not aligned
to the tiling, vector reshapes Mosaic refuses, VMEM overruns. Nothing
runs; each test only asserts that the compiled program holds the
kernel (``tpu_custom_call``), i.e. that it was compiled and not
interpreted, under the name its call site gives it: the instruction
``%<name>.N``, which is how a device trace names the kernel.

Shapes are those of the models the repo serves and trains: head dim
64, 32 flattened batch·heads, N = 4096.

Only one process at a time may load the TPU library, so the topology is
described inside a module fixture (never at import), and all these
tests live in this one file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import taylor as T
from repro.kernels.taylor_decode import taylor_decode_kernel
from repro.kernels.taylor_direct import taylor_direct_attention
from repro.kernels.taylor_efficient import (_amod_call, _pick_chunk_factor,
                                            _readout_call,
                                            taylor_efficient_attention)
from repro.kernels.taylor_grad import (_sym_quad_call,
                                       taylor_direct_attention_vjp,
                                       taylor_efficient_attention_vjp)

pytestmark = pytest.mark.kernels

BH, N, D = 32, 4096, 64


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *specs) -> str:
    return jax.jit(fn).lower(*specs).compile().as_text()


def _kernels(text: str) -> list[str]:
    """Names of the compiled kernels, ``%<name>.N`` -> ``<name>``; a call
    under ``jax.grad`` outside a rematerialised block carries its
    transformations too (``%transpose_jvp_<name>__.N``), dropped here."""
    names = re.findall(r'%([\w\-]+)\.\d+ = [^\n]*custom_call_target='
                       r'"tpu_custom_call"', text)
    return [re.sub(r"^(transpose_)?(jvp_)?", "", n).rstrip("_")
            for n in names]


EFFICIENT_FWD = ["taylor_eff_fwd_amod", "taylor_eff_fwd_readout"]
EFFICIENT_BWD = ["taylor_eff_bwd_amod_da", "taylor_eff_bwd_symquad_dq",
                 "taylor_eff_bwd_symquad_dk", "taylor_eff_bwd_readout_dv"]


@pytest.fixture
def qkv(one_chip):
    return [_spec((BH, N, D), one_chip) for _ in range(3)]


@pytest.mark.parametrize("causal", [False, True])
def test_direct_forward_compiles(qkv, causal):
    text = _compiled_text(
        lambda q, k, v: taylor_direct_attention(q, k, v, causal=causal,
                                                interpret=False), *qkv)
    assert "tpu_custom_call" in text
    assert _kernels(text) == ["taylor_dir_fwd"]


def test_direct_grad_compiles(qkv):
    def loss(q, k, v):
        return jnp.sum(taylor_direct_attention_vjp(q, k, v,
                                                   interpret=False) ** 2)
    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), *qkv)
    assert "tpu_custom_call" in text
    assert sorted(_kernels(text)) == ["taylor_dir_bwd_dkv",
                                      "taylor_dir_bwd_dq", "taylor_dir_fwd"]


def test_efficient_forward_compiles(qkv):
    text = _compiled_text(
        lambda q, k, v: taylor_efficient_attention(
            q, k, v, block_q=128, block_k=128, interpret=False), *qkv)
    assert "tpu_custom_call" in text
    assert _kernels(text) == EFFICIENT_FWD


def test_efficient_grad_compiles(qkv):
    def loss(q, k, v):
        return jnp.sum(taylor_efficient_attention_vjp(q, k, v,
                                                      interpret=False) ** 2)
    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), *qkv)
    assert "tpu_custom_call" in text
    # the backward recomputes A_mod under the forward's name
    assert sorted(_kernels(text)) == sorted(
        EFFICIENT_FWD + ["taylor_eff_fwd_amod"] + EFFICIENT_BWD)


def test_efficient_grad_under_remat_runs_eight_kernels(qkv):
    """As a rematerialised layer trains it: forward, recomputed forward,
    backward. The backward's A_mod, which keeps the forward's name, merges
    with the recomputed forward's: a name of its own would make it a
    ninth launch."""
    attn = jax.checkpoint(lambda q, k, v: taylor_efficient_attention_vjp(
        q, k, v, interpret=False))

    def loss(q, k, v):
        return jnp.sum(attn(q, k, v) ** 2)
    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), *qkv)
    assert sorted(_kernels(text)) == sorted(2 * EFFICIENT_FWD
                                            + EFFICIENT_BWD)


@pytest.mark.parametrize("site", ["dq", "dk"])
def test_sym_quad_kernel_compiles(one_chip, site):
    """The backward's symmetric-quadratic pass alone: the ⊠ product of u
    and x built in VMEM, one contraction against the re-laid-out A."""
    name = f"taylor_eff_bwd_symquad_{site}"
    text = _compiled_text(
        lambda x, u, a: _sym_quad_call(x, u, a, cf=_pick_chunk_factor(D),
                                       block_q=128, interpret=False,
                                       name=name),
        _spec((BH, N, D), one_chip), _spec((BH, N, D + 1), one_chip),
        _spec((BH, D + 1, D * D), one_chip))
    assert _kernels(text) == [name]


@pytest.mark.parametrize("name", ["taylor_eff_fwd_amod",
                                  "taylor_eff_bwd_amod_da"])
def test_amod_kernel_compiles(one_chip, name):
    """Phase A alone: the ⊠ product of x with itself built in VMEM with
    tokens in lanes, contracted with ûᵀ over the lanes of both."""
    text = _compiled_text(
        lambda x, u: _amod_call(x, u, cf=_pick_chunk_factor(D), block_k=128,
                                interpret=False, name=name),
        _spec((BH, N, D), one_chip), _spec((BH, N, D + 1), one_chip))
    assert _kernels(text) == [name]


@pytest.mark.parametrize("name,divide", [("taylor_eff_fwd_readout", True),
                                         ("taylor_eff_fwd_readout", False),
                                         ("taylor_eff_bwd_readout_dv", False)])
def test_readout_kernel_compiles(one_chip, name, divide):
    """Phase B alone, divided (the forward entry) and raw (the custom-VJP
    forward and the dV̂ backward): A_modᵀ against the ⊠ product of the
    queries, queries in lanes."""
    text = _compiled_text(
        lambda x, a, kv, s0: _readout_call(
            x, a, kv, s0, cf=_pick_chunk_factor(D), block_q=128, n_keys=N,
            out_scale=divide, out_dtype=jnp.float32, interpret=False,
            name=name, divide=divide),
        _spec((BH, N, D), one_chip), _spec((BH, D + 1, D * D), one_chip),
        _spec((BH, D, D + 1), one_chip), _spec((BH, 1, D + 1), one_chip))
    assert _kernels(text) == [name]


def test_decode_kernel_compiles(one_chip):
    state = T.TaylorState(s2=_spec((BH, D * D, D + 1), one_chip),
                          s1=_spec((BH, D, D + 1), one_chip),
                          s0=_spec((BH, 1, D + 1), one_chip),
                          n=_spec((BH,), one_chip, jnp.int32))
    q, k, v = (_spec((BH, 1, D), one_chip) for _ in range(3))
    text = _compiled_text(
        lambda st, q, k, v: taylor_decode_kernel(st, q, k, v,
                                                 interpret=False),
        state, q, k, v)
    assert "tpu_custom_call" in text
    assert _kernels(text) == ["taylor_decode"]
