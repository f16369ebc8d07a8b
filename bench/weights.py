"""Seeded random weights, made on the device in one jitted call.

The tree's structure and dtypes are the program's own (its parameter
shapes, read with ``jax.eval_shape``); the values are the benchmark's,
drawn from the seed by the rule below, so the plain references read the
same numbers and nothing the program made:

- ``w`` (a dense matrix, stored ``(..., fan_in, fan_out)``):
  N(0, 1/fan_in);
- ``emb`` (embedding, and the read-out where it is tied):
  N(0, 1/d_model);
- ``pos`` (learned positions): N(0, 0.02^2);
- ``scale`` / ``bias`` (LayerNorm): 1 / 0;
- ``tau`` (Taylor temperature): 1.
"""

from __future__ import annotations

import math
import jax
import jax.numpy as jnp
import numpy as np

from bench.traffic_gen import seed_sequence


def prng_key(seed: int, salt: int):
    """A jax key from any non-negative whole number and a salt."""
    word = seed_sequence(seed, salt).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def _leaf(name: str, key, shape, dtype, d_model: int):
    if name == "scale":
        return jnp.ones(shape, dtype)
    if name in ("bias",):
        return jnp.zeros(shape, dtype)
    if name == "tau":
        return jnp.ones(shape, dtype)
    if name == "w":
        std = 1.0 / math.sqrt(shape[-2])
    elif name == "emb":
        std = 1.0 / math.sqrt(d_model)
    elif name == "pos":
        std = 0.02
    else:
        raise ValueError(f"no initialisation rule for parameter {name!r}")
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def make_params(shapes, *, seed: int, d_model: int, sharding=None):
    """Fill the parameter tree ``shapes`` (ShapeDtypeStructs) from ``seed``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [path[-1].key for path, _ in flat]

    def build(key):
        leaves = [_leaf(name, jax.random.fold_in(key, i), s.shape, s.dtype,
                        d_model)
                  for i, (name, (_, s)) in enumerate(zip(names, flat))]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build, out_shardings=sharding)(prng_key(seed, 2))


def to_host(tree) -> list[np.ndarray]:
    """Leaves of a tree as float32 host arrays (a copy that survives
    donation of the device buffers)."""
    return [np.asarray(jax.device_get(x), np.float32)
            for x in jax.tree.leaves(tree)]
