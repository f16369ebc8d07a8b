"""Seeded random weights, made on the device in one jitted call.

The tree's structure and dtypes are the program's own (its parameter
shapes, read with ``jax.eval_shape``); the values are the benchmark's,
drawn from the seed by the rule below, so the plain references read the
same numbers and nothing the program made. The built-in rules, by leaf
name:

- ``w`` (a dense matrix, stored ``(..., fan_in, fan_out)``):
  N(0, 1/fan_in);
- ``emb`` (embedding, and the read-out where it is tied):
  N(0, 1/d_model);
- ``pos`` (learned positions): N(0, 0.02^2);
- ``scale`` / ``bias`` (LayerNorm): 1 / 0;
- ``tau`` (Taylor temperature): 1.

Any other leaf takes its rule from the configuration file's ``init``
section, keyed by leaf name (a built-in name there raises, and so does a
leaf with no rule):

- ``{"rule": "fan_in_normal"}``: N(0, 1/fan_in), fan-in ``shape[-2]``;
- ``{"rule": "normal", "std": s}``: N(0, s^2);
- ``{"rule": "ones"}``, ``{"rule": "zeros"}``,
  ``{"rule": "constant", "value": c}``;
- ``{"rule": "log_linspace", "lo": a, "hi": b}``: log of ``shape[-1]``
  points evenly spaced from a to b, the same in every layer (Mamba2's
  per-head ``A_log`` from log 1 to log 16).

Leaf ``i`` of the flattened tree draws from the seed's key folded with
``i``, whatever its rule.
"""

from __future__ import annotations

import math
import jax
import jax.numpy as jnp
import numpy as np

from bench.traffic_gen import seed_sequence

BUILT_IN = ("w", "emb", "pos", "scale", "bias", "tau")


def prng_key(seed: int, salt: int):
    """A jax key from any non-negative whole number and a salt."""
    word = seed_sequence(seed, salt).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def _normal(key, shape, dtype, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _built_in(name: str, key, shape, dtype, d_model: int):
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
    else:
        std = 0.02
    return _normal(key, shape, dtype, std)


def _configured(rule: dict, key, shape, dtype):
    kind = rule["rule"]
    if kind == "fan_in_normal":
        return _normal(key, shape, dtype, 1.0 / math.sqrt(shape[-2]))
    if kind == "normal":
        return _normal(key, shape, dtype, rule["std"])
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
    if kind == "constant":
        return jnp.full(shape, rule["value"], dtype)
    if kind == "log_linspace":
        row = jnp.log(jnp.linspace(rule["lo"], rule["hi"], shape[-1],
                                   dtype=jnp.float32))
        return jnp.broadcast_to(row, shape).astype(dtype)
    raise ValueError(f"unknown initialisation rule {kind!r}")


def _leaf(name: str, key, shape, dtype, d_model: int, init: dict):
    if name in BUILT_IN:
        return _built_in(name, key, shape, dtype, d_model)
    if name not in init:
        raise ValueError(f"no initialisation rule for parameter {name!r}")
    return _configured(init[name], key, shape, dtype)


def make_params(shapes, *, seed: int, d_model: int, init: dict | None = None,
                sharding=None):
    """Fill the parameter tree ``shapes`` (ShapeDtypeStructs) from
    ``seed``; ``init`` is the configuration's rules for leaves the
    built-in table does not name."""
    init = init or {}
    overridden = sorted(set(init) & set(BUILT_IN))
    if overridden:
        raise ValueError(f"built-in initialisation rules cannot be "
                         f"overridden: {overridden}")
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [path[-1].key for path, _ in flat]

    def build(key):
        leaves = [_leaf(name, jax.random.fold_in(key, i), s.shape, s.dtype,
                        d_model, init)
                  for i, (name, (_, s)) in enumerate(zip(names, flat))]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build, out_shardings=sharding)(prng_key(seed, 2))


def to_host(tree) -> list[np.ndarray]:
    """Leaves of a tree as float32 host arrays (a copy that survives
    donation of the device buffers)."""
    return [np.asarray(jax.device_get(x), np.float32)
            for x in jax.tree.leaves(tree)]
