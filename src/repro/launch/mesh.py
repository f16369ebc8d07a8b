"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches JAX device state — the dry-run entrypoint sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any
JAX import; tests and benches see the real single device.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType


def _mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules
    (distributed/sharding.py) annotate arrays and let GSPMD propagate,
    which ``Explicit`` axes (``make_mesh``'s default) refuse."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, seq: int = 1,
                         pipe: int = 1):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    ``seq > 1`` carves a sequence-parallel (context-parallel) axis out of
    the data axis: long-context cells trade data parallelism for
    sharding the token axis, so the causal Taylor scan (and the
    activations) split over ``seq`` (distributed/seqscan.py,
    docs/sharding.md). ``seq == 1`` keeps the historical 2-/3-axis mesh
    so existing sweeps and their result files stay comparable.

    ``pipe > 1`` carves a pipeline axis out of the data axis as well and
    switches to the composed training layout: a single
    ``(data, pipe, seq)`` mesh (no ``model`` axis — the composed path in
    distributed/composed.py shards parameters with FSDP over ``data``
    instead of tensor parallelism, so all 256/512 chips go to
    batch × stages × context).
    """
    chips = 512 if multi_pod else 256
    if pipe > 1:
        if chips % (pipe * seq):
            raise ValueError(
                f"pipe={pipe} × seq={seq} must divide the {chips}-chip pod")
        return _mesh((chips // (pipe * seq), pipe, seq),
                     ("data", "pipe", "seq"))
    if seq == 1:
        shape = (2, 16, 16) if multi_pod else (16, 16)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
        return _mesh(shape, axes)
    if 16 % seq:
        raise ValueError(f"seq={seq} must divide the 16-way data axis")
    shape = (2, 16 // seq, seq, 16) if multi_pod else (16 // seq, seq, 16)
    axes = (("pod", "data", "seq", "model") if multi_pod
            else ("data", "seq", "model"))
    return _mesh(shape, axes)


def make_local_mesh():
    """Whatever this host has — used by tests/examples."""
    n = len(jax.devices())
    return _mesh((n, 1), ("data", "model"))


def make_seq_mesh(seq: int | None = None):
    """A (data, seq, model) mesh with every local device on the ``seq``
    axis — the layout the multi-device CI job
    (``XLA_FLAGS=--xla_force_host_platform_device_count=8``) and the
    context-parallel benchmarks exercise."""
    n = len(jax.devices())
    seq = seq or n
    if n % seq:
        raise ValueError(f"seq={seq} must divide the device count {n}")
    return _mesh((n // seq, seq, 1), ("data", "seq", "model"))


def make_composed_mesh(*, data: int | None = None, pipe: int = 1,
                       seq: int = 1):
    """A ``(data, pipe, seq)`` mesh over this host's devices — the
    composed 3D-parallel training layout (distributed/composed.py).
    ``data=None`` soaks up whatever devices remain after pipe × seq."""
    n = len(jax.devices())
    if n % (pipe * seq):
        raise ValueError(
            f"pipe={pipe} × seq={seq} must divide the device count {n}")
    data = data if data is not None else n // (pipe * seq)
    if data * pipe * seq > n:
        raise ValueError(
            f"mesh ({data}, {pipe}, {seq}) needs {data * pipe * seq} "
            f"devices, host has {n}")
    devs = jax.devices()[:data * pipe * seq]
    return jax.sharding.Mesh(
        np.asarray(devs).reshape(data, pipe, seq), ("data", "pipe", "seq"))


def seq_size(mesh) -> int:
    """Size of the sequence-parallel axis (1 when the mesh has none)."""
    return mesh.shape["seq"] if "seq" in mesh.axis_names else 1


def pipe_size(mesh) -> int:
    """Size of the pipeline axis (1 when the mesh has none)."""
    return mesh.shape["pipe"] if "pipe" in mesh.axis_names else 1


def dp_axes(mesh) -> tuple[str, ...]:
    """The data-parallel (batch) axes of a mesh."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def axis_size(mesh, *names) -> int:
    s = 1
    for n in names:
        if n in mesh.axis_names:
            s *= mesh.shape[n]
    return s
