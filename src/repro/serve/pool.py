"""Slot-based decode-state pool (Taylor states, and Mamba2 state beside
them in a hybrid).

Preallocates the model's whole decode cache with a leading slot
dimension — for a TaylorShift model that is
``(layers, slots, kv_heads, 1, d², d+1)`` per layer group — plus
per-slot position counters. Because every slot is constant-size,
sequences join and leave the running batch by gather/scatter on the
pytree: no paged blocks, no reallocation, no recompilation, and decode
memory that never grows with context length.

Slot lifecycle: ``alloc`` (admission) → ``scatter`` (prefill finished,
single-sequence state dropped into the slot) → ``release`` (zero-reset,
back on the free list).

Because a Taylor slot is constant-size — O(layers · d²) sums plus
counters, independent of context length — a full copy of a slot's state
is as cheap as one decode step's state update. ``snapshot``/``restore``
expose that as the rollback primitive speculative decoding builds on
(src/repro/spec/, docs/design.md): snapshot before scoring drafted
tokens, restore when the drafts are rejected. jax arrays are immutable,
so a snapshot is simply the gathered sub-pytree — it can never be
corrupted by later pool updates, and restore is one scatter. (With
``cache_kind="kv"`` — the "and Back" regime below the N1 crossover —
a slot copy is O(layers · cache_len · d) instead: still one gather,
but growing with ``max_seq_len``; the constant-cost claim is the
Taylor state's.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import taylor as T
from repro.models import model as M


class StatePool:
    """Slot pool over one ``init_decode_state(per_slot=True)`` pytree.

    Contract: ``self.cache`` is the only mutable reference — every
    method that "mutates" a slot rebinds it to a functionally-updated
    pytree, so any pytree previously handed out (``gather``/
    ``snapshot`` results, prefix-cache entries, the pre-verify
    speculative snapshot) is immutable and stays bit-exact forever.
    ``alloc``/``release`` manage the free list only; state movement is
    ``scatter`` (overwrites *every* leaf of a slot — a recycled slot
    carries no trace of its previous occupant) and ``gather``. Byte
    accounting via ``nbytes()`` matches what the prefix cache charges
    per single-sequence entry times ``n_slots``.
    """

    def __init__(self, cfg: ModelConfig, n_slots: int, *, cache_len: int,
                 cache_kind: str = "taylor", dtype=jnp.float32):
        if n_slots < 1:
            raise ValueError("need at least one slot")
        self.cfg = cfg
        self.n_slots = n_slots
        self.cache_len = cache_len
        self.cache_kind = cache_kind
        self.dtype = dtype
        self.cache = M.init_decode_state(cfg, n_slots, cache_len=cache_len,
                                         cache_kind=cache_kind, dtype=dtype,
                                         per_slot=True)
        self._free = list(range(n_slots - 1, -1, -1))
        self._scatter = jax.jit(M.cache_scatter_slot)
        self._reset = jax.jit(M.cache_reset_slot)
        self._gather = jax.jit(M.cache_gather_slot)

    # -- slot bookkeeping ---------------------------------------------------

    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def occupancy(self) -> float:
        return 1.0 - len(self._free) / self.n_slots

    def alloc(self) -> int:
        if not self._free:
            raise RuntimeError("no free slot")
        return self._free.pop()

    def release(self, slot: int) -> None:
        """Zero the slot's state and return it to the free list. The
        zero-reset is hygiene, not correctness: a later ``scatter``
        overwrites every leaf of the slot anyway."""
        self.reset(slot)
        self._free.append(slot)

    def reset(self, slot: int) -> None:
        """Zero one slot *without* freeing it — for shadow pools (e.g.
        the self-drafter's) whose slot indices mirror this pool's and
        are not independently allocated."""
        self.cache = self._reset(self.cache, slot)

    # -- state movement -----------------------------------------------------

    def new_sequence_cache(self):
        """Private batch=1 cache a sequence prefills into before joining
        the pool (same cache_len so leaves scatter shape-exactly)."""
        return M.init_decode_state(self.cfg, 1, cache_len=self.cache_len,
                                   cache_kind=self.cache_kind,
                                   dtype=self.dtype)

    def scatter(self, src_cache, slot: int) -> None:
        self.cache = self._scatter(self.cache, src_cache, slot)

    def gather(self, slot: int):
        return self._gather(self.cache, slot)

    # -- snapshot / rollback (speculative decoding, repro.spec) -------------
    #
    # Thin rollback-facing names over gather/scatter — ONE underlying
    # slot-copy path. A snapshot is bit-exact for every leaf (state
    # sums / kv rows / pos counters) and immutable, so it survives any
    # number of pool mutations; restore makes the slot bit-identical to
    # snapshot time (tests/test_spec.py pins the round-trip). Cost is
    # O(layers · d²) for Taylor slots — context-length-independent —
    # and O(layers · cache_len · d) for kv slots.

    def snapshot(self, slot: int):
        return self.gather(slot)

    def restore(self, slot: int, snap) -> None:
        self.scatter(snap, slot)

    def nbytes(self) -> int:
        from repro.serve.prefix_cache import tree_nbytes
        return tree_nbytes(self.cache)

    def state_bytes(self) -> dict[str, int]:
        """Bytes of the pool's recurrent state by kind: ``ssm`` (Mamba2's
        state h), ``conv`` (its conv tails) and ``taylor`` (the Taylor
        states of the attention layers). A decode step reads all of it."""
        from repro.serve.prefix_cache import tree_nbytes
        out = {"ssm": 0, "conv": 0, "taylor": 0}

        def walk(t):
            if isinstance(t, T.TaylorState):
                out["taylor"] += tree_nbytes(t)
            elif isinstance(t, dict) and set(t) == {"conv", "h"}:
                out["conv"] += tree_nbytes(t["conv"])
                out["ssm"] += tree_nbytes(t["h"])
            elif isinstance(t, dict):
                for v in t.values():
                    walk(v)
            elif isinstance(t, (list, tuple)):
                for v in t:
                    walk(v)

        walk(self.cache)
        return out
