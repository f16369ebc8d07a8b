"""The continuous-batching inference engine.

Fixed shapes everywhere: decode always runs the full slot batch
(inactive slots compute on throwaway state and are ignored), prefill
runs per-sequence at a bounded set of chunk lengths — so after warmup
no step ever recompiles. Sequences at different context lengths share
decode batches thanks to the per-slot position counters
(``init_decode_state(per_slot=True)``).

Speculative decoding (``speculate_k > 0``, src/repro/spec/): instead of
one token per step, a drafter proposes k tokens per decoding slot, one
batched ``verify_chunk`` call scores all k+1 from each slot's current
Taylor state, and the longest argmax-matching prefix (plus one bonus
token) is emitted. Slots whose drafts are rejected roll back through
``StatePool.snapshot/restore`` — O(d²) regardless of context length —
and re-absorb just the accepted prefix. Greedy output is bit-identical
to the one-token-per-step engine; only throughput changes.

Typical use::

    eng = Engine(cfg, params, EngineConfig(n_slots=4))
    eng.submit(Request("a", prompt, max_new_tokens=16))
    for ev in eng.run():            # streams TokenEvents
        ...
    eng.results["a"].out_tokens
"""

from __future__ import annotations

import time
import zlib
from dataclasses import dataclass, field
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, PrefixCacheConfig, SpecConfig
from repro.models import backend as B
from repro.models import model as M
from repro.models.model import PREFILL_KINDS
from repro.obs import metrics as OM
from repro.obs.trace import tracer
from repro.serve import prefill as PF
from repro.serve.pool import StatePool
from repro.serve.prefix_cache import PrefixCache
from repro.serve.request import (AdmissionQueue, Request, Sequence,
                                 SequenceStatus, TokenEvent)
from repro.serve.scheduler import EngineStats, Scheduler, StepMetrics


@dataclass
class EngineConfig:
    """Engine-level knobs; one instance per :class:`Engine`.

    Contract highlights: ``max_seq_len`` bounds prompt + generation per
    request (kv pools preallocate it; Taylor slots are size-invariant);
    ``token_budget`` is the per-step scheduled-token ceiling that
    decode, speculative drafts and prefill chunks all draw from;
    ``cache_kind="auto"`` resolves through the paper's N1 memory
    crossover (models/backend.py:select_serve_plan);
    ``prefix_cache_mb > 0`` enables the shared-prefix state cache
    (serve/prefix_cache.py) with that byte budget — hits charge only
    the un-cached suffix against the token budget and never change
    emitted tokens (bit-identical streams, cache on or off).
    """
    n_slots: int = 4             # max sequences decoding concurrently
    max_queue: int = 64          # admission backpressure threshold
    prefill_chunk: int = 128     # target prompt tokens per prefill call
    token_budget: int = 256      # scheduled tokens per engine step
    max_seq_len: int = 2048      # pool cache_len (kv caches only grow to this)
    cache_kind: str = "taylor"   # taylor | kv | auto ("and Back" via the
    #   N1 memory crossover — models/backend.py:select_serve_plan)
    temperature: float = 0.0     # default; Request.temperature overrides
    seed: int = 0
    speculate_k: int = 0         # max draft length; 0 = no speculation
    spec: SpecConfig = field(default_factory=SpecConfig)
    batch_prefill: bool = True   # pool-resident batched prefill: group
    #   same-chunk-length prefilling sequences into ONE pool-level
    #   dispatch per step (Taylor pools only — the per-slot body is
    #   bit-identical to the scalar one there; kv pools keep the
    #   per-sequence path)
    prefix_cache_mb: float = 0.0  # shared-prefix cache byte budget in MB
    #   (0 = cache off; <0 = on, unbounded)
    prefix: PrefixCacheConfig = field(default_factory=PrefixCacheConfig)
    replica_id: str | None = None  # fleet identity: ONE name threaded
    #   through obs snapshots, ft.Membership and the router (serve/
    #   router.py) — None = single-replica deployment


def _filter_logits(lg: jnp.ndarray, top_k: int, top_p: float) -> jnp.ndarray:
    """Apply top-k then nucleus (top-p) filtering to one logits row.

    top-k keeps the k largest logits; top-p keeps the smallest
    probability-sorted prefix whose cumulative mass reaches ``top_p``
    (the first token always survives, so sampling is never empty).
    Filtered entries go to -inf — ``jax.random.categorical`` assigns
    them zero probability.
    """
    if top_k > 0:
        kth = jnp.sort(lg)[-min(top_k, lg.shape[-1])]
        lg = jnp.where(lg < kth, -jnp.inf, lg)
    if top_p < 1.0:
        order = jnp.argsort(-lg)
        probs = jax.nn.softmax(lg[order])
        keep_sorted = jnp.cumsum(probs) - probs < top_p
        keep = jnp.zeros_like(keep_sorted).at[order].set(keep_sorted)
        lg = jnp.where(keep, lg, -jnp.inf)
    return lg


class Engine:
    """The continuous-batching engine: submit ``Request``s, drive
    ``step()``/``run()``, drain ``results``.

    Contract: emitted token streams are a pure function of (params,
    ModelConfig, Request, EngineConfig.temperature/seed) — independent
    of batching, arrival order, speculation (``speculate_k``) and the
    shared-prefix cache (``prefix_cache_mb``), all of which only move
    throughput and latency. Greedy streams are bit-identical across
    those knobs; sampled streams are reproducible per (seed,
    request_id, token index). All pool mutation happens inside
    ``step()``; snapshots handed out (speculative rollback, prefix-cache
    entries) are immutable jax pytrees and can never observe later
    engine state.
    """

    def __init__(self, cfg: ModelConfig, params, econf: EngineConfig | None = None):
        econf = econf or EngineConfig()
        bad = [k for k in cfg.layer_pattern if k not in PREFILL_KINDS]
        if bad or cfg.family == "encdec":
            raise NotImplementedError(
                "serve engine: chunked prefill supports decoder "
                f"architectures of {PREFILL_KINDS} layers (pattern "
                f"{tuple(cfg.layer_pattern)})")
        if "mamba" in cfg.layer_pattern:
            # an SSM state is a running product, not a sum of per-token
            # terms: nothing takes drafted tokens back out of it
            if econf.speculate_k > 0:
                raise ValueError(
                    "speculate_k > 0 with Mamba2 layers: verify's rollback "
                    "cannot un-absorb SSM state")
            if econf.prefix_cache_mb:
                raise ValueError(
                    "prefix_cache_mb != 0 with Mamba2 layers: the prefix "
                    "cache keeps no entries of SSM state")
        self.cfg = cfg
        self.econf = econf
        self.replica_id = econf.replica_id
        # One routing decision for the whole engine: cache layout
        # (resolving cache_kind="auto" through the paper's N1 memory
        # crossover) plus the prefill/decode path selections the
        # attention layers will re-derive identically at trace time.
        self.plan = B.select_serve_plan(
            cfg, max_seq_len=econf.max_seq_len,
            prefill_chunk=econf.prefill_chunk,
            cache_kind=econf.cache_kind,
            speculate_k=econf.speculate_k)
        # kv caches need k rows of headroom: a verify block written at the
        # final context position overshoots max_seq_len by up to k before
        # the rollback trims it (Taylor slots are size-invariant anyway)
        cache_len = econf.max_seq_len + max(econf.speculate_k, 0)
        self.pool = StatePool(cfg, econf.n_slots,
                              cache_len=cache_len,
                              cache_kind=self.plan.cache_kind)
        # recurrent state each dispatched program reads, as span args:
        # the whole pool (decode, pooled prefill) or one slot's share
        self._state_by_kind = by_kind = self.pool.state_bytes()
        self._pool_state = {
            "ssm_state_bytes": by_kind["ssm"] + by_kind["conv"],
            "taylor_state_bytes": by_kind["taylor"]}
        self._slot_state = {k: v // econf.n_slots
                            for k, v in self._pool_state.items()}
        self.queue = AdmissionQueue(econf.max_queue)
        self.stats = EngineStats()
        self.scheduler = Scheduler(econf.token_budget)
        self.scheduler.bind_registry(self.stats.registry)
        self._publish_pool_state()
        # shared-prefix state cache: entries are immutable snapshots of
        # the chunked-prefill cache at full-chunk boundaries, so a hit
        # is a zero-copy resume (serve/prefix_cache.py). Keyed on the
        # engine's own prefill chunk — the granularity that keeps
        # cache-hit streams bit-identical to cold prefill.
        self.prefix_cache: PrefixCache | None = None
        if econf.prefix_cache_mb:
            if econf.prefix.chunk_tokens not in (0, econf.prefill_chunk):
                # any other granularity lets power-of-two *tail* chunks
                # land on the trie grid, and a hit would then resume
                # with a chunk decomposition no cold prefill runs —
                # breaking the bit-identity contract
                raise ValueError(
                    f"prefix.chunk_tokens={econf.prefix.chunk_tokens} "
                    f"must equal prefill_chunk={econf.prefill_chunk} "
                    "(or 0 to follow it)")
            budget = int(econf.prefix_cache_mb * 1024 * 1024) \
                if econf.prefix_cache_mb > 0 else 0
            self.prefix_cache = PrefixCache(
                econf.prefill_chunk,
                budget_bytes=budget, max_entries=econf.prefix.max_entries,
                # kv rows are positionally addressed, so entries can be
                # truncated to any matching token depth (partial-prefix
                # hits); Taylor prefix sums cannot
                kv_partial=(self.plan.cache_kind == "kv"))
        self.sequences: dict[str, Sequence] = {}
        self.results: dict[str, Sequence] = {}
        self._slots: list[Sequence | None] = [None] * econf.n_slots
        self._step_idx = 0
        self._rng = jax.random.PRNGKey(econf.seed)
        # params travel as a jit *argument* (not a closure capture) so
        # the weights aren't baked into the jaxpr as constants
        self._params = params
        # Pool-resident batched prefill is gated on the Taylor cache
        # kind: for Taylor states the per-slot-counter prefill body is
        # bit-identical to the scalar one (rows are computationally
        # independent), so pooling cannot change any stream; kv caches
        # attend over a different extent per body and stay per-sequence.
        self._batch_prefill = (econf.batch_prefill
                               and self.plan.cache_kind == "taylor")
        prefill_jit = jax.jit(
            lambda p, toks, cache: M.prefill_from_state(p, cfg,
                                                        {"tokens": toks},
                                                        cache))
        if self._batch_prefill:
            # Partially-prefilled state now lives in pool slots between
            # steps, so whole-pool writers (decode/verify) must merge
            # through a slot mask — unselected live slots keep their
            # state bit-exactly instead of absorbing throwaway tokens.
            def _masked(fn):
                def run(p, toks, mask, cache):
                    lg, nc = fn(p, cfg, {"tokens": toks}, cache)
                    return lg, M.cache_merge_slots(mask, nc, cache)
                return run
            decode_jit = jax.jit(_masked(M.decode_step))
            verify_jit = jax.jit(_masked(M.verify_chunk))
            pool_prefill_jit = jax.jit(
                lambda p, toks, mask, cache: M.prefill_slots(
                    p, cfg, {"tokens": toks}, cache, mask))
            self._pool_prefill_fn = lambda toks, mask, cache: \
                pool_prefill_jit(self._params, toks, mask, cache)
            # singleton groups bypass the full-batch dispatch: one
            # gathered slot, batch-1 per-slot body, same bits. The
            # gather -> prefill -> scatter round trip is fused into a
            # single jit (slot index is a traced argument) so a
            # singleton chunk costs exactly one dispatch, like the
            # private-cache path; the canonical sequence-layout state
            # comes back too, ready for a prefix-cache insert.
            def _slot_prefill(p, toks, cache, slot):
                sub = M.cache_gather_slot(cache, slot)
                logits, sub = M.prefill_from_state(
                    p, cfg, {"tokens": toks}, sub)
                return (logits, M.cache_scatter_slot(cache, sub, slot),
                        M.cache_slot_to_sequence(sub))
            slot_prefill_jit = jax.jit(_slot_prefill)
            self._slot_prefill_fn = lambda toks, cache, slot: \
                slot_prefill_jit(self._params, toks, cache, slot)
        else:
            decode_jit = jax.jit(
                lambda p, toks, mask, cache: M.decode_step(
                    p, cfg, {"tokens": toks}, cache))
            verify_jit = jax.jit(
                lambda p, toks, mask, cache: M.verify_chunk(
                    p, cfg, {"tokens": toks}, cache))
            self._pool_prefill_fn = None
            self._slot_prefill_fn = None
        rollback_jit = jax.jit(
            lambda p, cache, snap, slot, toks: M.verify_rollback(
                p, cfg, cache, snap, slot, {"tokens": toks}))
        self._prefill_fn = lambda toks, cache: prefill_jit(
            self._params, toks, cache)
        self._decode_fn = lambda toks, mask, cache: decode_jit(
            self._params, toks, mask, cache)
        self._verify_fn = lambda toks, mask, cache: verify_jit(
            self._params, toks, mask, cache)
        self._rollback_fn = lambda cache, snap, slot, toks: rollback_jit(
            self._params, cache, snap, slot, toks)
        # speculative machinery (lazy import: repro.spec builds on the
        # pool/prefill layers of this package)
        self.drafter = None
        self._controller = None
        if econf.speculate_k > 0:
            from repro.spec.controller import DraftController
            from repro.spec.drafter import make_drafter
            self.drafter = make_drafter(
                cfg, params, n_slots=econf.n_slots, cache_len=cache_len,
                cache_kind=self.plan.cache_kind, spec=econf.spec,
                prefill_chunk=econf.prefill_chunk)
            self._controller = DraftController(econf.speculate_k, econf.spec,
                                              registry=self.stats.registry)

    # ------------------------------------------------------------------
    # Submission
    # ------------------------------------------------------------------

    def submit(self, request: Request) -> Sequence:
        """Enqueue a request. Raises QueueFullError under backpressure."""
        if (request.request_id in self.sequences
                or request.request_id in self.results):
            raise ValueError(f"duplicate request_id {request.request_id!r}")
        if len(request.prompt) + request.max_new_tokens > self.econf.max_seq_len:
            raise ValueError("prompt + max_new_tokens exceeds max_seq_len")
        seq = Sequence(request=request)
        self.queue.push(seq)
        self.sequences[request.request_id] = seq
        return seq

    @property
    def idle(self) -> bool:
        return self.queue.depth == 0 and all(s is None for s in self._slots)

    @property
    def step_idx(self) -> int:
        """Number of scheduler steps taken (public: arrival schedules and
        tests key on this)."""
        return self._step_idx

    def reset_metrics(self) -> None:
        """Fresh ``EngineStats`` (with a fresh metrics registry) and
        draft controller. For warm/timed benchmark pairs: the adaptive
        controller's draft length follows its acceptance history, so
        without a reset the timed run would take a different k
        trajectory than the warmup (and recompile verify shapes
        mid-measurement).

        The prefix cache's lifetime registry is NOT reset (the counters
        describe state that survives); instead its current counter
        values become the baseline for the summary's
        ``prefix_cache.since_reset`` sub-dict, so post-reset summaries
        are self-consistent. Purely observational either way — resets
        never change emitted tokens."""
        self.stats = EngineStats()
        self.scheduler.bind_registry(self.stats.registry)
        self._publish_pool_state()
        if self.prefix_cache is not None:
            self.stats.prefix_cache_baseline = self.prefix_cache.stats()
        if self._controller is not None:
            from repro.spec.controller import DraftController
            self._controller = DraftController(self.econf.speculate_k,
                                               self.econf.spec,
                                               registry=self.stats.registry)

    def _publish_pool_state(self) -> None:
        g = self.stats.registry.gauge(
            "serve_pool_state_bytes",
            "bytes of the slot pool's recurrent state, by kind",
            labelnames=("kind",))
        for kind, n in self._state_by_kind.items():
            g.labels(kind=kind).set(n)

    def render_metrics(self) -> str:
        """Prometheus text exposition over every registry the engine
        owns: the resettable stats registry plus the prefix cache's
        lifetime registry (``launch/serve.py --metrics-file/-port``)."""
        regs = [self.stats.registry]
        if self.prefix_cache is not None:
            regs.append(self.prefix_cache.registry)
        return OM.render_all(*regs)

    def snapshot_metrics(self, *, replica: str | None = None) -> dict:
        """Versioned ``repro.obs/v1`` snapshot of every engine registry
        (``launch/serve.py --metrics-snapshot``). Unlike the rendered
        exposition this is mergeable: the fleet aggregator
        (``python -m repro.obs --merge-snapshots``) folds N replicas'
        snapshots into one exposition whose counters are the fleet sums
        and whose gauges keep a per-``replica`` label.

        ``replica`` defaults to ``EngineConfig.replica_id`` — the ONE
        identity the router, membership and obs agree on; the override
        exists for tooling that relabels snapshots after the fact."""
        from repro.obs import aggregate as OA
        regs = [self.stats.registry]
        if self.prefix_cache is not None:
            regs.append(self.prefix_cache.registry)
        if replica is None:
            replica = self.replica_id
        return OA.snapshot(*regs, replica=replica)

    def pop_result(self, request_id: str) -> Sequence:
        """Drain one finished sequence. ``results`` retains finished
        sequences until popped — long-running callers must drain (and may
        then reuse the request_id), or memory grows with requests served."""
        return self.results.pop(request_id)

    # ------------------------------------------------------------------
    # Live migration (serve/wire.py + serve/router.py)
    # ------------------------------------------------------------------
    #
    # A decoding stream is its slot snapshot plus the request and the
    # tokens emitted so far — O(layers·d²) bytes for Taylor slots,
    # independent of context (the paper's asset; ROADMAP "fleet-scale
    # serving"). Migration happens only at step boundaries: between
    # steps the slot state has absorbed exactly prompt + out_tokens[:-1]
    # (the last emitted token is the *next* decode feed), so a peer
    # restoring the snapshot continues the stream with the same float
    # ops a non-migrated engine would run — bit-identical tokens.
    # Sampling survives too: keys are derived from (engine seed,
    # request_id, token index), none of which move with the machine.

    def _fingerprint(self) -> dict:
        """What the importing engine must agree on for the continued
        stream to be bit-identical to an unmigrated run."""
        return {"model": {"name": self.cfg.name,
                          "n_layers": self.cfg.n_layers,
                          "d_model": self.cfg.d_model,
                          "n_heads": self.cfg.n_heads,
                          "vocab": self.cfg.vocab},
                "seed": self.econf.seed,
                "temperature": self.econf.temperature}

    def export_request(self, request_id: str) -> bytes:
        """Drain one decoding stream into a ``repro.state/v1`` wire blob
        and drop it from this engine (slot freed, bookkeeping cleared).

        Only DECODING streams export — WAITING/PREFILLING requests hold
        no state worth shipping (cancel + resubmit replays them
        deterministically), and mid-step there is no boundary to cut at.
        """
        from repro.serve import wire
        seq = self.sequences.get(request_id)
        if seq is None:
            raise KeyError(f"unknown request {request_id!r}")
        if seq.status is not SequenceStatus.DECODING:
            raise ValueError(
                f"request {request_id!r} is {seq.status.value}; only "
                "decoding streams migrate (step-boundary invariant)")
        with tracer.span("migrate_export", request=request_id):
            r = seq.request
            blob = wire.encode_stream(
                self.pool.snapshot(seq.slot),
                request={"request_id": r.request_id,
                         "prompt": [int(t) for t in r.prompt],
                         "max_new_tokens": r.max_new_tokens,
                         "eos_id": r.eos_id, "temperature": r.temperature,
                         "top_k": r.top_k, "top_p": r.top_p},
                out_tokens=seq.out_tokens,
                cache_kind=self.plan.cache_kind,
                cache_len=self.pool.cache_len,
                model=self._fingerprint(), replica=self.replica_id)
        # drain only after the snapshot is safely in the blob
        self._slots[seq.slot] = None
        if self.drafter is not None:
            self.drafter.release(seq.slot)
        self.pool.release(seq.slot)
        seq.slot = None
        del self.sequences[request_id]
        return blob

    def import_request(self, blob: bytes) -> Sequence:
        """Restore a migrated stream from a wire blob and resume
        decoding it here. All-or-nothing: every validation — blob
        integrity (wire.decode), engine compatibility, duplicate id,
        capacity, structural shape/dtype match against this pool's slot
        template — runs *before* a slot is touched, so a refused blob
        leaves the engine bit-exactly as it was (never half-restored).
        """
        from repro.serve import wire
        meta, state = wire.decode_stream(blob)
        req = Request(**meta["request"])
        rid = req.request_id
        out = [int(t) for t in meta["out_tokens"]]
        if rid in self.sequences or rid in self.results:
            raise ValueError(f"duplicate request_id {rid!r}")
        if not out:
            raise wire.WireError(
                "stream blob has no emitted tokens — a decoding stream "
                "always has at least the first token")
        if len(out) >= req.max_new_tokens or out[-1] == req.eos_id:
            raise wire.WireError("stream blob is already finished")
        if len(req.prompt) + req.max_new_tokens > self.econf.max_seq_len:
            raise ValueError("prompt + max_new_tokens exceeds max_seq_len")
        if meta["cache_kind"] != self.plan.cache_kind:
            raise wire.WireError(
                f"blob cache_kind {meta['cache_kind']!r} != engine "
                f"{self.plan.cache_kind!r}")
        if meta["cache_len"] != self.pool.cache_len:
            raise wire.WireError(
                f"blob cache_len {meta['cache_len']} != pool "
                f"{self.pool.cache_len}")
        fp = self._fingerprint()
        if meta.get("model", fp) != fp:
            raise wire.WireError(
                f"engine fingerprint mismatch: blob {meta['model']} vs "
                f"here {fp} — continued stream would not be bit-identical")
        # structural check: the blob's tree must match this pool's slot
        # layout leaf for leaf (shape AND dtype) before any scatter
        template = jax.eval_shape(
            lambda c: M.cache_gather_slot(c, 0), self.pool.cache)
        t_def = jax.tree.structure(template)
        s_def = jax.tree.structure(state)
        if t_def != s_def:
            raise wire.WireError(
                f"blob tree structure {s_def} != slot template {t_def}")
        for i, (want, got) in enumerate(zip(jax.tree.leaves(template),
                                            jax.tree.leaves(state))):
            if want.shape != got.shape or want.dtype != got.dtype:
                raise wire.WireError(
                    f"leaf {i}: blob {got.shape}/{got.dtype} != slot "
                    f"template {want.shape}/{want.dtype}")
        if not self.pool.free_slots:
            raise RuntimeError("no free slot")
        with tracer.span("migrate_import", request=rid):
            slot = self.pool.alloc()
            try:
                self.pool.restore(slot, state)
                seq = Sequence(request=req,
                               status=SequenceStatus.DECODING,
                               slot=slot, out_tokens=out,
                               consumed=len(req.prompt))
                seq.t_first_token = seq.t_submit  # TTFT was paid at the
                #   source; don't re-record it here
                self._slots[slot] = seq
                self.sequences[rid] = seq
                if self.drafter is not None:
                    self.drafter.on_ready(seq)
            except Exception:
                self._slots[slot] = None
                self.pool.release(slot)
                self.sequences.pop(rid, None)
                raise
        return seq

    def cancel(self, request_id: str) -> Request:
        """Abandon a live request (any pre-FINISHED status), free its
        slot if it holds one, and return the Request — the router's
        replay path (failed hard, nothing exportable) resubmits it
        elsewhere; determinism makes the replayed stream identical."""
        seq = self.sequences.get(request_id)
        if seq is None:
            raise KeyError(f"unknown request {request_id!r}")
        if seq.status is SequenceStatus.WAITING:
            self.queue._q.remove(seq)
        else:
            self._slots[seq.slot] = None
            if self.drafter is not None:
                self.drafter.release(seq.slot)
            self.pool.release(seq.slot)
            seq.slot = None
        del self.sequences[request_id]
        return seq.request

    # ------------------------------------------------------------------
    # One scheduler step
    # ------------------------------------------------------------------

    def step(self) -> tuple[StepMetrics, list[TokenEvent]]:
        t0 = time.perf_counter()
        events: list[TokenEvent] = []
        # every phase below is wrapped in an obs span; with the global
        # tracer's buffer off and no profile capturing (the default) each
        # wrapper is two checks returning a shared no-op context — in a
        # captured profile the spans name the host work the device waits
        # on (docs/observability.md)
        step_span = tracer.span("engine_step", step_num=self._step_idx)
        with step_span:

            # 1. admit — waiting sequences take free slots; the prefix
            # cache seeds each new sequence from its longest cached prefix
            cached_tokens = 0
            admitted = 0
            with tracer.span("admit") as adm:
                while self.pool.free_slots and self.queue.depth:
                    seq = self.queue.pop()
                    seq.slot = self.pool.alloc()
                    seq.status = SequenceStatus.PREFILLING
                    self._slots[seq.slot] = seq
                    # per-request child of the batch-level admit span:
                    # the first span of a request's cross-process
                    # timeline (python -m repro.obs --request <id>)
                    with tracer.span("admission",
                                     request=seq.request_id,
                                     slot=seq.slot):
                        with tracer.span("prefix_lookup",
                                         request=seq.request_id) as lk:
                            PF.start_prefill(
                                seq, self.pool,
                                self.econf.prefill_chunk,
                                self.prefix_cache,
                                pool_resident=self._batch_prefill)
                            lk.set("cached_tokens", seq.cached_tokens)
                    cached_tokens += seq.cached_tokens
                    admitted += 1
                adm.set("admitted", admitted)

            with tracer.span("plan"):
                plan = self.scheduler.plan(
                    [s for s in self._slots if s is not None])
            budget = self.scheduler.token_budget

            # 2. one batched decode (or draft+verify) pass for every
            # running sequence. Speculation only pays when at least one
            # decoding row is greedy — sampled rows always reject their
            # drafts, so an all-sampled batch takes the plain decode path
            # (one token per slot, no draft/verify/rollback work, no
            # budget surcharge).
            decode_tokens = decode_charge = 0
            draft_tokens = accepted_tokens = rollbacks = k_step = 0
            spec_step = (self.drafter is not None
                         and any(self._temp(s) <= 0.0 for s in plan.decode))
            if plan.decode and spec_step:
                k_step = self._controller.k
                (decode_tokens, draft_tokens, accepted_tokens,
                 rollbacks) = self._speculative_decode(plan.decode, k_step,
                                                       events)
                # charge the k the controller actually used, then refund
                # the verified-and-rolled-back drafts: the net equals
                # the tokens that advanced a stream, so speculation plus
                # prefix-cache hits can no longer double-charge the
                # budget relative to the work that really ran
                decode_charge = self.scheduler.decode_cost(
                    len(plan.decode), k_step,
                    rejected=draft_tokens - accepted_tokens)
                budget -= decode_charge
            elif plan.decode:
                dec_span = tracer.span(
                    "decode_batch",
                    compile_key=("decode", self.pool.n_slots),
                    slots=len(plan.decode), **self._pool_state)
                if tracer.enabled:
                    # batched phases list every member request so the
                    # per-request timeline can claim them; guarded so
                    # the disabled path builds no list
                    dec_span.set("requests",
                                 [s.request_id for s in plan.decode])
                with dec_span:
                    # dispatch returns once the step is enqueued; the
                    # device runs it while the read-back below waits
                    with tracer.span("decode_dispatch"):
                        tokens = np.zeros((self.pool.n_slots, 1), np.int32)
                        mask = np.zeros((self.pool.n_slots,), bool)
                        for s in plan.decode:
                            tokens[s.slot, 0] = s.next_token
                            mask[s.slot] = True
                        logits, self.pool.cache = self._decode_fn(
                            jnp.asarray(tokens), jnp.asarray(mask),
                            self.pool.cache)
                        last = logits[:, -1]
                    # one batched argmax + one device sync covers every
                    # greedy row; skipped when the whole batch is sampled
                    greedy = None
                    if any(self._temp(s) <= 0.0 for s in plan.decode):
                        with tracer.span("decode_readback"):
                            greedy = np.asarray(jnp.argmax(last, axis=-1))
                    with tracer.span("emit"):
                        for s in plan.decode:
                            if self._temp(s) <= 0.0:
                                events.append(
                                    self._emit(s, int(greedy[s.slot])))
                            else:
                                events.append(self._emit(
                                    s, self._sample(s, last[s.slot])))
                decode_tokens = len(plan.decode)
                decode_charge = self.scheduler.decode_cost(len(plan.decode))
                budget -= decode_charge

            # 3. chunked prefill under the remaining budget
            prefill_tokens = 0
            first = True
            if self._batch_prefill:
                # prefix-hit sequences resume on the private path
                # (zero-copy seed, see prefill.start_prefill) and run
                # first: a resumed suffix is the cheapest way to turn
                # budget into a first token
                resident = [s for s in plan.prefill if s.pool_resident]
                for s in plan.prefill:
                    if s.pool_resident:
                        continue
                    while not s.prefill_done:
                        c = s.next_chunk
                        if not first and c > budget:
                            break
                        with tracer.span(
                                "prefill_chunk",
                                compile_key=("prefill", c),
                                request=s.request_id, chunk=c,
                                **self._slot_state):
                            prefill_tokens += PF.advance_prefill(
                                s, self._prefill_fn, self.prefix_cache)
                        budget -= c
                        first = False
                    if not s.prefill_done:
                        break
                # then rounds of same-chunk-length groups over the
                # pool-resident (cold) sequences, each ONE pooled
                # dispatch over the full slot batch (fixed shapes)
                while True:
                    group = self.scheduler.group_prefill(
                        resident, budget, first_exempt=first)
                    if not group:
                        break
                    c = group[0].next_chunk
                    grp_span = tracer.span(
                        "prefill_batch",
                        compile_key=(("prefill_pool", c)
                                     if len(group) > 1
                                     else ("prefill_slot", c)),
                        slots=len(group), chunk=c,
                        **(self._pool_state if len(group) > 1
                           else self._slot_state))
                    if tracer.enabled:
                        grp_span.set("requests",
                                     [s.request_id for s in group])
                    with grp_span:
                        if tracer.enabled:
                            # the group span fans into per-slot markers
                            # so each request's timeline shows *its*
                            # slot inside the pooled dispatch
                            for s in group:
                                tracer.instant("prefill_slot",
                                               request=s.request_id,
                                               slot=s.slot, chunk=c)
                        prefill_tokens += PF.advance_prefill_batch(
                            group, self.pool, self._pool_prefill_fn,
                            self.prefix_cache, self._slot_prefill_fn)
                    budget -= len(group) * c
                    first = False
                for s in plan.prefill:
                    if s.prefill_done:
                        self._begin_decode(s, events)
            else:
                for s in plan.prefill:
                    while not s.prefill_done:
                        c = s.next_chunk
                        if not first and c > budget:
                            break
                        with tracer.span(
                                "prefill_chunk",
                                compile_key=("prefill", c),
                                request=s.request_id, chunk=c,
                                **self._slot_state):
                            prefill_tokens += PF.advance_prefill(
                                s, self._prefill_fn, self.prefix_cache)
                        budget -= c
                        first = False
                    if not s.prefill_done:
                        break
                    self._begin_decode(s, events)

            with tracer.span("record_step"):
                m = StepMetrics(
                    step=self._step_idx, wall_s=time.perf_counter() - t0,
                    decode_tokens=decode_tokens,
                    prefill_tokens=prefill_tokens,
                    queue_depth=self.queue.depth,
                    occupancy=self.pool.occupancy,
                    active_decoding=len(plan.decode),
                    draft_tokens=draft_tokens,
                    accepted_tokens=accepted_tokens,
                    rollbacks=rollbacks, speculate_k=k_step,
                    cached_prefix_tokens=cached_tokens,
                    scheduled_tokens=decode_charge + prefill_tokens)
                self.stats.record_step(m)
                if self.prefix_cache is not None:
                    self.stats.prefix_cache = self.prefix_cache.stats()
        self._step_idx += 1
        return m, events

    def run(self) -> Iterator[TokenEvent]:
        """Drive steps until idle, streaming TokenEvents."""
        while not self.idle:
            _, events = self.step()
            yield from events

    def generate(self, requests: list[Request]) -> dict[str, list[int]]:
        """Convenience batch API: submit everything, run to completion,
        return request_id -> generated tokens."""
        for r in requests:
            self.submit(r)
        for _ in self.run():
            pass
        return {r.request_id: self.results[r.request_id].out_tokens
                for r in requests}

    # ------------------------------------------------------------------
    # Speculative decode (draft -> one batched verify -> accept/rollback)
    # ------------------------------------------------------------------

    def _speculative_decode(self, decoding: list[Sequence], k: int,
                            events: list[TokenEvent]
                            ) -> tuple[int, int, int, int]:
        """One draft+verify pass over every decoding slot.

        Returns (emitted, drafted, accepted, rollbacks). Greedy
        sequences accept the longest draft prefix whose argmax chain
        matches (bit-identical to one-token-per-step greedy decoding);
        sampled sequences draw from the verify block's first position —
        exactly the next-token distribution — and always roll back the
        drafted tail.

        Rollback discipline: jax arrays are immutable, so holding the
        pre-verify pool pytree is a zero-copy bit-exact snapshot of
        every slot. A rejected slot is then fixed in ONE fused call
        (``models.model.verify_rollback``): restore from the snapshot +
        re-absorb the accepted prefix, ≤ k distinct shapes total. An
        accepted-everything step costs exactly the dispatches of a
        plain decode step (verify + argmax) while emitting k+1 tokens
        per slot.
        """
        from repro.spec.verify import accepted_prefix

        rids = ([s.request_id for s in decoding] if tracer.enabled
                else None)
        draft_span = tracer.span("draft", compile_key=("draft", k), k=k,
                                 slots=len(decoding))
        if rids is not None:
            draft_span.set("requests", rids)
        with draft_span:
            drafts = self.drafter.draft(decoding, k)
        tokens = np.zeros((self.pool.n_slots, k + 1), np.int32)
        mask = np.zeros((self.pool.n_slots,), bool)
        for s in decoding:
            tokens[s.slot, 0] = s.next_token
            tokens[s.slot, 1:] = drafts[s.slot]
            mask[s.slot] = True
        snap = self.pool.cache          # O(1): arrays are immutable
        verify_span = tracer.span("verify", compile_key=("verify", k + 1),
                                  k=k, slots=len(decoding))
        if rids is not None:
            verify_span.set("requests", rids)
        with verify_span:
            logits, self.pool.cache = self._verify_fn(
                jnp.asarray(tokens), jnp.asarray(mask), self.pool.cache)
            greedy = np.asarray(jnp.argmax(logits, axis=-1))  # (slots, k+1)

        # every decoding slot's k drafts are scored (and budgeted),
        # sampled ones included — only acceptance is greedy-specific
        emitted_n, accepted_n, rollbacks = 0, 0, 0
        drafted_n = k * len(decoding)
        for s in decoding:
            slot = s.slot
            if self._temp(s) <= 0.0:
                a, emitted = accepted_prefix(drafts[slot], greedy[slot])
                accepted_n += a
                self._controller.update(a, k)   # greedy observations only:
            else:                               # sampled seqs always reject
                a, emitted = 0, [self._sample(s, logits[slot, 0])]
            for t in emitted:
                ev = self._emit(s, t)
                events.append(ev)
                emitted_n += 1
                if ev.finished:
                    break
            if s.status is SequenceStatus.FINISHED:
                continue        # slot already released and zero-reset
            if a < k:
                # state absorbed all k+1 fed tokens but only a+1 are
                # real context: restore and re-absorb the accepted
                # prefix (the bonus token is the *next* feed, never
                # absorbed here — same as the non-speculative step)
                with tracer.span("rollback",
                                 compile_key=("rollback", a + 1),
                                 request=s.request_id, accepted=a):
                    self.pool.cache = self._rollback_fn(
                        self.pool.cache, snap, slot,
                        jnp.asarray(tokens[slot, :a + 1], jnp.int32)[None])
                rollbacks += 1
            self.drafter.commit(s, a, tokens[slot].tolist())
        return emitted_n, drafted_n, accepted_n, rollbacks

    # ------------------------------------------------------------------
    # Sampling / lifecycle internals
    # ------------------------------------------------------------------

    def _begin_decode(self, s: Sequence, events: list[TokenEvent]) -> None:
        """Prompt fully absorbed: hand the state to the decode path and
        sample the first token from the last chunk's logits. Pool-
        resident sequences already live in their slot; private ones
        scatter in here."""
        if not s.pool_resident:
            self.pool.scatter(s.cache, s.slot)
            s.cache = None
        s.status = SequenceStatus.DECODING
        if self.drafter is not None:
            self.drafter.on_ready(s)
        s.t_first_token = time.perf_counter()
        tracer.instant("first_token", request=s.request_id)
        self.stats.record_first_token(s.ttft)
        events.append(self._emit(s, self._sample(s, s.last_logits[0, -1]),
                                 first=True))
        s.last_logits = None

    def _temp(self, seq: Sequence) -> float:
        """Effective temperature: per-request override, engine default."""
        t = seq.request.temperature
        return self.econf.temperature if t is None else t

    def _sample(self, seq: Sequence, logits_row) -> int:
        temp = self._temp(seq)
        if temp <= 0.0:
            return int(jnp.argmax(logits_row))
        lg = jnp.asarray(logits_row, jnp.float32) / temp
        lg = _filter_logits(lg, seq.request.top_k, seq.request.top_p)
        # per-(request, index) keys: sampling is independent of how the
        # request was batched, so staggered arrivals stay reproducible;
        # crc32, not hash() — str hashing is salted per interpreter
        rid = zlib.crc32(seq.request_id.encode()) & 0x7FFFFFFF
        key = jax.random.fold_in(jax.random.fold_in(self._rng, rid),
                                 len(seq.out_tokens))
        return int(jax.random.categorical(key, lg))

    def _emit(self, seq: Sequence, token: int, *, first: bool = False
              ) -> TokenEvent:
        # per-request inter-token latency: wall gap between consecutive
        # emitted tokens (tokens a verify step releases together are
        # honest ~0 gaps — that burstiness is what the ITL percentiles
        # exist to show)
        now = time.perf_counter()
        if seq.t_last_token is not None:
            itl = now - seq.t_last_token
            seq.itls.append(itl)
            self.stats.record_itl(itl)
        seq.t_last_token = now
        seq.out_tokens.append(token)
        done = (len(seq.out_tokens) >= seq.request.max_new_tokens
                or token == seq.request.eos_id)
        if done:
            self._finish(seq)
        return TokenEvent(request_id=seq.request_id, token=token,
                          index=len(seq.out_tokens) - 1, first=first,
                          finished=done)

    def _finish(self, seq: Sequence) -> None:
        seq.status = SequenceStatus.FINISHED
        seq.t_finish = time.perf_counter()
        tracer.instant("finish", request=seq.request_id,
                       tokens=len(seq.out_tokens))
        self._slots[seq.slot] = None
        if self.drafter is not None:
            self.drafter.release(seq.slot)
        self.pool.release(seq.slot)
        seq.slot = None
        del self.sequences[seq.request_id]   # live bookkeeping only
        self.results[seq.request_id] = seq
        self.stats.record_finish()
