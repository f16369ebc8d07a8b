"""Model / run configuration.

One ``ModelConfig`` dataclass covers every assigned architecture family
(dense decoder, MoE, hybrid SSM, xLSTM, encoder-decoder, VLM). Each
``src/repro/configs/<arch>.py`` exports ``CONFIG`` built from this class,
plus the registry maps ``--arch <id>`` to it.

``layer_pattern`` encodes periodic heterogeneity (gemma's local:global
alternation, zamba's shared-attention interleave, xlstm's sLSTM/mLSTM
alternation) as a repeating unit; the model scans over full periods and
unrolls the remainder, so compile time stays O(pattern), not O(layers).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field, replace
from typing import Sequence

import jax.numpy as jnp


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 0
    top_k: int = 1
    capacity_factor: float = 1.25
    n_shared_experts: int = 0     # llama4-style always-on shared expert
    every: int = 1                # 1 = every layer, 2 = alternate dense/moe
    aux_loss_weight: float = 0.01


@dataclass(frozen=True)
class SSMConfig:
    state: int = 64               # N (ssm state size)
    head_dim: int = 64            # P
    expansion: int = 2            # d_inner = expansion * d_model
    conv_width: int = 4
    n_groups: int = 1             # B/C groups (GVA-style)
    chunk: int = 64
    conv_bias: bool = False       # bias on the causal conv (mamba_conv_bias)
    mlp: bool = False             # a gated-MLP sub-layer after each `mamba`
    #   mixer (granite-4.0-h); zamba2's blocks have none


@dataclass(frozen=True)
class TaylorConfig:
    """Paper knobs. Backend routing (which implementation serves which
    attention site under which mesh) is resolved from these by
    ``models/backend.py:select_backend`` — the single dispatch layer."""
    enabled: bool = True
    mode: str = "auto"            # auto | direct | efficient
    optimize_for: str = "speed"   # crossover flavor: speed (N0) | memory (N1)
    chunk: int = 128              # causal chunk size
    tau_init: float = 1.0         # learnable per-head temperature init
    normalize_inputs: bool = True
    output_scale: bool = True
    use_kernel: bool = False      # route through the Pallas kernels
    scan: str = "auto"            # causal chunk-scan core: auto | sequential
    #   | parallel — auto streams one state (lax.scan) on a single seq
    #   shard and switches to the associative form under a `seq` mesh axis


@dataclass(frozen=True)
class SpecConfig:
    """Speculative-generation knobs (src/repro/spec/, docs/serving.md).

    The draft-length cap itself lives on ``EngineConfig.speculate_k``
    (0 disables speculation); this groups the drafter-side choices so
    the engine config stays one flat dataclass.
    """
    drafter: str = "ngram"        # ngram (prompt-lookup) | self (shallow)
    draft_layers: int = 1         # self-drafter: reuse the first j blocks
    adaptive: bool = True         # acceptance-rate-adaptive draft length
    ngram_max: int = 3            # longest history suffix matched
    ngram_min: int = 1            # shortest suffix before giving up
    ewma: float = 0.5             # acceptance-rate EWMA weight on new obs
    grow_above: float = 0.8       # raise draft length above this rate
    shrink_below: float = 0.4     # lower draft length below this rate


@dataclass(frozen=True)
class PrefixCacheConfig:
    """Shared-prefix state-cache knobs (serve/prefix_cache.py,
    docs/serving.md).

    Mirrors the ``SpecConfig`` split: the on/off knob and byte budget
    live on ``EngineConfig.prefix_cache_mb`` (0 disables the cache);
    this groups the trie-side choices. ``chunk_tokens`` is the trie key
    granularity — 0 follows ``EngineConfig.prefill_chunk``, and the
    engine *rejects* any other value (a finer grid would let
    power-of-two tail chunks form boundaries no cold prefill
    reproduces, breaking the bit-identity contract); it exists so
    offline tools can build a ``PrefixCache`` without an engine.
    ``max_entries`` bounds the entry count independently of bytes
    (0 = byte budget only) — Taylor entries are so small a pure byte
    budget can let the trie grow very wide.
    """
    chunk_tokens: int = 0
    max_entries: int = 0


@dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "decoder"       # decoder | encdec | hybrid | xlstm | vlm | audio
    n_layers: int = 4
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int | None = None
    head_dim: int | None = None   # default d_model // n_heads
    d_ff: int = 1024
    vocab: int = 32000
    act: str = "silu"
    gated_mlp: bool = True
    norm: str = "rms"             # rms | ln
    rms_norm_eps: float = 1e-6    # eps of every RMSNorm, the gated one too
    post_norm: bool = False       # gemma2-style post-block norms
    qk_norm: bool = False
    # --- attention ---------------------------------------------------------
    causal: bool = True           # False = encoder-style (paper's setting)
    attn_backend: str = "taylor"  # taylor | softmax
    taylor: TaylorConfig = field(default_factory=TaylorConfig)
    layer_pattern: Sequence[str] = ("global",)
    #   entries: global | local | mamba | shared_attn | slstm | mlstm | moe…
    #   ("moe" is orthogonal; use MoEConfig.every)
    window: int = 1024            # local-attention window
    softcap_attn: float = 0.0     # gemma2 attn logit softcap (softmax path)
    softcap_final: float = 0.0    # gemma2 final logit softcap
    rope_theta: float = 10000.0
    pos_embed: str = "rope"       # rope | learned | none
    max_seq_len: int = 8192       # for learned positions only
    tie_embeddings: bool = True
    # --- muP multipliers (granite) -------------------------------------------
    embedding_multiplier: float | None = None  # None = sqrt(d_model)
    residual_multiplier: float = 1.0   # scales every sub-layer's output
    logits_divisor: float = 1.0        # logits = readout / logits_divisor
    # --- MoE / SSM ----------------------------------------------------------
    moe: MoEConfig = field(default_factory=MoEConfig)
    ssm: SSMConfig = field(default_factory=SSMConfig)
    shared_attn_every: int = 6    # zamba2: shared attn block period
    # --- encoder-decoder (whisper) ------------------------------------------
    n_encoder_layers: int = 0
    encoder_causal: bool = False
    decoder_len: int = 448        # training decoder length for encdec
    encoder_frames: int = 1500    # fixed encoder length for decode shapes
    # --- frontends (stubs per assignment) -----------------------------------
    frontend: str = "none"        # none | audio_stub | vision_stub
    n_patches: int = 576          # vlm stub: image patch tokens per example
    # --- numerics ------------------------------------------------------------
    dtype: str = "bfloat16"
    remat: bool = True
    logits_chunk: int = 0         # 0 = auto (chunked xent for big vocab)
    loss_dtype: str = "float32"

    # --- derived -------------------------------------------------------------
    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def dim_head(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def param_dtype(self):
        return jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32

    def with_(self, **kw) -> "ModelConfig":
        return replace(self, **kw)

    # -- smoke-test sizing ----------------------------------------------------
    def reduced(self) -> "ModelConfig":
        """Same family/pattern, tiny dims — for CPU smoke tests."""
        kw = dict(
            n_layers=max(len(self.layer_pattern), 2),
            d_model=64,
            n_heads=2,
            n_kv_heads=1 if (self.n_kv_heads or 0) and self.n_kv_heads < self.n_heads else None,
            head_dim=32,
            d_ff=128 if self.d_ff else 0,
            vocab=512,
            window=16,
            max_seq_len=256,
            decoder_len=16,
            encoder_frames=32,
            n_patches=8,
            remat=False,
            dtype="float32",
        )
        if self.n_encoder_layers:
            kw["n_encoder_layers"] = 2
        if self.moe.n_experts:
            kw["moe"] = replace(self.moe, n_experts=4, capacity_factor=2.0)
        if self.family in ("hybrid", "xlstm"):
            kw["ssm"] = replace(self.ssm, state=16, head_dim=16, chunk=8)
        kw["taylor"] = replace(self.taylor, chunk=16)
        return replace(self, **kw)

    def param_count(self) -> int:
        """Analytic parameter count (for MODEL_FLOPS = 6·N·D)."""
        from repro.models.model import count_params_analytic
        return count_params_analytic(self)

    def active_param_count(self) -> int:
        from repro.models.model import count_params_analytic
        return count_params_analytic(self, active_only=True)


def asdict(cfg: ModelConfig) -> dict:
    return dataclasses.asdict(cfg)
