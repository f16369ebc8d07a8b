"""granite-4.0-h-micro [hf:ibm-granite/granite-4.0-h-micro].

40 layers in 4 periods of 10: Mamba2 everywhere but one NoPE GQA
attention layer (32 query, 8 kv heads of 64) at position 5 of each
period; hidden 2048, a SwiGLU MLP of 8192 after every mixer. Mamba2: 64
heads of 64, d_state 128, 1 group, conv 4 with a bias, chunk 256,
expand 2. muP multipliers: embedding 12, residual 0.22, logits / 8;
RMSNorm eps 1e-5; vocab 100352, tied. TaylorShift replaces softmax on
the attention layers, so the source's attention_multiplier (1/64) has no
place: q and k are l2-normalised under a learned temperature.
"""

from repro.configs.base import ModelConfig, SSMConfig

CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    head_dim=64,
    d_ff=8192,
    vocab=100352,
    act="silu",
    gated_mlp=True,
    norm="rms",
    rms_norm_eps=1e-5,
    pos_embed="none",
    tie_embeddings=True,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_divisor=8.0,
    layer_pattern=("mamba",) * 5 + ("global",) + ("mamba",) * 4,
    ssm=SSMConfig(state=128, head_dim=64, expansion=2, conv_width=4,
                  n_groups=1, chunk=256, conv_bias=True, mlp=True),
)
