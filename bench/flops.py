"""Operations and bytes that the mathematics of a cell needs, from shapes.

Every count here is the least the computation requires at the given
shapes, so that no implementation can read above its roofline and the
count does not change with what implements it:

- a dense layer costs 2 FLOPs per multiply-add, counted once;
- TaylorShift attention is counted as the cheaper of the paper's two
  forms at the given length. The direct form (Eq. 1) costs ``4 d`` per
  (query, key) pair: the score ``q.k`` and the weighted sum of ``v``.
  The efficient form (Algorithm 1) costs ``E(d) = d (d+1) (d+3)`` per key
  to build the state and again per query to read it out, counting the
  symmetric ``k (x) k`` once as ``d (d+1) / 2`` rows: ``2 * d(d+1)/2 *
  (d+1)`` for the quadratic term and ``2 * d * (d+1)`` for the linear
  term, each over the ``d + 1`` columns of ``[1, v]``;
- the read-out to the vocabulary is counted only where a logit is
  needed: every position in training, one per prompt and one per decoded
  token in serving;
- backward costs twice the forward; recomputation is not counted;
- bytes are the inputs and outputs of a call, at their dtype's size.

Layers are counted by the configuration's ``layer_pattern`` (default
``["global"]``), layer ``i`` being of kind ``pattern[i % len(pattern)]``.
This module counts ``global`` layers (attention and a dense MLP). A layer
of any other kind is counted by ``layer_flops(m, kind, n_past, n_new)``
of the configuration's plain reference, where it defines one, and
otherwise the count raises: no kind is counted as another.

Elementwise work (norms, activations, the Taylor polynomial) is left
out: it is small against the counted terms and no bound rests on it.
"""

from __future__ import annotations


def taylor_state_flops(d: int) -> int:
    """E(d): FLOPs to fold one key into, or read one query out of, the
    efficient form's state, with the symmetric quadratic term once."""
    return d * (d + 1) * (d + 3)


def attn_flops_noncausal(n: int, d: int, heads: int, kv_heads: int) -> int:
    """One layer's attention over one sequence of ``n`` tokens, all
    ``n`` keys visible to every query."""
    direct = 4 * n * n * d * heads
    efficient = n * taylor_state_flops(d) * (heads + kv_heads)
    return min(direct, efficient)


def attn_flops_causal(n_past: int, n_new: int, d: int, heads: int,
                      kv_heads: int) -> int:
    """One layer's attention for ``n_new`` tokens that follow ``n_past``
    absorbed ones, each query seeing its own prefix."""
    lo, hi = n_past + 1, n_past + n_new
    pairs = (lo + hi) * n_new // 2
    direct = 4 * pairs * d * heads
    efficient = n_new * taylor_state_flops(d) * (heads + kv_heads)
    return min(direct, efficient)


def dense_params_per_layer(m: dict) -> int:
    """Multiply-add weights of one block: q, k, v, o and the MLP."""
    d, dh = m["d_model"], m["head_dim"]
    h, kv = m["n_heads"], m["n_kv_heads"]
    attn = d * h * dh * 2 + d * kv * dh * 2
    mlp = (3 if m["gated_mlp"] else 2) * d * m["d_ff"]
    return attn + mlp


COUNTED = ("global",)


def layer_kinds(m: dict) -> list[str]:
    """The kind of each of the model's layers, in order."""
    pattern = m.get("layer_pattern", ("global",))
    return [pattern[i % len(pattern)] for i in range(m["n_layers"])]


def lm_flops(m: dict, *, tokens: int, attn: int, logits: int,
             other=None) -> int:
    """Forward FLOPs of the whole model for ``tokens`` positions with
    ``attn`` attention FLOPs per ``global`` layer and ``logits`` read-out
    rows. ``other(kind)`` gives a layer of another kind for the same
    work; without it such a layer raises."""
    total = 2 * logits * m["d_model"] * m["vocab"]
    for kind in layer_kinds(m):
        if kind in COUNTED:
            total += 2 * tokens * dense_params_per_layer(m) + attn
        elif other is not None:
            total += other(kind)
        else:
            raise ValueError(
                f"no FLOP count for layer kind {kind!r}: the "
                "configuration's reference defines no layer_flops")
    return total


def _other(m: dict, layer_flops, n_past: int, n_new: int, times: int = 1):
    if layer_flops is None:
        return None
    return lambda kind: times * layer_flops(m, kind, n_past, n_new)


def train_step_flops(m: dict, *, batch: int, seq_len: int,
                     layer_flops=None) -> int:
    """Forward and backward of one optimizer step of a non-causal
    encoder trained with a loss at every position."""
    attn = batch * attn_flops_noncausal(seq_len, m["head_dim"], m["n_heads"],
                                        m["n_kv_heads"])
    tokens = batch * seq_len
    return 3 * lm_flops(m, tokens=tokens, attn=attn, logits=tokens,
                        other=_other(m, layer_flops, 0, seq_len, batch))


def prefill_flops(m: dict, n_past: int, n_new: int, *,
                  last_chunk: bool, layer_flops=None) -> int:
    """One prompt chunk of one request; a logit only after the last."""
    attn = attn_flops_causal(n_past, n_new, m["head_dim"], m["n_heads"],
                             m["n_kv_heads"])
    return lm_flops(m, tokens=n_new, attn=attn, logits=int(last_chunk),
                    other=_other(m, layer_flops, n_past, n_new))


def decode_flops(m: dict, n_past: int, layer_flops=None) -> int:
    """One decoded token of one request after ``n_past`` absorbed ones."""
    attn = attn_flops_causal(n_past, 1, m["head_dim"], m["n_heads"],
                             m["n_kv_heads"])
    return lm_flops(m, tokens=1, attn=attn, logits=1,
                    other=_other(m, layer_flops, n_past, 1))


def efficient_attention_call(n: int, d: int, *, heads: int,
                             dtype_bytes: int, backward: bool) -> dict:
    """Work of one non-causal efficient-form attention call over
    ``heads`` (batch x head) rows of ``n`` tokens: forward reads q, k, v
    and writes y; backward reads q, k, v, dy and writes dq, dk, dv."""
    fwd = 2 * n * taylor_state_flops(d) * heads
    if backward:
        return {"flops": 2 * fwd, "bytes": 7 * n * d * heads * dtype_bytes}
    return {"flops": fwd, "bytes": 4 * n * d * heads * dtype_bytes}


def least_seconds(work: dict, peaks: dict) -> tuple[float, str]:
    """Roofline floor of ``work`` on a chip: the larger of its FLOPs at
    peak bf16 rate and its bytes at peak HBM bandwidth, and which."""
    t_f = work["flops"] / peaks["bf16_flops_per_s"]
    t_b = work["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
