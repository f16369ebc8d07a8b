"""Roofline share of the efficient-form attention backward in training, %.

The least time that the attention backward of every step in the traced
window needs (``bench/flops.py:efficient_attention_call(backward=True)``
over batch x heads x layers rows: FLOPs at the bf16 peak or bytes at the
HBM peak, whichever is longer) over the summed device time of the
kernels named ``taylor_eff_bwd_*`` (``kernels/taylor_grad.py``) inside
those steps. Outside a rematerialised block a kernel's name carries its
transformations too (``%transpose_jvp_taylor_eff_bwd_amod_da__.3``).
The backward's recomputation of the forward's state runs under the
forward's kernel name and is not counted, as the least time leaves
recomputation out too. A program whose kernels carry no such name
reports nothing.
"""

from bench import flops as F
from bench import trace_reduce as TR

KERNELS = r"taylor_eff_bwd_\w+\.\d+ custom-call:tpu_custom_call"
STEP = r"train_step"


def read(r):
    if r.trace is None or r.peaks is None:
        return None
    steps = TR.complete_modules(r.trace, STEP)
    kernel_s = sum(TR.op_seconds(r.trace, KERNELS, within=steps).values())
    if not steps or kernel_s <= 0:
        return None
    m, t = r.run.model, r.run.traffic
    rows = t["global_batch"] * m["n_heads"] * m["n_layers"]
    least, _ = F.least_seconds(
        F.efficient_attention_call(t["seq_len"], m["head_dim"], heads=rows,
                                   dtype_bytes=2, backward=True), r.peaks)
    return 100.0 * len(steps) * least / kernel_s
