"""Roofline share of the efficient-form attention kernels in training, %.

The least time that the forward and backward attention of every step in
the traced window needs (``bench/flops.py``: FLOPs at the bf16 peak or
bytes at the HBM peak, whichever is longer) over the summed device time
of the Pallas kernels of ``kernels/taylor_efficient.py`` and
``kernels/taylor_grad.py`` inside those steps. Every ``tpu_custom_call``
of the train step counts: in this cell those are only the efficient-form
attention kernels, named ``taylor_eff_fwd_*`` and ``taylor_eff_bwd_*``
in the trace (``breakdown.device_ops`` shows them), the same set a match
on those names would select.
"""

from bench import flops as F
from bench import trace_reduce as TR

KERNELS = r"custom-call:tpu_custom_call"
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
    least = sum(F.least_seconds(
        F.efficient_attention_call(t["seq_len"], m["head_dim"], heads=rows,
                                   dtype_bytes=2, backward=bwd),
        r.peaks)[0] for bwd in (False, True))
    return 100.0 * len(steps) * least / kernel_s
