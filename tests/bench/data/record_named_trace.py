"""Record the small chip trace that ``test_bench_named_kernels.py`` reads.

    python3 tests/bench/data/record_named_trace.py OUT.xplane.pb

from the root of a checkout, on a machine with a TPU. Three steps of a
jitted ``train_step``: the efficient-form attention forward and backward
(``kernels/taylor_grad.py:taylor_efficient_attention_vjp``, its Pallas
kernels under their names), rematerialised as in the model's training,
over 4 rows of 2048 tokens at head dim 64,
each step inside a ``bench.train_step`` annotation, all inside
``bench.traced_window``.
"""

import glob
import shutil
import sys
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parents[3] / "src"))

ROWS, N, D = 4, 2048, 64
STEPS = 3


def inputs():
    """q and k l2-normalised and scaled by d ** 0.25, as the kernels take
    them; v as it comes."""
    q, k, v = jax.random.normal(jax.random.PRNGKey(0), (3, ROWS, N, D))
    scale = D ** 0.25
    q = scale * q / jnp.linalg.norm(q, axis=-1, keepdims=True)
    k = scale * k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    return q, k, v


def main(out: str) -> None:
    from repro.kernels.taylor_grad import taylor_efficient_attention_vjp

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU")

    # rematerialised, as the model's layers are in training
    attn = jax.checkpoint(taylor_efficient_attention_vjp)

    def loss(q, k, v):
        return jnp.sum(attn(q, k, v) ** 2)

    def train_step(q, k, v):
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    step = jax.jit(train_step)
    q, k, v = inputs()
    jax.block_until_ready(step(q, k, v))
    log = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.traced_window"):
        for i in range(STEPS):
            with jax.profiler.TraceAnnotation("bench.train_step", step=i):
                jax.block_until_ready(step(q, k, v))
    jax.profiler.stop_trace()
    src = glob.glob(f"{log}/**/*.xplane.pb", recursive=True)[0]
    shutil.copy(src, out)
    shutil.rmtree(log)


if __name__ == "__main__":
    main(sys.argv[1])
