"""Record the small chip trace that ``test_bench_trace_reduce.py`` reads.

    python3 tests/bench/data/record_sample_trace.py OUT.xplane.pb

on a machine with a TPU. Five 2048x2048 bf16 matrix products, each
inside a ``bench.train_step`` annotation and followed by a 20 ms sleep
inside ``bench.wait_arrival``, all inside ``bench.traced_window``.
"""

import glob
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

STEPS = 5
SLEEP_S = 0.02


def main(out: str) -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("needs a TPU")
    f = jax.jit(lambda a, b: a @ b)
    a = jnp.ones((2048, 2048), jnp.bfloat16)
    f(a, a).block_until_ready()
    log = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.traced_window"):
        for i in range(STEPS):
            with jax.profiler.TraceAnnotation("bench.train_step", step=i):
                f(a, a).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                time.sleep(SLEEP_S)
    jax.profiler.stop_trace()
    src = glob.glob(f"{log}/**/*.xplane.pb", recursive=True)[0]
    shutil.copy(src, out)
    shutil.rmtree(log)


if __name__ == "__main__":
    main(sys.argv[1])
