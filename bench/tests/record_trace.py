"""Record the small profiler trace that ``test_bench_trace_reduce.py`` reads.

    python bench/tests/record_trace.py OUT_DIR

On the chip: two calls of a jitted ``train_step`` (a matmul chain) inside a
``bench.window`` annotation, with host time between them under a
``bench.save`` annotation, so that the trace holds device ops, two program
executions, an idle gap inside a benchmark span and one outside every span.
The newest ``.xplane.pb`` under OUT_DIR is the recording.
"""
import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> None:
    def train_step(x, w):
        for _ in range(4):
            x = jnp.tanh(x @ w)
        return x

    step = jax.jit(train_step)
    x = jnp.ones((1024, 1024), jnp.float32)
    w = jnp.eye(1024, dtype=jnp.float32) * 0.5
    step(x, w).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        step(x, w).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.save"):
            time.sleep(0.02)
        time.sleep(0.01)
        step(x, w).block_until_ready()
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
