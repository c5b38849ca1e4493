"""Records the small chip trace that ``test_trace_reduce.py`` reads:
three calls of a small jitted program between the harness's host spans.

    python3 chipbench/tests/record_trace.py <out dir>   # on one TPU chip
"""

import sys
import time

import jax
import jax.numpy as jnp


def main(out: str) -> None:
    f = jax.jit(lambda x: jnp.tanh(x @ x) * 0.5)
    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("window.episode"):
                y = f(x)
            with jax.profiler.TraceAnnotation("host.summary"):
                jax.device_get(y)
                time.sleep(0.01)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
