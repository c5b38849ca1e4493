"""Records the small chip trace that ``test_stages.py`` reads: three calls
of a tiny program whose ops carry the twin's stage names, each between
the host spans a fleet segment has.

    python3 chipbench/tests/record_stages.py <out dir>   # on one TPU chip
"""

import sys
import time

import jax
import jax.numpy as jnp


def program(x):
    with jax.named_scope("macro.event"):
        x = jnp.tanh(x @ x)

    def body(c):
        i, y = c
        with jax.named_scope("tick.tail"):
            y = jnp.sin(y @ y) * 0.5
        return i + 1, y

    with jax.named_scope("macro.fast"):
        _, x = jax.lax.while_loop(lambda c: c[0] < 4, body, (0, x))
    return x * 2.0


def main(out: str) -> None:
    f = jax.jit(program)
    x = jnp.ones((256, 256), jnp.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("window.segment"):
                with jax.profiler.TraceAnnotation("host.fleet.prepare"):
                    time.sleep(0.005)
                with jax.profiler.TraceAnnotation("host.fleet.call"):
                    y = f(x)
            with jax.profiler.TraceAnnotation("host.summary"):
                jax.device_get(y)
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
