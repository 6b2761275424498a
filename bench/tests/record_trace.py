#!/usr/bin/env python3
"""Record the small chip trace that ``test_trace.py`` reads.

    python3 bench/tests/record_trace.py <dir>

Inside a ``bench.window`` span, four rounds of a ``bench.host_wait`` span
(the host sleeps 5 ms, the device idles) and a ``bench.compute`` span
(one 1024 x 1024 bf16 matrix product and a tanh).  The ``.xplane.pb`` it
writes under the directory is copied to ``bench/tests/data/``.
"""
import sys
import time

import jax
import jax.numpy as jnp


def main(out: str) -> None:
    assert jax.default_backend() == "tpu", jax.default_backend()
    f = jax.jit(lambda a, b: jnp.tanh(a @ b))
    a = jnp.ones((1024, 1024), jnp.bfloat16)
    f(a, a).block_until_ready()
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(4):
            with jax.profiler.TraceAnnotation("bench.host_wait"):
                time.sleep(0.005)
            with jax.profiler.TraceAnnotation("bench.compute"):
                f(a, a).block_until_ready()
    jax.profiler.stop_trace()


if __name__ == "__main__":
    main(sys.argv[1])
