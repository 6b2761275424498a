#!/usr/bin/env python3
"""Record the small chip trace that ``test_scoped.py`` reads.

    python3 bench/tests/record_scoped_trace.py <dir>

Two jitted functions named like the batcher's, each product under a scope
of the program's vocabulary: ``prefill_step`` (``attn/qkv`` and ``mlp``
products, compiled at 256 and at 512 rows) and ``decode_step`` (an
``mlp/gemm`` product, then an add outside any scope).  Inside a
``bench.window`` span, three rounds of a ``bench.batcher_step`` span
holding ``repro.serve.prefill`` (a prefill, 512 rows in the second
round), ``repro.serve.decode`` (the decode's dispatch),
``repro.serve.sample`` (its result to the host) and
``repro.serve.retire`` (the host sleeps 5 ms, the device idles).
Writes the ``.xplane.pb`` under ``<dir>`` and ``<dir>/scoped.hlo.json``,
the compiled text of the three modules; both are copied to
``bench/tests/data/`` as ``scoped.xplane.pb`` and ``scoped.hlo.json``.
"""
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def prefill_step(x, w):
    with jax.named_scope("attn"), jax.named_scope("qkv"):
        y = x @ w
    with jax.named_scope("mlp"):
        return jnp.tanh(y) @ w


def decode_step(x, w):
    with jax.named_scope("mlp"), jax.named_scope("gemm"):
        y = x @ w
    return y + 1.0


def main(out: str) -> None:
    assert jax.default_backend() == "tpu", jax.default_backend()
    pre, dec = jax.jit(prefill_step), jax.jit(decode_step)
    w = jnp.ones((1024, 1024), jnp.bfloat16)
    xs = {n: jnp.ones((n, 1024), jnp.bfloat16) for n in (256, 512)}
    xd = jnp.ones((16, 1024), jnp.bfloat16)
    texts = [pre.lower(xs[n], w).compile().as_text() for n in (256, 512)]
    texts.append(dec.lower(xd, w).compile().as_text())
    for n in (256, 512):
        pre(xs[n], w).block_until_ready()
    dec(xd, w).block_until_ready()
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation("bench.window"):
        for k in range(3):
            with jax.profiler.TraceAnnotation("bench.batcher_step"):
                n = 512 if k == 1 else 256
                with jax.profiler.TraceAnnotation("repro.serve.prefill",
                                                  uid=str(k), prompt_len=n):
                    pre(xs[n], w).block_until_ready()
                with jax.profiler.TraceAnnotation("repro.serve.decode"):
                    y = dec(xd, w)
                with jax.profiler.TraceAnnotation("repro.serve.sample"):
                    np.asarray(y)
                with jax.profiler.TraceAnnotation("repro.serve.retire"):
                    time.sleep(0.005)
    jax.profiler.stop_trace()
    # source paths relative to the checkout, as the tests read them
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))) + os.sep
    with open(os.path.join(out, "scoped.hlo.json"), "w") as f:
        json.dump([t.replace(root, "") for t in texts], f)


if __name__ == "__main__":
    main(sys.argv[1])
