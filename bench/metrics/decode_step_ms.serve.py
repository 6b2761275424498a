"""Median device duration of the batcher's lockstep decode: the runs of
the ``jit_decode_step`` module (``XLA Modules`` line) that lie wholly in
the traced window.  A program that jits prefill and decode as one
function has no such module apart and leaves it out."""
import statistics

UNIT = "ms"
MODULE = "jit_decode_step"


def compute(ctx):
    sc = ctx.get("scoped")
    if sc is None or not any(o.module == "jit_prefill_step" for o in sc.ops):
        return None
    runs = sc.run_seconds(MODULE)
    return 1e3 * statistics.median(runs) if runs else None
