"""Device time a decode step spends quantizing weights: the ops under the
``quant_w`` scope, and fusions that hold a ``quant_w`` cast, inside runs
of ``jit_decode_step`` that lie wholly in the traced window, over the
number of those runs.  Needs the scope map
(``ctx["scoped"]``); a program without scopes leaves it out."""
UNIT = "ms"
MODULE = "jit_decode_step"


def compute(ctx):
    sc = ctx.get("scoped")
    if sc is None:
        return None
    runs = {o.run for o in sc.ops if o.module == MODULE and sc.whole(o.run)}
    s = sc.seconds(lambda o: o.run in runs and o.under("quant_w"))
    return 1e3 * s / len(runs) if runs and s > 0 else None
