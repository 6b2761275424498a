"""Device time of the attention core per training step: the ops under the
program's ``attn/core`` scope (scores, softmax, values and the stacking of
their chunks; forward, backward and recomputation) in the traced window,
over the steps in it.  Needs the scope map (``ctx["scoped"]``,
``bench/scoped.py``); a program without scopes leaves it out."""
UNIT = "ms"


def compute(ctx):
    sc = ctx.get("scoped")
    if sc is None or not ctx["steps"]:
        return None
    s = sc.seconds(lambda o: o.scope is not None and "attn" in o.scope
                   and "core" in o.scope)
    return 1e3 * s / ctx["steps"] if s > 0 else None
