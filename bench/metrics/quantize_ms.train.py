"""Device time of the quantize passes per training step, over the steps
of the traced window: the ops under any ``quant_*`` scope of the
program's GEMMs (``quant_x``, ``quant_w`` forward and recomputed,
``quant_g`` and the backward's own quantizes), and the fusions into
which XLA fused a quantize cast (``ScopedOp.quantizes``).  Such a fusion
also lays out the GEMM's operand: its whole time is charged here, none
of it to the GEMM kernel.  Needs the scope map (``ctx["scoped"]``); a
program without scopes leaves it out."""
UNIT = "ms"


def compute(ctx):
    sc = ctx.get("scoped")
    if sc is None or not ctx["steps"]:
        return None
    s = sc.seconds(lambda o: o.quantizes)
    return 1e3 * s / ctx["steps"] if s > 0 else None
