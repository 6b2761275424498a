"""Model FLOP utilization of training: model FLOPs per token (6 N plus
causal attention, recomputation not counted; ``flops.train_flops_per_token``)
times the tokens of the steps in the traced window, over window x chips x
the bf16 peak."""
import flops

UNIT = "%"


def compute(ctx):
    red, seq = ctx["trace"], ctx["workload"]["traffic"]["seq"]
    if not ctx["tokens"]:
        return None
    work = flops.train_flops_per_token(ctx["conf"], seq) * ctx["tokens"]
    return 100.0 * work / (red.window_s * ctx["chips"]
                           * ctx["peaks"]["bf16_flops"])
