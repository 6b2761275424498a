"""Model FLOP utilization of serving: model FLOPs of every prefill and
decode token the traced ticks processed (``flops.prefill_flops``,
``flops.decode_flops``; idle slots do no model work) over the device's
busy time x the bf16 peak.  Busy time, not window time: the offered load
is fixed, so the window holds idle time by design."""
import flops

UNIT = "%"


def compute(ctx):
    conf, red = ctx["conf"], ctx["trace"]
    work = 0.0
    for t in ctx["ticks"]:
        work += sum(flops.prefill_flops(conf, p) for p in t["prefills"])
        work += sum(flops.decode_flops(conf, c) for c in t["decodes"])
    if work == 0.0 or red.busy_s <= 0.0:
        return None
    return 100.0 * work / (red.busy_s * ctx["chips"]
                           * ctx["peaks"]["bf16_flops"])
