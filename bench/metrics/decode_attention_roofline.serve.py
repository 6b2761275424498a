"""Roofline share of the paged decode-attention kernel: the least time its
calls in the traced ticks need, over the device time of its events.  Per
tick, the batched decode call over the live slots needs max(summed FLOPs
over the bf16 peak, summed bytes over HBM bandwidth), the bytes being the
KV pages in use at the cache format plus q and out
(``flops.decode_attention_call``); each block prefill is one such call
of its own (the prompt's rows against themselves, causal)."""
import flops

UNIT = "%"
#: the packed decode-attention kernel, as Mosaic names it in the trace
KERNELS = ("decode_attention",)
#: bytes per cached element: payload plus one E8M0 scale per 32
KV_BYTES = {"mxfp8": 1 + 1 / 32, "mxfp6": 0.75 + 1 / 32,
            "mxfp4": 0.5 + 1 / 32}


def compute(ctx):
    red, conf = ctx["trace"], ctx["conf"]
    busy = red.op_seconds(lambda op: any(k in op.name for k in KERNELS))
    if busy <= 0.0:
        return None
    pk = ctx["peaks"]
    bpe = KV_BYTES[conf["program"]["policy"]]
    least = 0.0
    for t in ctx["ticks"]:
        calls = [flops.decode_attention_call(conf, q_rows=p, ctx=p,
                                             kv_bytes_per_elem=bpe)
                 for p in t["prefills"]]
        dec = [flops.decode_attention_call(conf, q_rows=1, ctx=c,
                                           kv_bytes_per_elem=bpe)
               for c in t["decodes"]]
        if dec:
            calls.append((sum(f for f, _ in dec), sum(b for _, b in dec)))
        least += sum(max(f / pk["bf16_flops"], b / pk["hbm_bytes_per_s"])
                     for f, b in calls)
    return 100.0 * least / busy
