"""Roofline share of the training step's matrix products: the least time
they need (each product's max of FLOPs over the bf16 peak and bytes over
HBM bandwidth, from shapes: ``flops.train_step_matmuls``), times the steps
in the traced window, over the device time of every event that
implements a matrix product: the Pallas GEMM kernels, matched by name,
and XLA's dot and convolution ops and the fusions that hold one, matched
by instruction name in the compiled step (``ctx["matmul_ops"]``)."""
import flops

UNIT = "%"
#: Pallas GEMM kernels, by the function name the trace gives them
KERNELS = ("exsdotp_gemm_pallas", "blockscale_gemm_pallas",
           "mx_gemm_pallas", "mx_gemm_packed_pallas")


def compute(ctx):
    red, conf, wl = ctx["trace"], ctx["conf"], ctx["workload"]
    xla = ctx["matmul_ops"]
    busy = red.op_seconds(lambda op: op.base in KERNELS or op.name in xla)
    if busy <= 0.0 or not ctx["steps"]:
        return None
    pk = ctx["peaks"]
    t = wl["traffic"]
    least = sum(m.min_seconds(pk["bf16_flops"], pk["hbm_bytes_per_s"])
                for m in flops.train_step_matmuls(
                    conf, batch=t["batch"], seq=t["seq"], operand_bytes=1,
                    remat=True, attn_chunk=conf["program"]["attn_q_chunk"]))
    return 100.0 * least * ctx["steps"] / busy
