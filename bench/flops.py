"""Operations and bytes from shapes: the yardstick of the rooflines and
of the model FLOP utilization.

All counts are computed from a configuration file's published keys and
the cell's sizes, never read from the program.  A matrix product
``[M, K] x [K, N]`` does ``2 M K N`` operations and must move at least
its operands and its result once.
"""
from __future__ import annotations

import dataclasses


def dims(conf: dict) -> dict:
    h, kv = conf["num_attention_heads"], conf["num_key_value_heads"]
    d = conf["hidden_size"]
    return {"d": d, "h": h, "kv": kv, "hd": d // h,
            "f": conf["intermediate_size"], "v": conf["vocab_size"],
            "layers": conf["num_hidden_layers"]}


def layer_params(conf: dict) -> int:
    """Parameters of one decoder layer's matrices (norms and biases are
    elementwise work and are left out)."""
    s = dims(conf)
    attn = s["d"] * s["h"] * s["hd"] * 2 + s["d"] * s["kv"] * s["hd"] * 2
    return attn + 3 * s["d"] * s["f"]


def matmul_params(conf: dict) -> int:
    """Parameters that multiply a token in a forward pass: every layer's
    matrices and the head (the embedding lookup multiplies nothing)."""
    s = dims(conf)
    return s["layers"] * layer_params(conf) + s["d"] * s["v"]


def attention_flops(conf: dict, q_rows: int, ctx: int) -> float:
    """Forward q.k and p.v of ``q_rows`` queries against ``ctx`` keys each,
    over all layers (4 * hd * H per query-key pair)."""
    s = dims(conf)
    return 4.0 * s["hd"] * s["h"] * s["layers"] * q_rows * ctx


def train_flops_per_token(conf: dict, seq: int) -> float:
    """Model FLOPs per trained token: 6 N (forward and backward of every
    matrix) plus causal attention, 3 x its forward (recomputation not
    counted).  The arithmetic of ``launch/dryrun.py``'s
    ``model_flops_estimate``, kept here so it cannot move."""
    causal_fwd = attention_flops(conf, seq, seq) / 2 / seq
    return 6.0 * matmul_params(conf) + 3.0 * causal_fwd


def decode_flops(conf: dict, ctx: int) -> float:
    """Model FLOPs of one decoded token attending ``ctx`` cached keys."""
    return 2.0 * matmul_params(conf) + attention_flops(conf, 1, ctx)


def prefill_flops(conf: dict, prompt: int) -> float:
    """Model FLOPs of one causal prefill of ``prompt`` tokens."""
    pairs = prompt * (prompt + 1) / 2
    return 2.0 * matmul_params(conf) * prompt + attention_flops(conf, 1, 1) * pairs


@dataclasses.dataclass(frozen=True)
class MatMul:
    """``count`` executions of ``[m, k] x [k, n]`` with operand and result
    element sizes ``ba``, ``bb``, ``bo`` bytes."""
    name: str
    m: int
    k: int
    n: int
    ba: float
    bb: float
    bo: float
    count: int = 1

    @property
    def flops(self) -> float:
        return 2.0 * self.m * self.k * self.n * self.count

    @property
    def bytes(self) -> float:
        return (self.m * self.k * self.ba + self.k * self.n * self.bb
                + self.m * self.n * self.bo) * self.count

    def min_seconds(self, peak_flops: float, hbm_bytes_per_s: float) -> float:
        return max(self.flops / peak_flops, self.bytes / hbm_bytes_per_s)


def train_step_matmuls(conf: dict, *, batch: int, seq: int,
                       operand_bytes: float, remat: bool,
                       attn_chunk: int) -> list:
    """Every matrix product one training step executes, by the program's
    published structure: per layer the 7 projections (forward, the
    recomputed forward under ``remat`` but for ``w_down``'s, dgrad and wgrad) with
    ``operand_bytes``-wide operands and bf16 results; the bf16 head
    (forward, dgrad, wgrad); and the q-chunked attention products, which
    compute every query-key pair of a chunk (no causal skip): q.k and p.v
    forward (and recomputed), four products backward."""
    s = dims(conf)
    t = batch * seq
    L = s["layers"]
    fw = 2 if remat else 1
    ob = operand_bytes
    projs = [("wq", s["d"], s["h"] * s["hd"]), ("wk", s["d"], s["kv"] * s["hd"]),
             ("wv", s["d"], s["kv"] * s["hd"]), ("wo", s["h"] * s["hd"], s["d"]),
             ("w_gate", s["d"], s["f"]), ("w_up", s["d"], s["f"]),
             ("w_down", s["f"], s["d"])]
    out = []
    for name, k, n in projs:
        # the recomputed w_down output feeds nothing in the backward pass,
        # and the compiler drops it
        runs = L if name == "w_down" else fw * L
        out.append(MatMul(f"{name}.fwd", t, k, n, ob, ob, 2, runs))
        out.append(MatMul(f"{name}.dgrad", t, n, k, ob, ob, 2, L))
        out.append(MatMul(f"{name}.wgrad", k, t, n, ob, ob, 2, L))
    out.append(MatMul("head.fwd", t, s["d"], s["v"], 2, 2, 2))
    out.append(MatMul("head.dgrad", t, s["v"], s["d"], 2, 2, 2))
    out.append(MatMul("head.wgrad", s["d"], t, s["v"], 2, 2, 2))
    # attention, per (sequence, head, query chunk): C x hd x T products.
    # The scores and probabilities are the algorithm's intermediates and
    # need not touch HBM (a fused kernel keeps them on chip), so their
    # bytes count 0; q, k, v, the output and their gradients count bf16.
    c = seq if seq <= attn_chunk or seq % attn_chunk else attn_chunk
    n_chunks = batch * s["h"] * (seq // c)
    hd, n = s["hd"], L * n_chunks
    out.append(MatMul("attn.qk.fwd", c, hd, seq, 2, 2, 0, fw * n))
    out.append(MatMul("attn.pv.fwd", c, seq, hd, 0, 2, 2, fw * n))
    out.append(MatMul("attn.dp", c, hd, seq, 2, 2, 0, n))
    out.append(MatMul("attn.dv", seq, c, hd, 0, 2, 2, n))
    out.append(MatMul("attn.dq", c, seq, hd, 0, 2, 2, n))
    out.append(MatMul("attn.dk", seq, c, hd, 0, 2, 2, n))
    return out


def decode_attention_call(conf: dict, *, q_rows: int, ctx: int,
                          kv_bytes_per_elem: float) -> tuple:
    """(FLOPs, least bytes) of one sequence's paged decode attention over
    all layers: ``q_rows`` new rows against ``ctx`` live cache rows (the
    block prefill is causal: half the pairs).  Bytes are the live KV
    pages at the cache format, plus bf16 q and out."""
    s = dims(conf)
    if q_rows == 1:
        pairs = ctx
    else:
        pairs = q_rows * (q_rows + 1) / 2 + q_rows * (ctx - q_rows)
    fl = attention_flops(conf, 1, 1) * pairs
    kv = 2 * ctx * s["kv"] * s["hd"] * kv_bytes_per_elem * s["layers"]
    qo = 2 * q_rows * s["h"] * s["hd"] * 2 * s["layers"]
    return fl, kv + qo
