"""``bench/flops.py`` against the compiled program: the matrix products
of one training step, counted from shapes, equal the dot FLOPs that
``launch/hlo_analysis.py`` counts in the compiled CPU step (trip-count
weighted), at a small size on the XLA path."""
import collections

import jax
import jax.numpy as jnp
import pytest

import flops
import harness
from repro.launch import hlo_analysis
from repro.models import build_model
from repro.optim.adamw import AdamWConfig
from repro.train.train_step import make_train_state, make_train_step

TINY = {"name": "tiny", "hidden_size": 64, "intermediate_size": 96,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "num_hidden_layers": 2, "vocab_size": 160, "layer_norm_eps": 1e-5,
        "rope_theta": 10000, "use_qkv_bias": True,
        "tie_word_embeddings": False, "norm_type": "layernorm",
        "program": {"family": "dense", "policy": "hfp8",
                    "quantize_head": False, "attn_q_chunk": 8}}


def dot_flops(hlo: str) -> float:
    """Trip-count-weighted dot FLOPs of a compiled module."""
    comps, entry = hlo_analysis.parse_module(hlo)
    mult = collections.defaultdict(float)
    mult[entry], order, i = 1.0, [entry], 0
    while i < len(order):
        c = order[i]
        i += 1
        for op in comps.get(c, {}).values():
            f = mult[c] * (op.trip if op.kind == "while" else 1.0)
            for callee in op.called:
                if callee not in mult:
                    order.append(callee)
                mult[callee] += f
    return sum(mult.get(c, 0.0) * hlo_analysis._dot_flops(op, t)
               for c, t in comps.items() for op in t.values()
               if op.kind == "dot")


@pytest.mark.parametrize("batch,seq", [(1, 32), (2, 16)])
def test_train_step_matmuls_match_compiled_dots(batch, seq):
    cfg = harness.model_config(TINY)
    model = build_model(cfg)
    opt = AdamWConfig(total_steps=100)
    state = jax.eval_shape(lambda: make_train_state(
        model, jax.random.key(0), opt))
    step = make_train_step(model, opt, impl="xla")
    toks = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    hlo = jax.jit(step).lower(state, toks).compile().as_text()
    counted = sum(m.flops for m in flops.train_step_matmuls(
        TINY, batch=batch, seq=seq, operand_bytes=1, remat=True,
        attn_chunk=TINY["program"]["attn_q_chunk"]))
    assert counted == pytest.approx(dot_flops(hlo), rel=1e-9)


def test_model_flops_per_token_is_the_dryrun_arithmetic():
    """6 N + causal attention x 3, N = layer matrices + head."""
    d, f, v, h, L, s = 64, 96, 160, 4, 2, 32
    n = L * (4 * d * d + 3 * d * f) + d * v
    att = 2 * 2 * 0.5 * s * s * (d // h) * h * L / s
    assert flops.train_flops_per_token(TINY, s) == pytest.approx(
        6 * n + 3 * att)


def test_decode_attention_bytes_count_live_pages():
    """mxfp8 pages: 1 B payload + 1/32 B scale per element (8448 B per
    token per layer at deepseek-7b's 32 x 128 heads)."""
    conf = dict(TINY, hidden_size=4096, num_attention_heads=32,
                num_key_value_heads=32, num_hidden_layers=1)
    fl, by = flops.decode_attention_call(conf, q_rows=1, ctx=100,
                                         kv_bytes_per_elem=1 + 1 / 32)
    assert by == 100 * 8448 + 2 * 32 * 128 * 2
    assert fl == 4 * 128 * 32 * 100
