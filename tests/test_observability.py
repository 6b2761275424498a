"""What a profile can see of the program (``repro.scopes``).

* Every matrix product of the compiled train step and of the batcher's
  ``prefill_step``/``decode_step`` carries a scope of the vocabulary, so
  a trace's device time can be charged to the layer that issued it.
* ``ContinuousBatcher.ticks`` agrees with the tokens the batcher returns,
  and the batcher still decodes what ``generate`` decodes.
* The host spans of ``Trainer`` and ``ContinuousBatcher`` land in a
  ``jax.profiler`` trace.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import scopes
from repro.configs.base import ModelConfig
from repro.data.pipeline import DataConfig, SyntheticTokens
from repro.models import build_model
from repro.models import layers as L
from repro.optim.adamw import AdamWConfig
from repro.serve.decode import generate
from repro.serve.scheduler import ContinuousBatcher, ServeRequest
from repro.train.train_step import make_train_state, make_train_step
from repro.train.trainer import Trainer

_MATMUL = re.compile(r"\s(dot|convolution|custom-call)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _cfg(policy):
    # one layer (layers are scanned: depth adds nothing to the program)
    return ModelConfig(name=f"obs-{policy}", family="dense", n_layers=1,
                       d_model=64, n_heads=2, n_kv_heads=1, d_ff=128,
                       vocab_size=97, head_dim=32, policy_name=policy,
                       qkv_bias=True, attn_q_chunk=8)


def _matmul_scopes(hlo: str) -> list:
    """(instruction, scope path) of every matrix product in ``hlo``."""
    out = []
    for line in hlo.splitlines():
        if not _MATMUL.search(line):
            continue
        name = line.split("=", 1)[0].split()[-1]
        m = _OP_NAME.search(line)
        out.append((name, scopes.scope_path(m.group(1)) if m else ()))
    return out


def _all_scopes(hlo: str) -> set:
    return {s for m in _OP_NAME.finditer(hlo)
            for s in scopes.scope_path(m.group(1))}


@pytest.fixture(scope="module")
def served():
    """An hfp8 model for the batcher: its decode step quantizes the
    weights (``quant_w``) like the MX policies do, at a fraction of their
    tracing time on the CPU."""
    model = build_model(_cfg("hfp8"))
    return model, model.init(jax.random.key(0))


def test_scope_path_unwraps_transformations():
    name = ("jit(train_step)/transpose(jvp())/while/body/closed_call/"
            "checkpoint/rematted_computation/attn/core/dgrad/dot_general")
    assert scopes.scope_path(name) == ("attn", "core", "dgrad")
    assert scopes.scope_path("jit(step)/transpose(jvp(loss))/add_any") == (
        "loss",)
    assert scopes.scope_path("jit(step)/while/body/dynamic_slice") == ()


def test_train_step_products_carry_scopes():
    model = build_model(_cfg("hfp8"))
    opt = AdamWConfig(lr=1e-3, warmup_steps=1, schedule="constant")
    state = make_train_state(model, jax.random.key(0), opt)
    step = jax.jit(make_train_step(model, opt, impl="xla"))
    tokens = jnp.zeros((2, 16), jnp.int32)
    hlo = step.lower(state, tokens).compile().as_text()
    products = _matmul_scopes(hlo)
    assert products
    unscoped = [n for n, path in products if not path]
    assert not unscoped, unscoped
    for _, path in products:
        assert path[0] in scopes.LAYERS, path
    found = _all_scopes(hlo)
    assert {"embed", "norm", "attn", "mlp", "head", "loss",
            "optimizer"} <= found
    assert {"qkv", "rope", "core", "out"} <= found
    assert {"quant_x", "quant_w", "gemm", "quant_g", "dgrad",
            "wgrad"} <= found


def test_mx_attention_products_carry_scopes():
    """What mxfp8 training adds to hfp8's: the packed MX GEMMs with their
    backward, and the quantized attention core with its recomputing
    backward.  The gradient of one attention layer holds both; the whole
    step would take three times as long to trace on the CPU (the XLA
    reference quantizer), for the same code paths."""
    cfg = _cfg("mxfp8")
    p = L.init_attention(jax.random.key(0), cfg, jnp.float32)
    # 16 tokens tile the quantized flash path of mxfp8 attention
    x = jnp.ones((2, 16, cfg.d_model), jnp.float32)

    def f(x, p):
        out, _ = L.attention(x, p, cfg, cfg.policy_name,
                             positions=jnp.arange(16)[None], impl="xla")
        return jnp.sum(out * out)

    hlo = jax.jit(jax.grad(f, argnums=(0, 1))).lower(x, p).compile() \
        .as_text()
    products = _matmul_scopes(hlo)
    assert products and all(path for _, path in products), products
    assert {path for _, path in products} >= {
        ("attn", "core"), ("attn", "qkv", "gemm"), ("attn", "qkv", "dgrad"),
        ("attn", "qkv", "wgrad"), ("attn", "out", "gemm")}
    assert {"quant_x", "quant_w", "quant_g", "rope"} <= _all_scopes(hlo)


def test_batcher_steps_carry_scopes(served):
    model, params = served
    cb = ContinuousBatcher(model, params, max_batch=2, max_len=32,
                           page_size=8)
    pre = cb.prefill_step.lower(
        params, jnp.zeros((1, 8), jnp.int32),
        {"kv": cb.cache["kv"], "pt": jnp.asarray(cb.pt[:1]),
         "lens": jnp.zeros((1,), jnp.int32)}).compile().as_text()
    dec = cb.decode_step.lower(params, jnp.zeros((2,), jnp.int32),
                               cb.cache).compile().as_text()
    for hlo in (pre, dec):
        products = _matmul_scopes(hlo)
        assert products and all(path for _, path in products), products
        assert {"attn", "kv_write", "kv_gather", "core", "quant_w",
                "gemm"} <= _all_scopes(hlo)


def _requests(n, seed=0):
    """``n`` prompts of 4 tokens (one prefill shape), request ``i``
    asking for ``2 + i`` tokens."""
    rng = np.random.default_rng(seed)
    return [ServeRequest(i, rng.integers(1, 97, 4).astype(np.int32), 2 + i)
            for i in range(n)]


def test_tick_records_agree_with_tokens(served):
    model, params = served
    cb = ContinuousBatcher(model, params, max_batch=2, max_len=32,
                           page_size=8)
    reqs = _requests(3)
    out = cb.run(reqs)
    ticks = list(cb.ticks)
    admitted = [a for t in ticks for a in t.admitted]
    assert sorted(u for u, _, _ in admitted) == [r.uid for r in reqs]
    for uid, plen, t_first in admitted:
        assert plen == len(reqs[uid].prompt)
    # each request gets one token at its prefill and one per decode tick
    # it takes part in: all tokens = admissions + decoding slots
    assert len(admitted) + sum(t.decoding for t in ticks) == sum(
        len(v) for v in out.values())
    for t in ticks:
        assert t.start <= t.end
        assert all(t.start <= tf <= t.end for _, _, tf in t.admitted)
        assert 0 <= t.decoding <= 2 and t.queue >= 0
    assert ticks[0].queue == 3 and ticks[0].pages > 0
    assert ticks[-1].pages == 0       # the last retire freed every page
    prompts = jnp.asarray(np.stack([r.prompt for r in reqs]))
    want = np.asarray(generate(model, params, prompts, max_new_tokens=4,
                               max_len=32, paged=True, page_size=8))
    for r in reqs:
        np.testing.assert_array_equal(out[r.uid],
                                      want[r.uid, :r.max_new_tokens])


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    path = sorted(trace_dir.rglob("*.xplane.pb"))[-1]
    data = ProfileData.from_file(str(path))
    return [e.name for p in data.planes for line in p.lines
            for e in line.events]


def test_host_spans_land_in_a_profile(tmp_path, served):
    model, params = served
    cfg = _cfg("bf16")
    tmodel = build_model(cfg)
    opt = AdamWConfig(lr=1e-3, warmup_steps=1, schedule="constant")
    state = make_train_state(tmodel, jax.random.key(0), opt)
    data = SyntheticTokens(DataConfig(cfg.vocab_size, seq_len=8,
                                      global_batch=2))
    tr = Trainer(tmodel, make_train_step(tmodel, opt, impl="xla"), state,
                 data, ckpt_dir=str(tmp_path / "ckpt"), save_every=2)
    cb = ContinuousBatcher(model, params, max_batch=2, max_len=32,
                           page_size=8)
    cb.pending.extend(_requests(3))
    tr.run(1)
    cb.step()               # compiles; request 0 retires, 2 waits
    tr.start_step = 1       # steps 1, 2; step 1 saves (save_every=2)
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        tr.run(2)
        for _ in range(3):
            cb.step()
    finally:
        jax.profiler.stop_trace()
    names = _host_events(tmp_path / "trace")
    spans = {n.split("#", 1)[0] for n in names if n.startswith("repro.")}
    assert set(scopes.TRAIN_SPANS) <= spans, spans
    assert set(scopes.SERVE_SPANS) <= spans, spans
    assert len(cb.ticks) == 4
