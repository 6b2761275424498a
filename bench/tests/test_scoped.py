"""``bench/scoped.py`` on a small chip trace with named jits, scopes and
program spans (``record_scoped_trace.py``: a ``prefill_step`` compiled at
256 and at 512 rows, a ``decode_step``, and ``repro.serve.*`` spans inside
``bench.batcher_step``), on ``tiny.xplane.pb``, which names nothing, and
on a tiny CPU batcher whose tick records the harness rebuilds."""
import json
import pathlib

import numpy as np
import pytest

import harness

trace = harness.load_module("trace.py", "test_scoped_trace")
scoped = harness.load_module("scoped.py", "test_scoped_scoped")
DATA = pathlib.Path(__file__).resolve().parent / "data"
READERS = ("attention_core_ms.train", "quantize_ms.train",
           "decode_step_ms.serve", "weight_quant_ms.serve")
OLD_READERS = ("idle_share.train", "mfu.train", "gemm_roofline.train",
               "idle_share.serve", "mfu.serve",
               "decode_attention_roofline.serve")


def reader(name):
    return harness.load_module(f"metrics/{name}.py", f"test_scoped_{name}")


@pytest.fixture(scope="module")
def rec():
    path = str(DATA / "scoped.xplane.pb")
    red = trace.reduce(path)
    with open(DATA / "scoped.hlo.json") as f:
        texts = json.load(f)
    return red, scoped.attribute(path, red, texts), texts


def test_vocabulary_is_the_programs():
    from repro import scopes
    assert scoped.VOCABULARY == scopes.VOCABULARY
    assert scoped.LAYERS == scopes.LAYERS
    for name in ("jit(f)/transpose(jvp(attn))/core/while/body/mul",
                 "jit(f)/jvp()/while/body/closed_call/mlp/quant_w/max",
                 "jit(f)/while/cond/lt"):
        assert scoped.scope_path(name) == scopes.scope_path(name)


def test_each_op_gets_its_module(rec):
    red, sc, _ = rec
    assert {n for n, *_ in sc.runs} == {"jit_prefill_step", "jit_decode_step"}
    # the device clock reads about 1 ms behind the host's here, so the
    # first round's runs start before the window span: two lie inside
    assert len(sc.run_seconds("jit_decode_step")) == 2
    assert len(sc.run_seconds("jit_prefill_step")) == 2
    assert all(o.run >= 0 for o in sc.ops)
    assert all(o.module == sc.runs[o.run][0] for o in sc.ops)


def test_products_get_their_scopes(rec):
    _, sc, _ = rec
    prefill = {o.scope for o in sc.ops if o.module == "jit_prefill_step"}
    decode = {o.scope for o in sc.ops if o.module == "jit_decode_step"}
    # XLA fused both products of the prefill into one fusion: it takes
    # its root's scope, the mlp product's
    assert prefill == {("mlp",), None}
    assert decode == {("mlp", "gemm"), None}
    # the copies XLA put in have no op_name: unscoped, listed by base
    assert {b.split()[0] for b, _ in sc.unscoped_by_base()} == {
        "copy-start", "copy-done"}
    assert 0.9 < sc.scoped_share() < 1.0
    assert set(sc.by_top("jit_prefill_step")) == {"mlp", scoped.UNSCOPED}


def test_prefill_lengths_are_told_apart(rec):
    _, sc, texts = rec
    mods = [scoped.Module.from_text(t) for t in texts]
    assert [m.name for m in mods] == ["jit_prefill_step"] * 2 + [
        "jit_decode_step"]
    # both prefill modules have the same instruction names; the run of
    # the second round (512 rows) is matched to the 512-row module by the
    # shapes in its ops' text, the third round's to the 256-row one
    path = str(DATA / "scoped.xplane.pb")
    _, op_texts, _ = scoped._read(path, 1)
    by_run = {}
    for o in sc.ops:
        if o.module == "jit_prefill_step":
            by_run.setdefault(o.run, []).append(o.op)
    got = [scoped.choose(mods[:2], ops, op_texts) for _, ops in
           sorted(by_run.items())]
    assert got == [mods[1], mods[0]]
    assert any("512" in s for s in mods[1].shapes["fusion"])


def test_finding_line_lists_scopes_and_the_rest(rec):
    _, sc, _ = rec
    line = sc.finding("decode run", 2, "jit_decode_step")
    assert line.startswith("device ms per decode run in jit_decode_step "
                           "by scope (2 decode runs): ")
    ms = 1e3 * sc.by_top("jit_decode_step")["mlp"] / 2
    assert f"mlp {ms:.2f}" in line
    assert f"scoped {100 * sc.scoped_share('jit_decode_step'):.1f} %" in line
    assert "unscoped by base: " in line


def test_program_spans_label_the_gaps(rec):
    _, sc, _ = rec
    # each round's 5 ms sleep is idle time inside bench.batcher_step, and
    # the innermost span there is the program's
    assert [label for _, label in sc.gaps[:3]] == ["repro.serve.retire"] * 3
    assert all(s >= 0.004 for s, _ in sc.gaps[:3])
    assert sc.gaps_by_label()[0][0] == "repro.serve.retire"
    assert any(n.startswith("repro.serve.prefill") for n, _, _ in sc.spans)


def test_idle_pieces_go_to_the_innermost_span():
    spans = [("bench.batcher_step", 0, 100), ("repro.serve.sample", 10, 40),
             ("repro.serve.admit", 60, 90), ("repro.serve.prefill", 70, 80)]
    assert scoped._pieces(spans, 0, 100) == [
        (10e-9, "batcher_step"), (30e-9, "repro.serve.sample"),
        (20e-9, "batcher_step"), (10e-9, "repro.serve.admit"),
        (10e-9, "repro.serve.prefill"), (10e-9, "repro.serve.admit"),
        (10e-9, "batcher_step")]
    assert scoped._pieces(spans, 100, 120) == [(20e-9, "host (no span)")]


def test_decode_step_reader_reads_the_module_runs(rec):
    _, sc, _ = rec
    got = reader("decode_step_ms.serve").compute({"scoped": sc})
    runs = sc.run_seconds("jit_decode_step")
    assert got == pytest.approx(1e3 * float(np.median(runs)))
    assert reader("weight_quant_ms.serve").compute({"scoped": sc}) is None


def test_op_scopes_reads_fusions_and_loop_bodies():
    hlo = "\n".join([
        "HloModule jit_train_step, is_scheduled=true",
        "%fused_computation.1 (p0: bf16[8,8]) -> f32[8,8] {",
        "  %p0 = bf16[8,8]{1,0} parameter(0)",
        "  ROOT %convolution.3 = f32[8,8]{1,0} convolution(%p0, %p0), "
        'metadata={op_name="jit(train_step)/transpose(jvp())/attn/core/'
        'dgrad/dot_general"}',
        "}",
        "ENTRY %main.9 (a: bf16[8,8]) -> f32[8,8] {",
        "  %a = bf16[8,8]{1,0} parameter(0)",
        "  %copy.1 = bf16[8,8]{0,1} copy(%a)",
        "  ROOT %fusion.4 = f32[8,8]{1,0} fusion(bf16[8,8]{1,0} %a), "
        "kind=kOutput, calls=%fused_computation.1, "
        'metadata={op_name="jit(train_step)/transpose(jvp())/attn/core/'
        'dgrad/dot_general" source_file="x.py"}',
        "}",
    ])
    got = scoped.op_scopes(hlo)
    assert got["fusion.4"] == ("attn", "core", "dgrad")
    assert got["convolution.3"] == ("attn", "core", "dgrad")
    assert "copy.1" not in got
    mod = scoped.Module.from_text(hlo)
    assert mod.name == "jit_train_step"
    assert mod.shapes["fusion.4"] == ("f32[8,8]",)
    assert scoped._op_shapes(
        "%copy-start = (bf16[64,8]{1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}) "
        "copy-start(bf16[64,8]{1,0:T(8,128)(2,1)} %w.1)") == (
        "bf16[64,8]", "u32[]")
    assert scoped._op_shapes(
        "%fusion = bf16[512,1024]{1,0:T(8,128)(2,1)} fusion(bf16[1024,1024]"
        "{1,0} %copy-done), kind=kOutput") == ("bf16[512,1024]",)


def test_cast_scopes_find_quantize_casts_fused_under_a_gemm():
    # as a described-v5e compile of the hfp8 step shows them: the cast to
    # f8 under quant_w, the root (a bitcast to u8 for the kernel) under
    # gemm; a quant_g fusion is charged by its own scope already
    meta = 'metadata={{op_name="jit(train_step)/jvp()/while/body/{}"}}'
    hlo = "\n".join([
        "HloModule jit_train_step, is_scheduled=true",
        "%fused_computation.1 (p0: bf16[8,8]) -> u8[8,8] {",
        "  %p0 = bf16[8,8]{1,0} parameter(0)",
        "  %convert.2 = f8e4m3fn[8,8]{1,0:T(8,128)(4,1)} convert(%p0), "
        + meta.format("mlp/quant_w/jit(quantize_tensor)/convert_element_type"),
        "  ROOT %bitcast-convert.3 = u8[8,8]{1,0} bitcast-convert("
        "%convert.2), " + meta.format("mlp/gemm/bitcast_convert_type"),
        "}",
        "%fused_computation.2 (p0: bf16[8,8]) -> f8e5m2[8,8] {",
        "  %p0.1 = bf16[8,8]{1,0} parameter(0)",
        "  ROOT %convert.4 = f8e5m2[8,8]{1,0} convert(%p0.1), "
        + meta.format("mlp/quant_g/convert_element_type"),
        "}",
        "%fused_computation.3 (p0: bf16[8,8]) -> u8[8,8] {",
        "  %p0.2 = bf16[8,8]{1,0} parameter(0)",
        "  ROOT %fusion.9 = u8[8,8]{1,0} fusion(%p0.2), kind=kLoop, "
        "calls=%fused_computation.1, " + meta.format("attn/qkv/gemm/x"),
        "}",
        "%fused_computation.4 (p0: bf16[8,8]) -> f32[8,8] {",
        "  %p0.3 = bf16[8,8]{1,0} parameter(0)",
        "  ROOT %convert.5 = f32[8,8]{1,0} convert(%p0.3), "
        + meta.format("mlp/quant_w/convert_element_type"),
        "}",
        "ENTRY %main.9 (a: bf16[8,8]) -> u8[8,8] {",
        "  %a = bf16[8,8]{1,0} parameter(0)",
        "  %fusion.1 = u8[8,8]{1,0} fusion(%a), kind=kLoop, "
        "calls=%fused_computation.1, " + meta.format("mlp/gemm/x"),
        "  %fusion.2 = f8e5m2[8,8]{1,0} fusion(%a), kind=kLoop, "
        "calls=%fused_computation.2, " + meta.format("mlp/quant_g/x"),
        "  %fusion.3 = u8[8,8]{1,0} fusion(%a), kind=kLoop, "
        "calls=%fused_computation.3, " + meta.format("attn/qkv/gemm/x"),
        "  ROOT %fusion.4 = f32[8,8]{1,0} fusion(%a), kind=kLoop, "
        "calls=%fused_computation.4, " + meta.format("mlp/gemm/x"),
        "}",
    ])
    casts = scoped.cast_scopes(hlo)
    # fusion.3 holds the cast through the fusion it calls; fusion.4's
    # convert makes no f8
    assert casts == {"fusion.1": ("mlp", "quant_w"),
                     "fusion.3": ("mlp", "quant_w"),
                     "fusion.9": ("mlp", "quant_w")}
    mod = scoped.Module.from_text(hlo)
    assert mod.casts == casts
    assert mod.scopes["fusion.1"] == ("mlp", "gemm")


def _fake(ops, runs, window):
    class Op:
        def __init__(self, name, a, b):
            self.name = self.base = name
            self.kind, self.start_ns, self.end_ns, self.device = "", a, b, 0
    sops = [scoped.ScopedOp(Op(n, a, b), m, r, *rest)
            for n, a, b, m, r, *rest in ops]
    return scoped.Scoped(sops, runs, [], [], 1, window)


def test_quantize_readers_count_fused_casts():
    runs = [("jit_train_step", 0, 0, 200), ("jit_decode_step", 0, 200, 300)]
    sc = _fake([("a", 0, 40, "jit_train_step", 0, ("mlp", "quant_g")),
                ("b", 40, 100, "jit_train_step", 0, ("mlp", "gemm"),
                 ("mlp", "quant_x")),
                ("c", 100, 200, "jit_train_step", 0, ("mlp", "gemm")),
                ("d", 200, 230, "jit_decode_step", 1, ("attn", "qkv", "gemm"),
                 ("attn", "qkv", "quant_w"))],
               runs, (0, 300))
    assert [o.quantizes for o in sc.ops] == [True, True, False, True]
    assert reader("quantize_ms.train").compute(
        {"scoped": sc, "steps": 1}) == pytest.approx(1e3 * 130e-9)
    assert reader("weight_quant_ms.serve").compute({"scoped": sc}) == \
        pytest.approx(1e3 * 30e-9)
    line = sc.finding("step", 1, "jit_train_step")
    assert "quantize 0.00 (of it casts fused under other scopes 0.00)" in line
    # the fused cast keeps its fusion's top-level scope
    assert sc.by_top("jit_train_step") == pytest.approx({"mlp": 200e-9})


def test_readers_on_a_known_attribution():
    runs = [("jit_decode_step", 0, 0, 100), ("jit_decode_step", 0, 100, 300),
            ("jit_prefill_step", 0, 300, 400)]
    sc = _fake([("a", 0, 40, "jit_decode_step", 0, ("mlp", "quant_w")),
                ("b", 40, 100, "jit_decode_step", 0, ("mlp", "gemm")),
                ("c", 100, 130, "jit_decode_step", 1, ("attn", "qkv",
                                                      "quant_w")),
                ("d", 130, 300, "jit_decode_step", 1, None),
                ("e", 300, 400, "jit_prefill_step", 2, ("attn", "core"))],
               runs, (0, 400))
    assert reader("weight_quant_ms.serve").compute({"scoped": sc}) == \
        pytest.approx(1e3 * 70e-9 / 2)
    assert reader("decode_step_ms.serve").compute({"scoped": sc}) == \
        pytest.approx(1e3 * 150e-9)
    ctx = {"scoped": sc, "steps": 2}
    assert reader("attention_core_ms.train").compute(ctx) == \
        pytest.approx(1e3 * 100e-9 / 2)
    assert reader("quantize_ms.train").compute(ctx) == \
        pytest.approx(1e3 * 70e-9 / 2)
    assert sc.by_top() == pytest.approx(
        {"attn": 130e-9, "mlp": 100e-9, scoped.UNSCOPED: 170e-9})


@pytest.mark.parametrize("name", READERS)
def test_new_readers_find_nothing_without_scopes(name):
    red = trace.reduce(str(DATA / "tiny.xplane.pb"))
    sc = scoped.attribute(str(DATA / "tiny.xplane.pb"), red)
    assert reader(name).compute({"steps": 4, "trace": red}) is None
    assert reader(name).compute({"steps": 4, "trace": red,
                                 "scoped": sc}) is None


def _old_ctx(red):
    conf = harness.load_json("configs/deepseek-7b-l8-mxfp8.json")
    return {"trace": red, "conf": conf, "chips": 1, "steps": 4,
            "tokens": 4 * 4096, "matmul_ops": set(),
            "workload": harness.load_json("workloads/train-stablelm-l6.json")
            | {"traffic": harness.load_json("mixes/train-seq4096-b1.json")},
            "peaks": harness.peaks("TPU v5 lite"),
            "ticks": [{"prefills": [128], "decodes": [300, 40]}]}


def test_tiny_trace_reads_as_before():
    path = str(DATA / "tiny.xplane.pb")
    red = trace.reduce(path)
    before = (red.busy_s, red.window_s, list(red.gaps), red.breakdown())
    old = {n: reader(n).compute(_old_ctx(red)) for n in OLD_READERS}
    sc = scoped.attribute(path, red)
    assert (red.busy_s, red.window_s, list(red.gaps), red.breakdown()) == \
        before
    assert {n: reader(n).compute(_old_ctx(red)) for n in OLD_READERS} == old
    # no program span there: the idle time is what trace.reduce finds,
    # and the sleeps still own most of it
    assert sum(s for s, _ in sc.gaps) == pytest.approx(
        sum(s for s, _ in red.gaps))
    assert [lb for _, lb in sc.gaps[:4]] == ["host_wait"] * 4
    assert sc.gaps_by_label()[0][0] == red.gaps_by_label()[0][0]
    assert all(o.scope is None for o in sc.ops)


def test_harness_tick_log_equals_program_records():
    import jax
    from repro.configs.base import ModelConfig
    from repro.models import build_model
    from repro.serve.scheduler import ContinuousBatcher

    serve = harness.load_module("modes/serve.py", "test_scoped_serve_mode")
    import traffic
    cfg = ModelConfig(name="tick", family="dense", n_layers=1, d_model=64,
                      n_heads=2, n_kv_heads=1, d_ff=128, vocab_size=97,
                      head_dim=32, policy_name="bf16", attn_q_chunk=8)
    model = build_model(cfg)
    bat = ContinuousBatcher(model, model.init(jax.random.key(0)),
                            max_batch=2, max_len=32, page_size=8)
    sess = serve.Session(bat, harness.Spans())
    rng = np.random.default_rng(0)
    for i, (plen, new) in enumerate([(4, 1), (4, 3), (8, 2), (4, 4)]):
        r = traffic.Request(i, 0.0, rng.integers(1, 97, plen), new)
        sess.offer(r, 0.0, 0.0)
    while not sess.idle():
        sess.tick()
    recs = list(bat.ticks)
    assert len(recs) == len(sess.ticks) > 2
    for h, p in zip(sess.ticks, recs):
        assert h["prefills"] == [n for _, n, _ in p.admitted]
        assert len(h["decodes"]) == p.decoding
        assert h["start"] <= p.start <= p.end <= h["end"]
        assert all(p.start <= t <= h["end"] for _, _, t in p.admitted)
    # the finding line reads each first token's time from the records:
    # never later than the end of the tick that returned it
    uids = list(sess.req)
    line = scoped.first_token_finding(bat.ticks, sess.req, uids)
    assert line.endswith(f"; {len(uids)} requests")
    first = {u: t for p in recs for u, _, t in p.admitted}
    assert all(first[u] <= sess.req[u]["times"][0] for u in uids)
    assert scoped.first_token_finding([], sess.req, uids) is None
    # the queue and page counters reach a finding line
    line = scoped.tick_finding(bat.ticks, recs[0].start, recs[-1].end)
    queue = sorted(p.queue for p in recs if p.admitted)
    assert f"{len(recs)} ticks" in line
    assert f"max {queue[-1]} over {len(queue)} admitting ticks" in line
    assert f"max {max(p.pages for p in recs)}" in line
    assert max(p.pages for p in recs) > 0
    assert scoped.tick_finding(bat.ticks, recs[-1].end + 1, 1e12) is None
    # the compiled text of both phases at the run's shapes
    mods = [scoped.Module.from_text(t)
            for t in scoped.serve_modules(bat, [4, 8])]
    assert [m.name for m in mods] == ["jit_decode_step"] + [
        "jit_prefill_step"] * 2
    assert all(any(m.scopes.values()) for m in mods)
    assert scoped.serve_modules(object(), [4]) == []
