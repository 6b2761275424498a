#!/usr/bin/env python3
"""Run the trainer and the server once on a TPU, at published widths.

    python chip_smoke.py             # one chip: trainer phase, then server
    python chip_smoke.py --chips 4   # the trainer on a 4-chip mesh vs 1 chip

Trainer: ``stablelm-1.6b`` at its published widths under its own
``hfp8`` policy, depth cut to 6 layers (the state then fits 16 GB of
HBM), batch 8 x 512 tokens, 5 steps through ``launch.train``'s
``build_trainer`` / ``Trainer``.  Step 0's loss under the compiled
Pallas kernels must match the ``impl="xla"`` loss of the same
parameters and batch within ``TRAIN_LOSS_RTOL``.

Server: ``llama3.2-3b`` at its full published config (28 layers) under
``mxfp8``, so the paged KV cache holds packed pages.  A
``ContinuousBatcher`` answers 4 requests of different prompt lengths,
one admitted while the others are mid-decode, 16 new tokens each.  The
server's kernels must match their XLA references at its widths
(``kernel_checks``); a one-layer model at the same widths must give
block-prefill and next decode-step logits within ``SHALLOW_RTOL`` of
``impl="xla"``, both compiled to round every bf16 value as the model
declares it (``EXACT_BF16``); at full depth, where the two paths' mxfp8 roundings
decorrelate, the bound is ``SERVE_NOISE_FACTOR`` times the mxfp8
rounding's own effect.

With ``--chips 4`` only the trainer runs: first on one device, then on
``launch.train.auto_mesh()`` (data=1, model=4) with parameters sharded
over all four devices; the per-step losses must agree within
``MESH_LOSS_RTOL``.

The script refuses to run anywhere but a TPU backend, asserts that the
compiled train and decode steps contain Pallas kernels
(``tpu_custom_call``), and exits non-zero on any failed phase.  Its last
line is one JSON object naming the device; findings (compile and step
seconds, losses, tokens, peak device memory) go on the lines before.
Everything runs in this one process, which holds the chip.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import os
import shutil
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.configs import get_arch  # noqa: E402  (src/ is on the path now)
from repro.data.pipeline import DataConfig, SyntheticTokens  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.train import auto_mesh, build_trainer  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serve.kv_cache import paged_kv_applicable  # noqa: E402
from repro.serve.scheduler import (ContinuousBatcher,  # noqa: E402
                                   ServeRequest)

#: step-0 loss, compiled Pallas vs the XLA reference: half a bf16 ulp
#: (2^-9) relative — both paths quantize identically and differ only in
#: f32 summation order inside the GEMMs
TRAIN_LOSS_RTOL = 2.0 ** -9
#: packed GEMM, Pallas vs XLA (f32 out): both sum exact products of
#: decoded fp8 operands in f32 and differ only in summation order
GEMM_RTOL = 2.0 ** -16
#: packed decode attention, Pallas vs XLA (bf16 out): the f32 softmax
#: paths differ in exp / reciprocal rounding, far below a bf16 ulp, so
#: only a fraction of outputs flip by one ulp (2^-8 relative)
ATTN_RTOL = 2.0 ** -8
#: a one-layer server at full widths, Pallas vs XLA logits (rel RMS):
#: the paths share every rounding but f32 summation order (online
#: softmax over KV tiles), so a rare fp8 rounding flips.  One flipped
#: E4M3 rounding in the last row of one activation moves these logits
#: by 4.3e-5 to 3.8e-3 (CPU, XLA path, 35 single flips); the bound
#: admits two of the largest, and fails a systematic gap (0.02 to 0.08)
SHALLOW_LAYERS, SHALLOW_RTOL = 1, 8e-3
#: both shallow programs round every bf16 value the model declares.  By
#: default XLA on TPU keeps fused bf16 arithmetic (the SwiGLU product,
#: residual adds) in f32 up to the next op, so the XLA path quantizes
#: values the kernels only see rounded to bf16 in HBM; each such input
#: flips fp8 roundings, which moved the 1-layer logits by ~0.08
EXACT_BF16 = {"xla_allow_excess_precision": False}
#: the full-depth server: Pallas-vs-XLA over XLA-mxfp8-vs-bf16.  Two
#: independent roundings of one model differ by sqrt(2) times the
#: rounding's own effect; 2 leaves room for unequal noise, while a
#: wrong kernel (unrelated or zero logits) lands near sqrt(2) / 0.1
SERVE_NOISE_FACTOR = 2.0
#: per-step loss, 4-chip mesh vs one device: the TP partial sums
#: reduce in another order, and Adam's first steps amplify sign flips
#: of near-zero gradients
MESH_LOSS_RTOL = 2.0 ** -8

TRAIN_ARCH, TRAIN_LAYERS, TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = (
    "stablelm-1.6b", 6, 8, 512, 5)
SERVE_ARCH, SERVE_POLICY = "llama3.2-3b", "mxfp8"
SERVE_PROMPTS, SERVE_NEW, SERVE_SLOTS, SERVE_MAX_LEN = (
    (17, 64, 130, 200), 16, 4, 256)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {msg}")


def require_kernels(compiled, what: str) -> int:
    """The compiled program must call Pallas kernels (no silent XLA
    fallback); returns how many call sites it has."""
    n = compiled.as_text().count("tpu_custom_call")
    check(n > 0, f"{what}: no tpu_custom_call in the compiled program")
    return n


def peak_bytes(dev) -> "int | None":
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def fresh_dir(name: str) -> str:
    path = os.path.join(ROOT, ".smoke_ckpt", name)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def train_config(layers: int = TRAIN_LAYERS):
    return dataclasses.replace(get_arch(TRAIN_ARCH), n_layers=layers)


# ----------------------------------------------------------- trainer ----

def run_trainer(cfg, *, batch, seq, steps, mesh=None, tag="train"):
    """Build through ``launch.train.build_trainer``, compile the step
    ahead of time (timed; Pallas kernels asserted), take ``steps``
    steps.  Returns ``(report, trainer)``."""
    trainer = build_trainer(cfg, steps=steps, batch=batch, seq=seq,
                            ckpt=fresh_dir(tag), save_every=steps + 1,
                            mesh=mesh)
    check(trainer.start_step == 0, f"{tag}: resumed from a stale checkpoint")
    batch0 = trainer.data.global_batch_at_step(0)
    t0 = time.perf_counter()
    compiled = trainer.train_step.lower(trainer.state, batch0).compile()
    compile_s = time.perf_counter() - t0
    if mesh is None:
        kernels = require_kernels(compiled, f"{tag} step")
    else:
        # GSPMD partitions the mesh step's GEMMs, which Mosaic kernels
        # cannot be (models/layers.py proj): they run as XLA dots there
        kernels = compiled.as_text().count("tpu_custom_call")
    hist = trainer.run(steps)
    losses = [m["loss"] for m in hist]
    times = [m["step_time_s"] for m in hist]
    check(all(map(_finite, losses)), f"{tag}: non-finite loss {losses}")
    check(not any(m["skipped"] for m in hist), f"{tag}: skipped steps")
    steady = statistics.median(times[1:]) if len(times) > 1 else times[0]
    report = {"compile_s": compile_s, "kernel_calls": kernels,
              "step_s": times, "steady_step_s": steady,
              "tokens_per_s": batch * seq / steady, "losses": losses}
    return report, trainer


def _finite(x) -> bool:
    return x == x and abs(x) != float("inf")


def train_phase(cfg, *, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
                steps=TRAIN_STEPS) -> dict:
    # the XLA reference loss of step 0: the trainer's params (its state
    # is made by model.init from the same seed) and batch, computed
    # while the optimizer state does not yet occupy the device
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    tokens = SyntheticTokens(
        DataConfig(cfg.vocab_size, seq, batch)).global_batch_at_step(0)
    ref_loss = float(jax.jit(lambda p, t: model.loss(p, t, impl="xla"))(
        params, tokens))
    del params
    gc.collect()

    report, trainer = run_trainer(cfg, batch=batch, seq=seq, steps=steps)
    del trainer
    gc.collect()
    got = report["losses"][0]
    report["xla_step0_loss"] = ref_loss
    report["step0_rel_err"] = abs(got - ref_loss) / abs(ref_loss)
    check(report["step0_rel_err"] <= TRAIN_LOSS_RTOL,
          f"step-0 loss {got} vs xla {ref_loss}")
    return report


# ------------------------------------------------------------ server ----

def serve_phase(cfg, *, prompt_lens=SERVE_PROMPTS, new_tokens=SERVE_NEW,
                slots=SERVE_SLOTS, max_len=SERVE_MAX_LEN,
                page_size=16) -> dict:
    check(paged_kv_applicable(cfg, cfg.policy_name),
          f"{cfg.name}/{cfg.policy_name}: cache pages would not be packed")
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in prompt_lens]
    run = functools.partial(_prefill_then_decode, prompts=prompts,
                            slots=slots, max_len=max_len,
                            page_size=page_size)
    kernels_vs_xla = kernel_checks(cfg, max_len=max_len)
    log(f"serve kernels vs xla: {json.dumps(kernels_vs_xla)}")
    shallow = shallow_check(cfg, run, nxt=[int(p[-1]) for p in prompts])
    log(f"serve {SHALLOW_LAYERS}-layer logits vs xla: {shallow}")

    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.key(0))
    reqs = [ServeRequest(i, p, new_tokens) for i, p in enumerate(prompts)]

    cb = ContinuousBatcher(model, params, max_batch=slots, max_len=max_len,
                           page_size=page_size)
    toks = jnp.zeros((slots,), jnp.int32)
    t0 = time.perf_counter()
    compiled = cb.decode_step.lower(params, toks, cb.cache).compile()
    decode_compile_s = time.perf_counter() - t0
    kernels = require_kernels(compiled, "decode step")

    # all but the last request start together; the last is submitted
    # once the others are decoding, into the slot left free
    t0 = time.perf_counter()
    cb.pending.extend(reqs[:-1])
    ticks = 0
    while ticks < 4:
        cb.step()
        ticks += 1
    busy = sum(s is not None for s in cb.slots)
    check(busy == len(reqs) - 1, f"expected {len(reqs) - 1} busy slots")
    cb.pending.append(reqs[-1])
    while cb.pending or any(s is not None for s in cb.slots):
        cb.step()
        ticks += 1
    wall_s = time.perf_counter() - t0
    out = cb.done
    for r in reqs:
        check(len(out.get(r.uid, ())) == new_tokens,
              f"request {r.uid}: {len(out.get(r.uid, ()))} tokens")

    # the compiled path against impl="xla" at full depth.  The two
    # paths' mxfp8 roundings decorrelate there (any last-bit difference
    # moves a whole fp8 ulp, which later layers spread), so the bound
    # is the format's own effect: the same weights under bf16.  The
    # kernels themselves are held to tight tolerances in kernel_checks.
    nxt = [int(out[r.uid][0]) for r in reqs]       # what the batcher sampled
    got = run(model, params, cb.decode_step, nxt=nxt)
    want = run(model, params, _xla_step(model), nxt=nxt)
    wide_model = build_model(dataclasses.replace(cfg, policy_name="bf16"))
    wide = run(wide_model, params, _xla_step(wide_model), nxt=nxt)
    errs = [_rel_rms(g, w) for g, w in zip(got, want)]
    noise = [_rel_rms(w, b) for w, b in zip(want, wide)]
    check(all(np.isfinite(g).all() for g in got), "non-finite logits")
    for e, n in zip(errs, noise):
        check(e <= SERVE_NOISE_FACTOR * n,
              f"logits vs xla: rel rms {e}, mxfp8 vs bf16 {n}")
    del params
    gc.collect()

    n_tok = sum(len(v) for v in out.values())
    return {"decode_compile_s": decode_compile_s, "kernel_calls": kernels,
            "requests": len(reqs), "prompt_lens": list(prompt_lens),
            "tokens": n_tok, "scheduler_ticks": ticks,
            "wall_s_incl_prefill_compiles": wall_s,
            "rel_rms_vs_xla": errs, "rel_rms_xla_vs_bf16": noise,
            "shallow_rel_rms_vs_xla": shallow,
            "kernels_vs_xla": kernels_vs_xla,
            "first_tokens": {str(k): v[:4].tolist() for k, v in out.items()}}


def _xla_step(model, compiler_options=None):
    return jax.jit(functools.partial(model.decode_step, impl="xla"),
                   compiler_options=compiler_options)


def shallow_check(cfg, run, *, nxt) -> list:
    """``cfg`` cut to ``SHALLOW_LAYERS`` layers (widths unchanged): the
    compiled path's block-prefill and decode-step logits against
    ``impl="xla"`` on the same weights, each within ``SHALLOW_RTOL``,
    both compiled with ``EXACT_BF16``."""
    model = build_model(dataclasses.replace(cfg, n_layers=SHALLOW_LAYERS))
    params = jax.jit(model.init)(jax.random.key(0))
    pallas = jax.jit(model.decode_step,                 # impl="auto"
                     compiler_options=EXACT_BF16)
    got = run(model, params, pallas, nxt=nxt)
    want = run(model, params, _xla_step(model, EXACT_BF16), nxt=nxt)
    errs = [_rel_rms(g, w) for g, w in zip(got, want)]
    check(all(np.isfinite(g).all() for g in got), "shallow: non-finite")
    check(max(errs) <= SHALLOW_RTOL,
          f"{SHALLOW_LAYERS}-layer logits vs xla: rel rms {errs}")
    return errs


def kernel_checks(cfg, *, max_len, tokens=512, seed=1) -> dict:
    """The server's Mosaic kernels against their XLA references at its
    widths, on random data: the packed quantize must agree bit for bit,
    the packed GEMM and the packed decode attention (steady decode, and
    a 17-row block prefill whose q tile is padded) within
    ``GEMM_RTOL`` / ``ATTN_RTOL``."""
    from repro.core.policy import get_policy
    from repro.kernels import ops

    pol = get_policy(cfg.policy_name)
    mx, mx_kv = pol.mx_fwd, pol.mx_kv_cache_name
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(0, 1, (tokens, cfg.d_model)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(0, 0.02, (cfg.d_model, cfg.d_ff)),
                    jnp.bfloat16)
    xp, xs = ops.mx_quantize(x, mx, impl="auto", packed=True)
    rp, rs = ops.mx_quantize(x, mx, impl="xla", packed=True)
    bad = int(np.sum(np.asarray(xp) != np.asarray(rp))
              + np.sum(np.asarray(xs) != np.asarray(rs)))
    check(bad == 0, f"packed quantize: {bad} bytes differ from xla")
    wp, ws = ops.mx_quantize(w.T, mx, impl="xla", packed=True)
    gemm = _rel_rms(*(np.asarray(ops.mx_gemm_packed(
        rp, rs, wp, ws, mx_a=mx, out_dtype=jnp.float32, impl=impl))
        for impl in ("auto", "xla")))
    check(gemm <= GEMM_RTOL, f"packed GEMM vs xla: rel rms {gemm}")
    attn = {}
    bh, hd = cfg.n_heads, cfg.head_dim_eff
    for s, base in ((1, 40), (17, 0)):
        q = jnp.asarray(rng.normal(0, 1, (bh, s, hd)), jnp.bfloat16)
        kv = [ops.mx_quantize_kv(jnp.asarray(rng.normal(0, 1, (
            bh, max_len, hd)), jnp.float32), mx_kv, impl="xla")
            for _ in range(2)]
        lens = jnp.full((bh,), base, jnp.int32)
        attn[f"S{s}"] = e = _rel_rms(*(np.asarray(
            ops.mx_decode_attention_packed(
                q, *kv[0], *kv[1], lens, mx_k=mx_kv, impl=impl),
            np.float32) for impl in ("auto", "xla")))
        check(e <= ATTN_RTOL, f"packed decode attention S={s}: {e}")
    return {"quantize_bytes_differing": bad, "gemm_rel_rms": gemm,
            "decode_attention_rel_rms": attn}


def _prefill_then_decode(model, params, step, *, prompts, nxt, slots,
                         max_len, page_size):
    """Logits of each prompt's block prefill into a fresh pool (the
    batcher's shapes), then of one batched decode step on tokens
    ``nxt``: ``[prefill_0, ..., prefill_n, decode]``."""
    cache = model.init_cache(slots, max_len, paged=True, page_size=page_size)
    kv, logits = cache["kv"], []
    for b, p in enumerate(prompts):
        view = {"kv": kv, "pt": cache["pt"][b:b + 1],
                "lens": jnp.zeros((1,), jnp.int32)}
        lg, view = step(params, jnp.asarray(p[None]), view)
        kv = view["kv"]
        logits.append(np.asarray(lg[0], np.float32))
    lens = jnp.asarray([len(p) for p in prompts], jnp.int32)
    lg, _ = step(params, jnp.asarray(nxt, jnp.int32),
                 {"kv": kv, "pt": cache["pt"], "lens": lens})
    return logits + [np.asarray(lg, np.float32)]


def _rel_rms(got, want) -> float:
    return float(np.sqrt(np.mean((got - want) ** 2))
                 / max(np.sqrt(np.mean(want ** 2)), 1e-30))


# -------------------------------------------------------- four chips ----

def mesh_phase(cfg, *, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
               steps=TRAIN_STEPS) -> dict:
    one, trainer = run_trainer(cfg, batch=batch, seq=seq, steps=steps,
                               tag="one_device")
    del trainer
    gc.collect()

    mesh = auto_mesh()
    check(mesh is not None and dict(mesh.shape) == {"data": 1, "model": 4},
          f"auto_mesh gave {mesh and dict(mesh.shape)}")
    four, trainer = run_trainer(cfg, batch=batch, seq=seq, steps=steps,
                                mesh=mesh, tag="mesh")
    sharded = _sharding_report(trainer.state["params"], jax.devices())
    del trainer
    gc.collect()
    errs = [abs(a - b) / abs(b) for a, b in zip(four["losses"],
                                                one["losses"])]
    four["rel_err_vs_one_device"] = errs
    check(max(errs) <= MESH_LOSS_RTOL,
          f"mesh losses {four['losses']} vs one device {one['losses']}")
    return {"one_device": one, "mesh": four, "params": sharded}


def _sharding_report(params, devices) -> dict:
    """Where the parameters live: every leaf spans all devices, and a
    device holds well under the whole model (not a quiet replica)."""
    leaves = jax.tree.leaves(params)
    total = sum(x.nbytes for x in leaves)
    per_dev = {d.id: 0 for d in devices}
    for x in leaves:
        check(x.sharding.device_set == set(devices),
              f"param {x.shape} lives on {x.sharding.device_set}")
        for s in x.addressable_shards:
            per_dev[s.device.id] += s.data.nbytes
    check(max(per_dev.values()) <= 0.5 * total,
          f"params per device {per_dev} of {total} B: not sharded")
    return {"total_bytes": total, "bytes_per_device": per_dev}


# -------------------------------------------------------------- main ----

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)



    backend = jax.default_backend()
    if backend != "tpu":
        print(f"[smoke] no TPU: JAX backend is {backend!r}", file=sys.stderr)
        return 1
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"[smoke] --chips {args.chips} but JAX sees {len(devices)}",
              file=sys.stderr)
        return 1
    dev = devices[0]
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}")
    cache = enable_compile_cache()
    warm = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    log(f"compile cache: {cache} ({warm} entries at start)")
    log(f"LIBTPU_INIT_ARGS={os.environ.get('LIBTPU_INIT_ARGS', '')!r}")

    summary = {}
    if args.chips == 4:
        cfg = train_config()
        log(f"mesh trainer: {cfg.name} d_model={cfg.d_model} "
            f"layers={cfg.n_layers} (of 24, cut to fit) "
            f"policy={cfg.policy_name}")
        summary["mesh"] = mesh_phase(cfg)
        log(f"mesh: {json.dumps(summary['mesh'])}")
    else:
        cfg = train_config()
        log(f"trainer: {cfg.name} d_model={cfg.d_model} "
            f"heads={cfg.n_heads}x{cfg.head_dim_eff} d_ff={cfg.d_ff} "
            f"vocab={cfg.vocab_size} layers={cfg.n_layers} (of 24, cut to "
            f"fit 16 GB) policy={cfg.policy_name} batch={TRAIN_BATCH}x"
            f"{TRAIN_SEQ} steps={TRAIN_STEPS}")
        summary["train"] = train_phase(cfg)
        summary["train"]["peak_bytes_in_use"] = peak_bytes(dev)
        log(f"train: {json.dumps(summary['train'])}")

        cfg = dataclasses.replace(get_arch(SERVE_ARCH),
                                  policy_name=SERVE_POLICY)
        log(f"server: {cfg.name} d_model={cfg.d_model} layers="
            f"{cfg.n_layers} heads={cfg.n_heads}/{cfg.n_kv_heads}x"
            f"{cfg.head_dim_eff} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
            f"policy={cfg.policy_name}")
        summary["serve"] = serve_phase(cfg)
        summary["serve"]["peak_bytes_in_use"] = peak_bytes(dev)
        log(f"serve: {json.dumps(summary['serve'])}")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
