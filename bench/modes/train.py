"""Training mode: a closed loop of the program's jitted train step.

Set-up makes the weights from the seed, builds the state with the
program's ``make_train_state`` and the step with ``make_train_step``
(AdamW as the workload file states it), hands both to the program's
``Trainer`` and drives its first three steps through ``Trainer.run`` on
the seeded batches of steps 0..2 (the first compiles).  The window then
calls ``Trainer.run`` one step at a time, on new rows each step, for
``seconds``; ``train_tokens_per_s`` is all tokens of the steps it
completed over the whole window.

``correct``: once the window has closed and the program's state is
freed, the float32 reference (``bench/reference.py``) follows the same
three steps from the same weights.  Compared, each against its limit in
the workload file: the norm of the first gradient as the optimizer got
it (its first moment after one step over 1 - b1), worst leaf; the norm
of each parameter's change over the three steps (the float32 master that
step 4 starts from), worst leaf; and the relative difference of the
head's first gradient from the reference's.  A leaf gap is
|norm(program) - norm(reference)| over the larger of the reference's
norm of that leaf and its median leaf norm.  Leaves whose reference
gradient is under 1e-3 of the median leaf's (a key bias under softmax)
move by round-off alone and are left out of the change.  The loss of
each step is read too, and printed, but not compared: no control or
fault moves it far enough from the sound program's (PERF.md).
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

import harness
import reference
import traffic
from repro.models import build_model
from repro.optim.adamw import AdamWConfig
from repro.train.train_step import make_train_state, make_train_step
from repro.train.trainer import Trainer

trace_mod = harness.load_module("trace.py", "bench_trace")

SETUP_STEPS = 3
NEGLIGIBLE_GRAD = 1e-3


def worst_leaf_gap(prog: dict, ref: dict, keep=None) -> tuple:
    """(largest gap, its leaf) of per-leaf norms, each over the larger of
    the reference leaf's norm and the median reference leaf norm."""
    names = [n for n in ref if keep is None or n in keep]
    med = statistics.median(ref[n] for n in names)
    gaps = {n: abs(prog[n] - ref[n]) / max(ref[n], med) for n in names}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def build(conf: dict, workload: dict, seed: int, spans):
    """The program's trainer for this cell, weights from the seed."""
    cfg = harness.model_config(conf)
    model = build_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    weights = [harness.make_weights(shapes, seed)]
    program = dataclasses.replace(model, init=lambda key: weights.pop())
    opt_cfg = AdamWConfig(**workload["optimizer"])
    state = make_train_state(program, harness.seed_key(seed), opt_cfg)
    step = make_train_step(model, opt_cfg, impl="auto")
    t = workload["traffic"]
    data = traffic.TrainBatches(vocab=cfg.vocab_size, seq=t["seq"],
                                batch=t["batch"], seed=seed, span=spans)
    ckpt = harness.ROOT / ".bench_ckpt" / workload["name"]
    shutil.rmtree(ckpt, ignore_errors=True)
    trainer = Trainer(model, step, state, data, ckpt_dir=str(ckpt),
                      save_every=2 ** 62)
    return trainer, shapes


def reference_steps(conf: dict, workload: dict, shapes, seed: int, data,
                    g_head):
    """The reference's losses, first-gradient leaf norms and change leaf
    norms over the first ``SETUP_STEPS`` steps, and the relative
    difference of the program's first head gradient ``g_head`` from
    its own."""
    step = reference.train_step_fn(conf, workload["optimizer"],
                                   harness.norm_arrays)
    p = jax.tree.map(lambda a: a.astype(jnp.float32),
                     harness.make_weights(shapes, seed))
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    losses, gnorms, head = [], None, None
    for k in range(SETUP_STEPS):
        p, m, v, lval, gn, gh = step(p, m, v, jnp.int32(k),
                                     jnp.asarray(data.global_batch_at_step(k)))
        losses.append(float(lval))
        if k == 0:
            gnorms = harness.norms_to_dict(shapes, gn)
            head = float(jnp.linalg.norm(gh - jnp.asarray(g_head))
                         / jnp.linalg.norm(gh))
        del gh
    del m, v
    w0 = harness.make_weights(shapes, seed)
    change = harness.leaf_norms(p, minus=w0)
    return losses, gnorms, change, head


def first_steps(trainer, shapes, seed: int, b1: float, spans):
    """Drive the trainer's first ``SETUP_STEPS`` steps through
    ``Trainer.run``; returns (their metrics, first-gradient leaf norms as
    the optimizer got them, leaf norms of the master's change, the first
    gradient of the head as the optimizer got it, on the host)."""
    first = []
    for k in range(SETUP_STEPS):
        trainer.start_step = k
        with spans("train_step"):
            trainer.run(1)
        first.append(trainer.metrics_log[-1])
        if k == 0:
            m = trainer.state["opt"]["m"]
            g_prog = {n: x / (1 - b1) for n, x in
                      harness.leaf_norms(m).items()}
            g_head = np.asarray(jax.device_get(m["lm_head"])) / (1 - b1)
    w0 = harness.make_weights(shapes, seed)
    change = harness.leaf_norms(trainer.state["opt"]["master"], minus=w0)
    return first, g_prog, change, g_head


def compare(first, g_prog, change_prog, ref) -> dict:
    """The numbers read against the reference (see the module docstring)."""
    ref_losses, g_ref, change_ref, head = ref
    med_g = statistics.median(g_ref.values())
    moved = {n for n, x in g_ref.items() if x >= NEGLIGIBLE_GRAD * med_g}
    grad_gap, grad_leaf = worst_leaf_gap(g_prog, g_ref)
    change_gap, change_leaf = worst_leaf_gap(change_prog, change_ref, moved)
    return {"loss_rel_gap": max(abs(m["loss"] - r) / abs(r)
                                for m, r in zip(first, ref_losses)),
            "grad_norm_gap": grad_gap, "change_norm_gap": change_gap,
            "head_grad_rel_diff": head,
            "worst_grad_leaf": grad_leaf, "worst_change_leaf": change_leaf,
            "left_out": sorted(set(g_ref) - moved),
            "sound": all(not m["skipped"] and np.isfinite(m["loss"])
                         for m in first)}


def slow_steps(done, walls, pauses, over: float = 0.05) -> str:
    """A finding line: the window's steps that took ``over`` seconds or
    more past the median on the harness's clock, each with the step time
    the program measured and the garbage collector's pauses inside it."""
    med = statistics.median(b - a for a, b in walls)
    slow = [(i, a, b) for i, (a, b) in enumerate(walls) if b - a >= med + over]
    gen2 = [p for p in pauses.pauses if p[2] == 2]
    head = (f"slow steps: {len(slow)} of {len(walls)} at >= {over} s past "
            f"the median {med:.4f} s; collector pauses in the window "
            f"{len(pauses.pauses)} ({len(gen2)} of generation 2), "
            f"{pauses.within(walls[0][0], walls[-1][1]):.3f} s in all")
    return head + "".join(
        f"; step {i}: {b - a:.3f} s (program {done[i]['step_time_s']:.3f} s,"
        f" collector {pauses.within(a, b):.3f} s)" for i, a, b in slow)


def run(*, workload, conf, seed, seconds, trace, t_start):
    devs = jax.devices()[: workload["chips"]]
    spans = harness.Spans()
    trainer, shapes = build(conf, workload, seed, spans)
    t = workload["traffic"]
    tokens_per_step = t["batch"] * t["seq"]

    def one_step(k):
        trainer.start_step = k
        with spans("train_step"):
            trainer.run(1)
        return trainer.metrics_log[-1]

    # set-up: the first steps, through the window's own call
    first, g_prog, change_prog, g_head = first_steps(
        trainer, shapes, seed, workload["optimizer"]["b1"], spans)
    harness.find("set-up steps: " + ", ".join(
        f"loss {m['loss']:.6f} skipped {int(m['skipped'])} "
        f"{m['step_time_s']:.3f} s" for m in first))

    # the window
    tw = harness.TracedWindow(trace, workload["trace_seconds"],
                              harness.ROOT / ".bench_trace" / workload["name"],
                              spans)
    compiles = harness.CompileCounter()
    pauses = harness.GcPauses()
    done, walls, traced_steps = [], [], None
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    compiles.armed = pauses.armed = True
    tw.start()
    k = SETUP_STEPS
    while time.perf_counter() - t0 < seconds:
        a = time.perf_counter()
        done.append(one_step(k))
        walls.append((a, time.perf_counter()))
        k += 1
        if tw.due():
            tw.stop()
            traced_steps = len(done)
    t1 = time.perf_counter()
    compiles.armed = pauses.armed = False
    if tw.active:
        tw.stop()
        traced_steps = len(done)
    window_s = t1 - t0
    steps = len(done)
    failed = sum(1 for m in done if m["skipped"] or not np.isfinite(m["loss"]))
    times = [m["step_time_s"] for m in done]
    harness.find(f"window {window_s:.3f} s, {steps} steps, step median "
                 f"{statistics.median(times):.4f} s, min {min(times):.4f}, "
                 f"max {max(times):.4f}, skipped or non-finite {failed}")
    harness.find(f"compiles inside the window: {compiles.n}")
    harness.find(slow_steps(done, walls, pauses))
    peak = harness.memory_peak_bytes(devs)
    harness.find(f"memory peak {peak} bytes")

    matmul_ops = set()
    if trace:
        # the compiled step's XLA matrix products, by instruction name
        # (the persistent cache hands back the executable the window ran)
        hlo = trainer.train_step.lower(
            trainer.state, trainer.data.global_batch_at_step(0)
        ).compile().as_text()
        matmul_ops = trace_mod.matmul_instructions(hlo)

    # correctness: the reference follows the first three steps
    data = trainer.data
    del trainer
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference_steps(conf, workload, shapes, seed, data, g_head)
    del g_head
    harness.find(f"reference {time.perf_counter() - t_ref:.2f} s, losses "
                 + ", ".join(f"{x:.6f}" for x in ref[0]))
    got = compare(first, g_prog, change_prog, ref)
    harness.find(f"loss gap {got['loss_rel_gap']!r} (relative, worst of "
                 f"the three steps; not compared), head gradient "
                 f"{got['head_grad_rel_diff']!r}")
    harness.find(f"worst grad leaf {got['worst_grad_leaf']}, worst change "
                 f"leaf {got['worst_change_leaf']}; left out of the change: "
                 f"{got['left_out']}")
    lim = workload["limits"]
    names = tuple(lim)
    checks = [("setup_steps_sound", int(got["sound"]), 1)] + [
        (n, got[n], lim[n]) for n in names]
    correct = got["sound"] and failed == 0 and all(
        got[n] <= lim[n] for n in names)

    out = {"correct": bool(correct), "attempted": steps, "failed": failed,
           "memory_peak_bytes": peak, "checks": checks,
           "end_to_end": {
               "train_tokens_per_s": {"value": steps * tokens_per_step
                                      / window_s, "unit": "tokens/s"},
               "setup_s": {"value": setup_s, "unit": "s"}}}
    if trace:
        red = trace_mod.reduce(harness.xplane_file(tw.dir), chips=len(devs))
        out.update(busy_s=red.busy_s, window_s=red.window_s,
                   breakdown=red.breakdown())
        out["ctx"] = {"trace": red, "conf": conf, "workload": workload,
                      "peaks": harness.peaks(devs[0].device_kind),
                      "chips": len(devs), "steps": traced_steps,
                      "matmul_ops": matmul_ops,
                      "tokens": traced_steps * tokens_per_step}
    return out
