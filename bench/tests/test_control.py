"""Readings that the limits of ``correct`` are set from: the sound
program, the lower-precision control put in its place, and the planted
faults, seed by seed.

The control is the program's own next path down from the precision each
configuration states: its ``mxfp4`` policy (4-bit E2M1 elements under
E8M0 group scales) in place of ``hfp8`` (per-tensor FP8) for training and
of ``mxfp8`` for serving.  For serving it does not decode: at each
position of the sampled prompts and served tokens, the reference reads the
gap of the token that the control puts first.  The training fault read
here is half of the batch left out, the mean taken over the rest (a
batch of one sequence leaves out half of its positions); a state left
unchanged reads 1 by construction and needs no run.

The variant ``control_run`` is a whole run of the cell (the mode's
``run``, window and check included) with the control in the program's
place; the harness has to read it as not correct.

Under pytest this runs on the CPU at a tiny size and checks that the
control and the fault read above the sound program.  On the chip, at the
cell's own size, in one process:

    python3 bench/tests/test_control.py --cell train-stablelm-l6 \\
        --seeds 11,12,13 --variants sound,control,half_batch,control_run \\
        --seconds 5 --out <dir>/train_readings.json
    python3 bench/tests/test_control.py --cell serve-deepseek-l8-chat \\
        --seeds 21,22,23 --variants sound,control,control_run --seconds 51 \\
        --out <dir>/serve_readings.json

(Serving readings take the cell's own window: a shorter one finishes
fewer requests than a run compares.)
"""
from __future__ import annotations

import argparse
import copy
import gc
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
for _p in (BENCH, BENCH.parent / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import harness  # noqa: E402

CONTROL_POLICY = "mxfp4"


def with_policy(conf: dict, policy: str) -> dict:
    conf = copy.deepcopy(conf)
    conf["program"]["policy"] = policy
    return conf


def half_batch(make):
    """Left out: half of the batch's sequences, or half of the positions
    of a one-sequence batch; the loss is the mean over the rest."""
    def make_broken(model, opt, **kw):
        step = make(model, opt, **kw)

        def broken(state, tokens, aux=None):
            b, s = tokens.shape
            kept = tokens[: b // 2] if b > 1 else tokens[:, : s // 2]
            return step(state, kept)
        return broken
    return make_broken


def train_reading(mode, wl, conf, seed: int, variant: str) -> dict:
    spans = harness.Spans()
    prog_conf = with_policy(conf, CONTROL_POLICY) if variant == "control" \
        else conf
    real = mode.make_train_step
    if variant == "half_batch":
        mode.make_train_step = half_batch(real)
    try:
        trainer, shapes = mode.build(prog_conf, wl, seed, spans)
        first, g, ch, g_head = mode.first_steps(
            trainer, shapes, seed, wl["optimizer"]["b1"], spans)
    finally:
        mode.make_train_step = real
    data = trainer.data
    del trainer
    gc.collect()
    ref = mode.reference_steps(conf, wl, shapes, seed, data, g_head)
    got = mode.compare(first, g, ch, ref)
    got["losses"] = [m["loss"] for m in first]
    got["ref_losses"] = ref[0]
    return got


def control_run(mode, wl, conf, seed: int, seconds: float) -> dict:
    """A whole run of the cell with the control in the program's place:
    ``correct`` and each compared number as the harness read them."""
    out = mode.run(workload=wl, conf=with_policy(conf, CONTROL_POLICY),
                   seed=seed, seconds=seconds, trace=False,
                   t_start=time.perf_counter())
    got = {n: v for n, v, _ in out["checks"]}
    got["correct"] = out["correct"]
    got["over_limit"] = [n for n, v, lim in out["checks"]
                         if n in wl["limits"] and v > lim]
    del out
    gc.collect()
    return got


def serve_control_gaps(conf, sample, seed: int) -> list:
    """Per sampled request: (widest served gap, widest control gap)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import reference
    from repro.models import build_model
    model = build_model(harness.model_config(with_policy(conf,
                                                         CONTROL_POLICY)))
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    params = harness.make_weights(shapes, seed)
    fwd = jax.jit(lambda p, t: model.apply(p, t)[0])
    picks = []
    for prompt, out in sample:
        seq = np.concatenate([prompt, out[:-1]])
        pad = -(-len(seq) // 512) * 512
        toks = np.zeros((1, pad), np.int32)
        toks[0, : len(seq)] = seq
        lg = fwd(params, jnp.asarray(toks))[0, len(prompt) - 1: len(seq)]
        picks.append(np.asarray(jnp.argmax(lg, -1)))
    del params
    gc.collect()
    ref_params = harness.make_weights(shapes, seed)
    res = []
    for (prompt, out), pick in zip(sample, picks):
        seq = np.concatenate([prompt, out[:-1]])
        pos = np.arange(len(prompt) - 1, len(seq))
        g_served, g_ctrl = reference.serve_logit_gaps(ref_params, conf, seq,
                                                      pos, out, pick)
        res.append((float(g_served.max()), float(g_ctrl.max())))
    return res


def serve_reading(mode, wl, conf, seed: int, seconds: float,
                  variants) -> dict:
    out = mode.run(workload=wl, conf=conf, seed=seed, seconds=seconds,
                   trace=False, t_start=time.perf_counter())
    got = {n: v for n, v, _ in out["checks"]}
    got["correct"] = out["correct"]
    got["end_to_end"] = {k: v["value"] for k, v in out["end_to_end"].items()}
    if "control" in variants:
        pairs = serve_control_gaps(conf, out["sample"], seed)
        got["control_logit_gap"] = max(c for _, c in pairs)
        got["served_logit_gap_again"] = max(s for s, _ in pairs)
    return got


def readings(cell: str, seeds, variants, *, seconds: float = 51.0,
             workload=None, conf=None) -> dict:
    if workload is None:
        workload, conf = harness.load_cell(cell)
    wl = workload
    mode = harness.load_module(f"modes/{wl['mode']}.py",
                               f"control_mode_{wl['mode']}")
    res = {}
    for seed in seeds:
        if "control_run" in variants:
            res[f"control_run/{seed}"] = control_run(mode, wl, conf, seed,
                                                     seconds)
            harness.find(f"control_run seed {seed}: "
                         f"{res[f'control_run/{seed}']}")
        if wl["mode"] == "train":
            for v in variants:
                if v == "control_run":
                    continue
                res[f"{v}/{seed}"] = train_reading(mode, wl, conf, seed, v)
                harness.find(f"{v} seed {seed}: {res[f'{v}/{seed}']}")
        elif set(variants) - {"control_run"}:
            res[f"serve/{seed}"] = serve_reading(mode, wl, conf, seed,
                                                 seconds, variants)
            harness.find(f"serve seed {seed}: {res[f'serve/{seed}']}")
    return res


# ----------------------------------------------------------- CPU test --

def test_control_and_fault_read_above_the_sound_program():
    import test_faults as tf
    r = readings("train-stablelm-l6", [5],
                 ["sound", "control", "half_batch"],
                 workload=tf.train_cell(), conf=tf.TRAIN_CONF)
    sound, ctrl, half = (r[f"{v}/5"] for v in ("sound", "control",
                                               "half_batch"))
    assert ctrl["loss_rel_gap"] > sound["loss_rel_gap"]
    assert half["grad_norm_gap"] > sound["grad_norm_gap"]
    s = readings("serve-deepseek-l8-chat", [5], ["sound", "control"],
                 seconds=1.0, workload=tf.serve_cell(), conf=tf.SERVE_CONF)
    got = s["serve/5"]
    assert got["control_logit_gap"] > got["served_logit_gap"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="sound,control")
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--out", required=True)
    a = ap.parse_args(argv)
    harness.use_program()
    harness.require_chips(1)
    harness.enable_cache()
    res = readings(a.cell, [int(s) for s in a.seeds.split(",")],
                   a.variants.split(","), seconds=a.seconds)
    pathlib.Path(a.out).parent.mkdir(parents=True, exist_ok=True)
    pathlib.Path(a.out).write_text(json.dumps(res, indent=1, default=str))
    print(json.dumps(res, default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())
