"""Accuracy study: (a) Table IV reproduction; (b) HFP8 vs BF16 vs FP32
end-to-end training-loss curves on the same tiny LM — the paper's premise
("low-precision training works when you accumulate wide") verified through
the whole framework stack.

    PYTHONPATH=src python examples/accuracy_study.py [--steps 40]
"""
import argparse
import dataclasses

import jax
import numpy as np

from benchmarks import table4_accuracy
from repro.configs import ARCHS
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.optim.adamw import AdamWConfig
from repro.train.train_step import make_train_state, make_train_step


def train_curve(policy: str, steps: int):
    cfg = dataclasses.replace(ARCHS["stablelm-1.6b"].reduced(),
                              vocab_size=128, policy_name=policy)
    model = build_model(cfg)
    opt = AdamWConfig(lr=2e-3, warmup_steps=5, schedule="constant")
    state = make_train_state(model, jax.random.key(0), opt)
    step = jax.jit(make_train_step(model, opt, impl="xla"))
    rng = np.random.default_rng(0)
    toks = np.zeros((8, 33), np.int32)
    toks[:, 0] = rng.integers(0, 128, 8)
    for i in range(32):
        toks[:, i + 1] = (toks[:, i] * 3 + 7) % 128
    losses = []
    for _ in range(steps):
        state, m = step(state, toks)
        losses.append(float(m["loss"]))
    return losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=40)
    args = ap.parse_args()
    enable_compile_cache()

    print("== Table IV reproduction (relative error vs FP64 golden) ==")
    table4_accuracy.main(trials=15)

    print("\n== end-to-end: same model under different policies ==")
    print("policy,loss_step0,loss_final")
    finals = {}
    for pol in ("fp32", "bf16", "hfp8"):
        ls = train_curve(pol, args.steps)
        finals[pol] = ls[-1]
        print(f"{pol},{ls[0]:.4f},{ls[-1]:.4f}")
    gap = finals["hfp8"] - finals["fp32"]
    print(f"hfp8-vs-fp32 final-loss gap: {gap:+.4f} "
          f"({'OK: low-precision training tracks fp32' if gap < 0.5 else 'DEGRADED'})")


if __name__ == "__main__":
    main()
