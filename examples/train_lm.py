"""End-to-end training driver: a ~100M-param LM under the HFP8 recipe with
checkpointing, loss-scale tracking, straggler watch and resume.

    PYTHONPATH=src python examples/train_lm.py --preset tiny --steps 30
    PYTHONPATH=src python examples/train_lm.py --preset 100m --steps 300

The 100m preset is the deliverable configuration (~100M params, a few
hundred steps); tiny is a CPU-minute smoke of the same path. Both resume
from ckpt_dir automatically (kill it mid-run and rerun to see).
"""
import argparse
import dataclasses

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.policy import POLICIES
from repro.data.pipeline import DataConfig, SyntheticTokens
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.hlo_analysis import format_packed_footprint
from repro.models import build_model
from repro.optim.adamw import AdamWConfig
from repro.train.train_step import make_train_state, make_train_step
from repro.train.trainer import Trainer

PRESETS = {
    "tiny": dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
                 d_ff=512, vocab_size=2048, seq=64, batch=8),
    "30m": dict(n_layers=6, d_model=384, n_heads=6, n_kv_heads=6,
                d_ff=1536, vocab_size=32768, seq=256, batch=8),
    "100m": dict(n_layers=12, d_model=640, n_heads=10, n_kv_heads=10,
                 d_ff=2560, vocab_size=50304, seq=512, batch=8),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="tiny", choices=sorted(PRESETS))
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--policy", default="hfp8", choices=sorted(POLICIES))
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--save-every", type=int, default=25)
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()
    enable_compile_cache()

    p = PRESETS[args.preset]
    cfg = ModelConfig(
        name=f"lm-{args.preset}", family="dense",
        n_layers=p["n_layers"], d_model=p["d_model"], n_heads=p["n_heads"],
        n_kv_heads=p["n_kv_heads"], d_ff=p["d_ff"],
        vocab_size=p["vocab_size"], head_dim=p["d_model"] // p["n_heads"],
        policy_name=args.policy, attn_q_chunk=p["seq"])
    model = build_model(cfg)
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(
        jax.eval_shape(model.init, jax.random.key(0))))
    print(f"[train_lm] {cfg.name}: {n_params/1e6:.1f}M params, "
          f"policy={args.policy}")
    # what the packed payload pipeline (DESIGN.md §10) buys per operand
    print(format_packed_footprint(args.policy))

    opt = AdamWConfig(lr=3e-4, warmup_steps=20, total_steps=max(args.steps, 100))
    state = make_train_state(model, jax.random.key(0), opt)
    step = make_train_step(model, opt, microbatches=args.microbatches,
                           impl="xla")
    data = SyntheticTokens(DataConfig(cfg.vocab_size, p["seq"], p["batch"]))
    trainer = Trainer(model, step, state, data, ckpt_dir=args.ckpt_dir,
                      save_every=args.save_every)
    if trainer.start_step:
        print(f"[train_lm] resumed from step {trainer.start_step}")
    log = trainer.run(args.steps)
    for m in log[:: max(len(log) // 10, 1)]:
        print(f"  step {m['step']:5d}  loss {m['loss']:.4f}  "
              f"gnorm {m['grad_norm']:.2f}  {m['step_time_s']*1e3:.0f} ms")
    print(f"[train_lm] done. stragglers observed: {trainer.straggler_count}")


if __name__ == "__main__":
    main()
