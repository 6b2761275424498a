"""Production training launcher.

On a real multi-host TPU fleet this binary runs once per host
(jax.distributed.initialize is called when JAX_COORDINATOR is set); on
this container it runs the same code path on whatever devices exist.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b \
        --reduced --steps 20 --batch 8 --seq 64 --ckpt /tmp/ck

Flags mirror the dry-run cells: the same (arch x shape) configs that
compile at 512 chips run here at reduced scale; the mesh adapts to the
device count (elastic).
"""
import argparse
import dataclasses
import os

import jax
import numpy as np

from ..compat import make_mesh
from ..configs import get_arch
from ..data.pipeline import DataConfig, SyntheticTokens
from ..models import build_model
from ..optim.adamw import AdamWConfig
from ..parallel.sharding import make_rules, param_pspecs
from ..train.train_step import make_train_state, make_train_step
from ..train.trainer import Trainer
from .compile_cache import enable_compile_cache


def auto_mesh():
    """Build the largest (data, model) mesh the devices support."""
    n = len(jax.devices())
    if n == 1:
        return None
    model = 1
    for m in (16, 8, 4, 2):
        if n % m == 0:
            model = m
            break
    return make_mesh((n // model, model), ("data", "model"))


def build_trainer(cfg, *, steps: int, batch: int, seq: int, ckpt: str,
                  save_every: int, microbatches: int = 1, mesh=None):
    """Model, sharded train state, jitted step, data and ``Trainer`` for
    ``cfg`` — the whole launcher short of running it (``chip_smoke.py``
    builds its trainer phases through here too)."""
    model = build_model(cfg)
    rules = make_rules(mesh) if mesh else None

    opt_cfg = AdamWConfig(total_steps=max(steps, 100))
    state = make_train_state(model, jax.random.key(0), opt_cfg)
    if mesh is not None:
        from jax.sharding import NamedSharding
        pspecs = param_pspecs(jax.eval_shape(lambda: state["params"]), mesh)
        shard = jax.tree.map(
            lambda s: NamedSharding(mesh, s), pspecs,
            is_leaf=lambda x: hasattr(x, "_normalized_spec") or
            type(x).__name__ == "PartitionSpec")
        # the optimizer's master/moments mirror the params tree and take
        # the same shardings (ZeRO): a full f32 copy left on device 0
        # would be resharded by the first step, next to its own shards
        opt = state["opt"]
        for tree, k in ((state, "params"), (opt, "master"), (opt, "m"),
                        (opt, "v")):
            tree[k] = jax.tree.map(jax.device_put, tree[k], shard)
    step = make_train_step(model, opt_cfg, rules=rules,
                           microbatches=microbatches, impl="auto")
    data = SyntheticTokens(DataConfig(cfg.vocab_size, seq, batch))
    return Trainer(model, step, state, data, ckpt_dir=ckpt,
                   save_every=save_every)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized variant of the arch")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to N layers (widths unchanged)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt", default="/tmp/repro_launch_train")
    ap.add_argument("--save-every", type=int, default=10)
    ap.add_argument("--policy", default=None)
    args = ap.parse_args()

    if os.environ.get("JAX_COORDINATOR"):
        jax.distributed.initialize()  # multi-host fleet entry
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.layers:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    if args.policy:
        cfg = dataclasses.replace(cfg, policy_name=args.policy)
    dev = jax.devices()[0]
    print(f"[launch.train] device: {dev.platform} {dev.device_kind} "
          f"x{len(jax.devices())}")
    trainer = build_trainer(cfg, steps=args.steps, batch=args.batch,
                            seq=args.seq, ckpt=args.ckpt,
                            save_every=args.save_every,
                            microbatches=args.microbatches, mesh=auto_mesh())
    if trainer.start_step:
        print(f"[launch.train] resumed at step {trainer.start_step}")
    log = trainer.run(args.steps)
    print(f"[launch.train] {cfg.name}: "
          f"loss {log[0]['loss']:.4f} -> {log[-1]['loss']:.4f}, "
          f"{len(log)} steps, stragglers={trainer.straggler_count}")


if __name__ == "__main__":
    main()
