import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"   # placeholder devices, never the chip

"""Dry-run profiler: compile one cell and attribute collective bytes, dot
FLOPs and large buffers to source ops — the measurement half of the
hypothesis -> change -> measure loop (EXPERIMENTS.md §Perf).

    PYTHONPATH=src python -m repro.launch.profile_cell --arch deepseek-7b \
        --shape train_4k [--multi-pod] [--depth 5]
"""
import argparse

import jax

from ..compat import set_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--depth", type=int, default=5)
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()

    from ..configs import get_arch
    from ..configs.base import SHAPES
    from ..launch.mesh import make_production_mesh
    from ..launch.specs import build_cell, shardings_for
    from ..launch import hlo_analysis as H
    from ..optim.adamw import AdamWConfig

    cfg = get_arch(args.arch)
    shape = SHAPES[args.shape]
    mesh = make_production_mesh(multi_pod=args.multi_pod)
    fn, cell_args, in_specs, donate, model, rules = build_cell(
        cfg, shape, mesh, opt_cfg=AdamWConfig(),
        microbatches=args.microbatches)
    with set_mesh(mesh):
        compiled = jax.jit(fn, in_shardings=shardings_for(in_specs, mesh),
                           donate_argnums=donate).lower(*cell_args).compile()
    hlo = compiled.as_text()
    res = H.analyze(hlo)
    att = H.attribute(hlo, depth=args.depth, top=args.top)
    ma = compiled.memory_analysis()
    print(f"== {cfg.name} {shape.name} "
          f"{'pod2x16x16' if args.multi_pod else 'pod16x16'} ==")
    print(f"flops/dev {res['flops']:.3e}  bytes/dev {res['bytes']:.3e}  "
          f"coll/dev {res['coll_total']:.3e}")
    print(f"temp {ma.temp_size_in_bytes/2**30:.1f} GiB  "
          f"args {ma.argument_size_in_bytes/2**30:.1f} GiB")
    print("\n-- top collectives (bytes/device) --")
    for k, v in att["collectives"]:
        print(f"{v:12.3e}  {k}")
    print("\n-- top dot flops --")
    for k, v in att["dot_flops"]:
        print(f"{v:12.3e}  {k}")
    print("\n-- top buffers (bytes x executions) --")
    for k, v in att["buffers"]:
        print(f"{v:12.3e}  {k}")


if __name__ == "__main__":
    main()
