import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# The lines above MUST precede every other import: jax locks the device
# count at first init, and the dry-run needs 512 placeholder host devices
# to build the production meshes.  The CPU pin keeps this process and the
# per-cell children it spawns (which inherit it) off any attached chip.
# (Only this entry point does this — tests and benches see the real
# single CPU device.)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves, without hardware:
  * the sharding config is coherent (GSPMD partitions cleanly at 256/512
    chips — sharding mismatches, unsupported collectives and compile-time
    OOMs all fail here);
  * the memory footprint fits (memory_analysis, bytes per device);
  * the roofline inputs (cost_analysis FLOPs/bytes + HLO collective bytes)
    — consumed by benchmarks/roofline.py and EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch deepseek-7b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all            # every applicable cell
  python -m repro.launch.dryrun --all --jobs 4   # subprocess per cell
"""
import argparse
import json
import re
import subprocess
import sys
import time

import jax

from ..compat import set_mesh

HLO_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "f8e4m3fn": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2,
    "u16": 2, "s8": 1, "u8": 1, "pred": 1,
}

COLLECTIVE_RE = re.compile(
    r"=\s+(\(?[a-z0-9\[\],{}: ]+?\)?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
SHAPE_RE = re.compile(r"([a-z]\d?[a-z0-9]*)\[([\d,]*)\]")


def _shape_bytes(txt: str) -> int:
    total = 0
    for dt, dims in SHAPE_RE.findall(txt):
        if dt not in HLO_DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * HLO_DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-device bytes by collective type (result-shape convention;
    all-reduce counted x2 for its reduce-scatter + all-gather phases)."""
    out = {k: 0 for k in ("all-reduce", "all-gather", "reduce-scatter",
                          "all-to-all", "collective-permute")}
    counts = dict.fromkeys(out, 0)
    for line in hlo_text.splitlines():
        m = COLLECTIVE_RE.search(line)
        if not m:
            continue
        kind = m.group(2)
        b = _shape_bytes(m.group(1))
        out[kind] += b * (2 if kind == "all-reduce" else 1)
        counts[kind] += 1
    return {"bytes": out, "counts": counts,
            "total_bytes": sum(out.values())}


def model_flops_estimate(cfg, shape, params_shapes) -> dict:
    """MODEL_FLOPS = 6*N*D (train) / 2*N*D (inference), N = active params
    excluding the embedding table lookup; + causal-attention term."""
    import numpy as np

    def leaves_with_paths(tree):
        return jax.tree_util.tree_flatten_with_path(tree)[0]

    total = active = embed = 0
    for path, leaf in leaves_with_paths(params_shapes):
        pstr = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        n = int(np.prod(leaf.shape))
        total += n
        if "embed" in pstr and "lm_head" not in pstr:
            embed += n
        if "experts" in pstr and cfg.n_experts:
            active += n * cfg.top_k / cfg.n_experts
        else:
            active += n
    n_active = active - embed
    if cfg.tie_embeddings:
        # tied head: the embedding matrix IS the logits GEMM weight
        n_active += cfg.vocab_size * cfg.d_model
    toks = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                 else 1)
    mult = 6 if shape.kind == "train" else 2
    flops = mult * n_active * toks
    # causal attention: 2 matmuls * 2 flops * (S^2/2) * d_attn * H * L * B
    if cfg.family not in ("xlstm",):
        s_ctx = shape.seq_len
        s_q = shape.seq_len if shape.kind != "decode" else 1
        att = (2 * 2 * 0.5 * s_q * s_ctx * cfg.head_dim_eff * cfg.n_heads
               * cfg.n_layers * shape.global_batch)
        if cfg.family == "hybrid":
            att *= (cfg.n_layers // max(cfg.attn_every, 1)) / cfg.n_layers
        flops += att * (3 if shape.kind == "train" else 1)
    return {"params_total": int(total), "params_active_nonembed":
            int(n_active), "model_flops_global": float(flops)}


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             *, seq_shard=None, microbatches=1, opt_overrides=None) -> dict:
    from ..configs import get_arch
    from ..configs.base import SHAPES
    from ..launch.mesh import make_production_mesh
    from ..launch.specs import build_cell, cell_is_applicable, shardings_for
    from ..optim.adamw import AdamWConfig

    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    ok, why = cell_is_applicable(cfg, shape)
    rec = {"arch": cfg.name, "shape": shape.name, "mesh": mesh_name,
           "kind": shape.kind}
    if not ok:
        rec.update(status="skipped", reason=why)
        _write(rec, out_dir)
        return rec

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    if shape.kind == "train" and microbatches == 1:
        # optimized default (§Perf D7): 4-way gradient accumulation keeps
        # activation temp inside HBM at identical wire bytes
        microbatches = 4
    if opt_overrides is None and cfg.name == "arctic-480b":
        # 480B params cannot carry f32 optimizer state at 256-512 chips
        # (DESIGN.md §7): fp16 master + bf16 moments, f32 update arithmetic
        import jax.numpy as _jnp
        opt_overrides = {"master_dtype": _jnp.float16,
                         "moment_dtype": _jnp.bfloat16}
    opt_cfg = AdamWConfig(**(opt_overrides or {}))
    fn, args, in_specs, donate, model, rules = build_cell(
        cfg, shape, mesh, opt_cfg=opt_cfg, seq_shard=seq_shard,
        microbatches=microbatches)
    in_shardings = shardings_for(in_specs, mesh)
    with set_mesh(mesh):
        lowered = jax.jit(fn, in_shardings=in_shardings,
                          donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    ma = compiled.memory_analysis()
    print(ma)                           # proves it fits (bytes per device)
    ca = compiled.cost_analysis()
    if isinstance(ca, list):  # older jax wraps the dict in a list
        ca = ca[0] if ca else {}
    print({k: ca.get(k) for k in ("flops", "bytes accessed")})
    hlo = compiled.as_text()

    # trip-count-weighted analysis: XLA's cost_analysis counts scan bodies
    # once; hlo_analysis weights every computation by its execution count.
    from .hlo_analysis import analyze
    h = analyze(hlo)

    params_shapes = jax.eval_shape(model.init, jax.random.key(0))
    rec.update(
        status="ok",
        n_devices=mesh.devices.size,
        lower_s=round(t_lower, 2),
        compile_s=round(t_compile, 2),
        flops_per_device=h["flops"],
        bytes_per_device=h["bytes"],
        collectives={"bytes": h["coll_bytes"],
                     "counts": h["coll_counts"],
                     "total_bytes": h["coll_total"]},
        raw_scan_once={"flops": float(ca.get("flops", 0.0)),
                       "bytes": float(ca.get("bytes accessed", 0.0))},
        memory=dict(
            argument_bytes=ma.argument_size_in_bytes,
            output_bytes=ma.output_size_in_bytes,
            temp_bytes=ma.temp_size_in_bytes,
            alias_bytes=ma.alias_size_in_bytes,
            code_bytes=ma.generated_code_size_in_bytes,
        ) if ma is not None else None,
        hlo_chars=len(hlo),
        **model_flops_estimate(cfg, shape, params_shapes),
    )
    _write(rec, out_dir)
    return rec


def _write(rec: dict, out_dir: str):
    os.makedirs(out_dir, exist_ok=True)
    name = f"{rec['arch']}_{rec['shape']}_{rec['mesh']}.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(rec, f, indent=1)
    print(f"[dryrun] {name}: {rec['status']}"
          + (f" ({rec.get('compile_s', '?')}s compile)"
             if rec["status"] == "ok" else f" — {rec.get('reason','')}"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=1)
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()

    if not args.all:
        run_cell(args.arch, args.shape, args.multi_pod, args.out,
                 microbatches=args.microbatches)
        return

    from ..configs import ARCHS
    from ..configs.base import SHAPES
    cells = [(a, s, mp) for a in sorted(ARCHS) for s in SHAPES
             for mp in (False, True)]
    procs = []
    for a, s, mp in cells:
        done = os.path.join(
            args.out, f"{a}_{s}_{'pod2x16x16' if mp else 'pod16x16'}.json")
        if os.path.exists(done):
            print(f"[dryrun] skip existing {done}")
            continue
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", a,
               "--shape", s, "--out", args.out]
        if mp:
            cmd.append("--multi-pod")
        if args.jobs == 1:
            subprocess.run(cmd, check=False)
        else:
            procs.append(subprocess.Popen(cmd))
            while len([p for p in procs if p.poll() is None]) >= args.jobs:
                time.sleep(2)
    for p in procs:
        p.wait()


if __name__ == "__main__":
    main()
