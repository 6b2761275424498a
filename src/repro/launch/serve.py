"""Production serving launcher: block prefill + batched decode over the
paged KV cache (DESIGN.md §12).

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b \
        --reduced --batch 4 --new-tokens 8 --policy mxfp8

Under an MX ``--policy`` (and a group-aligned head dim) the cache pages
hold packed codec payloads + E8M0 scales and decode runs the packed
kernel; otherwise carrier pages (or, for the recurrent families, their
native state caches).  The cache footprint line shows what the packed
pool pins in HBM per sequence vs bf16.
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_arch
from ..core.policy import POLICIES
from ..models import build_model
from ..serve.decode import generate
from .compile_cache import enable_compile_cache
from .hlo_analysis import format_serve_cache_footprint


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--policy", default=None, choices=sorted(POLICIES),
                    help="override the arch's training policy for serving")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=16)
    args = ap.parse_args()
    enable_compile_cache()
    dev = jax.devices()[0]
    print(f"[launch.serve] device: {dev.platform} {dev.device_kind} "
          f"x{len(jax.devices())}")

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.policy:
        cfg = dataclasses.replace(cfg, policy_name=args.policy)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    if getattr(model, "block_decode", False):
        print(format_serve_cache_footprint(cfg, cfg.policy_name,
                                           args.max_len,
                                           page_size=args.page_size))

    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                      (args.batch, args.prompt_len)))
    aux = None
    if cfg.family == "encdec":
        aux = {"frames": jnp.asarray(
            rng.normal(0, 1, (args.batch, cfg.enc_seq, cfg.d_model)),
            jnp.bfloat16)}
    t0 = time.perf_counter()
    out = generate(model, params, prompt, max_new_tokens=args.new_tokens,
                   max_len=args.max_len, aux=aux, page_size=args.page_size)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    print(f"[launch.serve] {cfg.name} policy={cfg.policy_name}: "
          f"{args.batch}x{args.new_tokens} tokens in {dt*1e3:.0f} ms "
          f"({args.batch*args.new_tokens/dt:.1f} tok/s incl. compile)")


if __name__ == "__main__":
    main()
