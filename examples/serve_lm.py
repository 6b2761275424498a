"""Serving example: continuous batching over the packed paged KV cache.

Simulates a decode server: a queue of variable-length prompts flows
through ``serve.scheduler.ContinuousBatcher`` — block prefill into
freshly allocated pages, lockstep decode, mid-flight admission into
slots freed by finished sequences.  Under an MX ``--policy`` the cache
pages hold packed codec payloads (DESIGN.md §12); the footprint line
shows the HBM bytes each sequence pins vs bf16 pages.

    PYTHONPATH=src python examples/serve_lm.py --batch 4 --new-tokens 16 \
        --policy mxfp8
"""
import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs import ARCHS
from repro.configs.base import ModelConfig
from repro.core.policy import POLICIES
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.hlo_analysis import format_serve_cache_footprint
from repro.models import build_model
from repro.serve.scheduler import ContinuousBatcher, ServeRequest


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--policy", default="mxfp8", choices=sorted(POLICIES))
    ap.add_argument("--batch", type=int, default=4,
                    help="decode slots (requests = 2x batch, so admission "
                         "into freed slots is exercised)")
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--page-size", type=int, default=16)
    args = ap.parse_args()
    enable_compile_cache()

    # CPU-sized variant of the real arch; head_dim widened to a whole
    # scale group so the MX policies serve *packed* pages (reduced()
    # keeps hd=16, which would fall back to carrier pages)
    cfg = dataclasses.replace(ARCHS[args.arch].reduced(),
                              head_dim=32, policy_name=args.policy)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    print(f"[serve_lm] arch={cfg.name} policy={args.policy} "
          f"slots={args.batch}")
    print(format_serve_cache_footprint(cfg, args.policy, args.max_len,
                                       page_size=args.page_size))

    rng = np.random.default_rng(0)
    reqs = [ServeRequest(i, rng.integers(0, cfg.vocab_size,
                                         rng.integers(4, args.prompt_len + 1)),
                         args.new_tokens)
            for i in range(2 * args.batch)]
    cb = ContinuousBatcher(model, params, max_batch=args.batch,
                           max_len=args.max_len, page_size=args.page_size)
    t0 = time.perf_counter()
    out = cb.run(reqs)
    dt = time.perf_counter() - t0
    n_tok = sum(len(v) for v in out.values())
    print(f"  {len(reqs)} requests, {n_tok} tokens in {dt*1e3:.0f} ms "
          f"({n_tok/dt:.1f} tok/s incl. compile)")
    print(f"  sample continuation[0]: {out[0][:10]}")


if __name__ == "__main__":
    main()
