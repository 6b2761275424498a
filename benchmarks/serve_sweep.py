"""Serving sweep: KV-cache HBM bytes per sequence across cache
policies (DESIGN.md §12).

For each serving policy (``bf16`` carrier pages, ``mxfp8``/``mxfp6``/
``mxfp4`` packed payload + E8M0 pages) the paged cache is built for a
small dense config; reported per policy:

* ``cache_bytes_per_seq`` — the HBM bytes one sequence's page-pool
  share pins across the layer stack (trash page excluded), measured
  from the actual cache arrays AND cross-checked against the analytic
  ``serve.kv_cache.paged_kv_bytes_per_seq`` — they must agree exactly;
* ``ratios`` — packed-vs-bf16 cache compression.  ``mxfp4`` must hold
  >= 2.5x (the paper-level win the packed pipeline promises; the
  layout arithmetic gives 2.0 / 0.53125 ≈ 3.76x).

This doubles as CI's serving regression gate: ``--check BASELINE``
fails (exit 1) if any policy's cache bytes/sequence grow >10% over the
committed baseline (``benchmarks/baselines/serve.json``) or the mxfp4
compression ratio drops below 2.5x — mirroring the wire-bytes gate.

Run:
    PYTHONPATH=src python -m benchmarks.serve_sweep
        [--out BENCH_serve.json] [--check benchmarks/baselines/serve.json]
"""
from __future__ import annotations

import json
import sys

POLICIES = ("bf16", "mxfp8", "mxfp6", "mxfp4")
MIN_MXFP4_RATIO = 2.5


def _cfg(policy):
    from repro.configs.base import ModelConfig
    return ModelConfig(name=f"serve-bench-{policy}", family="dense",
                       n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                       d_ff=128, vocab_size=128, head_dim=32,
                       policy_name=policy, attn_q_chunk=8)


def _pool_bytes_per_seq(cache, mp):
    """Measured pool bytes backing one sequence: per-page bytes of every
    kv leaf (leaves are [L, P, page, KV, W]; nbytes/P is one page across
    the layer stack) times the sequence's max_pages."""
    import jax
    total = 0
    for leaf in jax.tree_util.tree_leaves(cache["kv"]):
        total += leaf.nbytes // leaf.shape[1] * mp
    return total


def measure():
    from repro.models import build_model
    from repro.serve.kv_cache import (max_pages, paged_kv_applicable,
                                      paged_kv_bytes_per_seq)

    max_len, page_size = 64, 16
    mp = max_pages(max_len, page_size)
    report = {"shape": {"max_len": max_len, "page_size": page_size,
                        "config": "dense L=2 d=64 H=4 KV=2 hd=32"},
              "policies": {}}
    for pname in POLICIES:
        cfg = _cfg(pname)
        model = build_model(cfg)
        from repro.core.policy import get_policy
        pol = get_policy(pname)
        cache = model.init_cache(2, max_len, paged=True,
                                 page_size=page_size)
        measured = _pool_bytes_per_seq(cache, mp)
        analytic = paged_kv_bytes_per_seq(cfg, pol, max_len,
                                          page_size=page_size)
        assert measured == analytic, (pname, measured, analytic)
        report["policies"][pname] = {
            "packed": paged_kv_applicable(cfg, pol),
            "cache_format": pol.mx_kv_cache_name or "carrier-bf16",
            "cache_bytes_per_seq": measured}
    base = report["policies"]["bf16"]["cache_bytes_per_seq"]
    report["ratios"] = {
        f"{p}_vs_bf16": round(
            base / report["policies"][p]["cache_bytes_per_seq"], 4)
        for p in POLICIES if p != "bf16"}
    return report


def check(report, baseline_path, tol=1.10):
    """>10% cache-byte regression or a <2.5x mxfp4 ratio fails."""
    with open(baseline_path) as f:
        base = json.load(f)
    failed = []
    for pname, rec in report["policies"].items():
        b = base.get("policies", {}).get(pname)
        if b is None:
            continue
        ratio = rec["cache_bytes_per_seq"] / max(
            b["cache_bytes_per_seq"], 1)
        status = "OK" if ratio <= tol else "REGRESSED"
        print(f"serve-cache {pname}: {rec['cache_bytes_per_seq']} B/seq vs "
              f"baseline {b['cache_bytes_per_seq']} ({ratio:.3f}x) {status}")
        if ratio > tol:
            failed.append(pname)
    r4 = report["ratios"]["mxfp4_vs_bf16"]
    status = "OK" if r4 >= MIN_MXFP4_RATIO else "REGRESSED"
    print(f"serve-cache mxfp4 compression: {r4:.2f}x vs bf16 "
          f"(floor {MIN_MXFP4_RATIO}x) {status}")
    if r4 < MIN_MXFP4_RATIO:
        failed.append("mxfp4_ratio")
    return failed


def main():
    args = sys.argv[1:]

    def opt(name, default=None):
        if name in args:
            return args[args.index(name) + 1]
        return default

    report = measure()
    out = opt("--out", "BENCH_serve.json")
    with open(out, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    print(json.dumps(report, indent=2, sort_keys=True))
    baseline = opt("--check")
    if baseline:
        failed = check(report, baseline)
        if failed:
            print(f"serve regression gate FAILED: {failed}")
            raise SystemExit(1)
        print("serve regression gate passed")


if __name__ == "__main__":
    main()
