"""Benchmark harness — one entry per paper table/figure + the roofline
table from the dry-run artifacts. Prints structured CSV for the modeled
and accuracy sections and JSON for the byte counts; nothing here times
a kernel (device time comes from the chip benchmark, ``bench/``).

Covers: Table II / Fig. 8 (table2_gemm), Table IV (table4_accuracy),
Fig. 7a (fig7_resources), plus the beyond-paper block-scaling sweep
(blockscale_gemm) and the roofline instrument (roofline).

Run:
    PYTHONPATH=src python -m benchmarks.run [--quick]
"""
from __future__ import annotations

import sys


def main() -> None:
    quick = "--quick" in sys.argv
    print("=" * 72)
    print("## Table II / Fig. 8 — GEMM cycles & FLOP/cycle (modeled)")
    from benchmarks import table2_gemm
    table2_gemm.main()
    print("=" * 72)
    print("## Table IV — ExSdotp vs ExFMA accuracy (bit-exact oracle)")
    from benchmarks import table4_accuracy
    # >= 25 draws: single draws are cancellation-conditioned (see module)
    table4_accuracy.main(trials=8 if quick else 25)
    print("=" * 72)
    print("## Fig. 7 — datapath resource proxies + kernel VMEM budget")
    from benchmarks import fig7_resources
    fig7_resources.main()
    print("=" * 72)
    print("## Block-scaled vs per-tensor GEMM (beyond-paper; outlier sweep)")
    from benchmarks import blockscale_gemm
    blockscale_gemm.accuracy_sweep(quick)
    blockscale_gemm.throughput(quick)
    blockscale_gemm.tp_sweep(quick)  # skips unless >= 8 (forced) devices
    print("=" * 72)
    print("## Packed payload pipeline: bytes + accuracy across MXFP8/6/4 (§10)")
    from benchmarks import mx_packed_sweep
    mx_packed_sweep.main(quick)
    print("=" * 72)
    print("## Serving: paged-cache bytes/seq per policy (§12)")
    import json as _json
    from benchmarks import serve_sweep
    print(_json.dumps(serve_sweep.measure(), indent=2, sort_keys=True))
    print("=" * 72)
    print("## Wire bytes per policy across the explicit TP wire (§9)")
    import jax
    if len(jax.devices()) >= 8:
        import json
        from benchmarks import wire_bytes
        print(json.dumps(wire_bytes.measure(quick), indent=2, sort_keys=True))
    else:
        print("(skipped: needs 8 forced host devices; "
              "run python -m benchmarks.wire_bytes)")
    print("=" * 72)
    print("## Roofline (from dry-run artifacts, if present)")
    import os
    if any(os.path.isdir(d) and os.listdir(d) for d in
           ("experiments/dryrun_baseline", "experiments/dryrun_opt",
            "experiments/dryrun")):
        from benchmarks import roofline
        roofline.main()
    else:
        print("(no dry-run artifacts; run python -m repro.launch.dryrun --all)")


if __name__ == "__main__":
    main()
