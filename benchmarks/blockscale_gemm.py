"""Block-scaled vs per-tensor ExSdotp GEMM: accuracy + throughput sweep.

Beyond-paper extension of Table IV (accuracy of expanding chains) to
GEMM granularity: the same fused multiply-narrow/accumulate-wide/round-
once structure, with quantization scales at per-tensor vs per-block
(row-tile × K-tile) granularity.  The workload is an outlier-tile sweep:
a unit-scale Gaussian matrix with a fraction of tiles boosted by 2^E,
E swept past each format's dynamic range (FP8alt E4M3 ~2^18, FP8 E5M2
~2^32) — the regime where one outlier flushes the per-tensor-scaled
tensor to zero but leaves per-block untouched.

Reported per (format, E): row-normalized MSE for per-tensor and
per-block, their ratio, and wall-clock of the jitted fused GEMM vs the
separate quantize→GEMM pipeline (the fused path also saves the
quantized tensor's HBM round-trip).

A third sweep (``tp_sweep``) measures the same protocol *across the
wire*: the shard_map TP column GEMM with sequence-sharded activations
on a forced (data=2, model=4) host mesh, comparing the ``hfp8`` wire
(per-shard-tensor scales) against ``hfp8_block`` (per-block scale grids
riding alongside the fp8 payload) — block scaling × sequence
parallelism composed (DESIGN.md §3).

A fourth sweep (``mx_sweep``) pushes scale granularity to the MX limit
(DESIGN.md §8): per-(row × group-of-32-along-K) E8M0 shared exponents,
for all five predefined MX formats, against per-tensor scaling and
128×128 block scaling.  The workload plants one hot 32-column group per
128×128 tile — exactly the granularity block scaling cannot resolve (the
hot group drags its whole tile's window up) but group-32 can.

Run:
    PYTHONPATH=src python -m benchmarks.blockscale_gemm [--quick]
"""
from __future__ import annotations

import sys
import time

import numpy as np


def _time_us(fn, *args, warmup=2, iters=10):
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def outlier_matrix(rng, m, k, bs, emax, frac=0.15):
    x = rng.normal(0, 1, (m, k))
    mask = rng.random((m // bs, k // bs)) < frac
    x *= np.kron(np.where(mask, 2.0 ** emax, 1.0), np.ones((bs, bs)))
    return x


def accuracy_sweep(quick=False):
    import jax.numpy as jnp
    from repro.core.scaling import BlockScaleConfig
    from repro.kernels import ops, ref

    m, k, n, bs = (128, 128, 64, 32) if quick else (512, 512, 256, 64)
    rng = np.random.default_rng(0)
    b = jnp.asarray(rng.normal(0, 1, (k, n)), jnp.float32)
    cfg = BlockScaleConfig(block_m=bs, block_n=bs, block_k=bs)
    print("format,outlier_exp,nmse_per_tensor,nmse_per_block,ratio")
    for fname, q in [("fp8alt_e4m3", jnp.float8_e4m3),
                     ("fp8_e5m2", jnp.float8_e5m2)]:
        for emax in (0, 8, 16, 24, 32, 40):
            a = jnp.asarray(outlier_matrix(rng, m, k, bs, emax), jnp.float32)
            exact = np.asarray(a, np.float64) @ np.asarray(b, np.float64)

            def row_nmse(out):
                err = np.asarray(out, np.float64) - exact
                pw = (exact ** 2).sum(1)
                return float(np.mean((err ** 2).sum(1)[pw > 0] / pw[pw > 0]))

            blk = ops.blockscale_gemm(a, b, q_dtype_a=q, cfg=cfg)
            aq, sa = ops.quantize_tensor(a, q)
            bq, sb = ops.quantize_tensor(b, q)
            pt = ref.exsdotp_gemm_ref(aq, bq, sa * sb)
            e_b, e_t = row_nmse(blk), row_nmse(pt)
            print(f"{fname},{emax},{e_t:.3e},{e_b:.3e},"
                  f"{e_t / max(e_b, 1e-300):.1f}")


def throughput(quick=False):
    import jax
    import jax.numpy as jnp
    from repro.core.scaling import BlockScaleConfig
    from repro.kernels import ops

    m = k = n = 512 if quick else 1024
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.normal(0, 1, (m, k)), jnp.float32)
    b = jnp.asarray(rng.normal(0, 1, (k, n)), jnp.float32)
    cfg = BlockScaleConfig()
    q = jnp.float8_e4m3

    @jax.jit
    def fused(a, b):
        return ops.blockscale_gemm(a, b, q_dtype_a=q, cfg=cfg)

    @jax.jit
    def two_pass(a, b):
        aq, sa = ops.quantize_tensor(a, q)
        bq, sb = ops.quantize_tensor(b, q)
        return ops.exsdotp_gemm(aq, bq, sa * sb)

    print("name,us_per_call,shape")
    print(f"blockscale_fused,{_time_us(fused, a, b):.1f},{m}x{k}x{n}")
    print(f"per_tensor_two_pass,{_time_us(two_pass, a, b):.1f},{m}x{k}x{n}")


def tp_sweep(quick=False):
    """Block scaling × TP/SP: outlier accuracy across the fp8 wire.

    Requires >= 8 host devices — ``main()`` forces them via XLA_FLAGS
    before the first jax import.
    """
    import jax
    import jax.numpy as jnp
    from repro.compat import make_mesh, set_mesh
    from repro.core.policy import get_policy
    from repro.parallel.sharding import make_rules
    from repro.parallel.tp_gemm import tp_column_linear

    if len(jax.devices()) < 8:
        print("tp_sweep: skipped (needs 8 devices; run via __main__)")
        return
    mesh = make_mesh((2, 4), ("data", "model"))
    rules = make_rules(mesh, seq_shard=True)
    b, s, k, n, bs = (4, 32, 128, 128, 32) if quick else (4, 64, 256, 256, 64)
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(0, 0.3, (k, n)), jnp.float32).astype(
        jnp.bfloat16)
    print("wire,outlier_exp,nmse_per_tensor,nmse_per_block,ratio")
    for emax in (0, 8, 16, 24, 32):
        x = jnp.asarray(outlier_matrix(rng, b * s, k, bs, emax)
                        .reshape(b, s, k), jnp.float32).astype(jnp.bfloat16)
        exact = (np.asarray(x, np.float64).reshape(-1, k)
                 @ np.asarray(w, np.float64))

        def row_nmse(y):
            err = np.asarray(y, np.float64).reshape(-1, n) - exact
            pw = (exact ** 2).sum(1)
            nz = pw > 0
            return float(np.mean((err ** 2).sum(1)[nz] / pw[nz]))

        with set_mesh(mesh):
            yb = jax.jit(lambda x, w: tp_column_linear(
                x, w, get_policy("hfp8_block"), rules))(x, w)
            yt = jax.jit(lambda x, w: tp_column_linear(
                x, w, get_policy("hfp8"), rules))(x, w)
        e_b, e_t = row_nmse(yb), row_nmse(yt)
        print(f"tp_column,{emax},{e_t:.3e},{e_b:.3e},"
              f"{e_t / max(e_b, 1e-300):.1f}")


def mx_outlier_matrix(rng, m, k, group, emax, tile=128):
    """Unit Gaussians with one hot 32-column group per (tile × tile) tile
    — sub-tile outlier granularity, the regime MX groups exist for."""
    x = rng.normal(0, 1, (m, k))
    for ti in range(max(1, m // tile)):
        for tj in range(max(1, k // tile)):
            i = tile * ti + rng.integers(min(tile, m))
            j = tile * tj + group * rng.integers(max(1, min(tile, k) // group))
            x[i, j:j + group] *= 2.0 ** emax
    return x


def mx_sweep(quick=False):
    """Group-32 (MX) vs per-tensor vs 128×128 block scaling accuracy."""
    import jax.numpy as jnp
    from repro.core.formats import MX_FORMATS
    from repro.core.scaling import BlockScaleConfig
    from repro.kernels import ops, ref

    m, k, n = (128, 128, 64) if quick else (512, 512, 256)
    g = 32
    rng = np.random.default_rng(2)
    b = jnp.asarray(rng.normal(0, 1, (k, n)), jnp.float32)
    cfg = BlockScaleConfig()  # 128×128 tiles
    print("format,outlier_exp,nmse_per_tensor,nmse_block128,nmse_mx_group32,"
          "ratio_pt_over_mx,ratio_blk_over_mx")
    for name, mx in MX_FORMATS.items():
        q8 = jnp.float8_e4m3 if "e4m3" in name else jnp.float8_e5m2
        for emax in (0, 8, 16, 24):
            a = jnp.asarray(mx_outlier_matrix(rng, m, k, g, emax),
                            jnp.float32)
            exact = np.asarray(a, np.float64) @ np.asarray(b, np.float64)

            def row_nmse(out):
                err = np.asarray(out, np.float64) - exact
                pw = (exact ** 2).sum(1)
                nz = pw > 0
                return float(np.mean((err ** 2).sum(1)[nz] / pw[nz]))

            e_mx = row_nmse(ops.mx_gemm(a, b, mx_a=name))
            # per-tensor / block baselines use the nearest fp8 dtype (the
            # sub-byte element formats exist only on the MX path)
            e_blk = row_nmse(ops.blockscale_gemm(a, b, q_dtype_a=q8,
                                                 cfg=cfg))
            aq, sa = ops.quantize_tensor(a, q8)
            bq, sb = ops.quantize_tensor(b, q8)
            e_pt = row_nmse(ref.exsdotp_gemm_ref(aq, bq, sa * sb))
            print(f"{name},{emax},{e_pt:.3e},{e_blk:.3e},{e_mx:.3e},"
                  f"{e_pt / max(e_mx, 1e-300):.1f},"
                  f"{e_blk / max(e_mx, 1e-300):.1f}")


def main():
    import os
    # the 8-host-device sweep runs on the CPU only: pin the platform
    # before any backend starts so neither it nor a child takes a chip
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        # must happen before the first jax import (sweeps import lazily)
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    quick = "--quick" in sys.argv
    accuracy_sweep(quick)
    throughput(quick)
    mx_sweep(quick)
    tp_sweep(quick)


if __name__ == "__main__":
    main()
