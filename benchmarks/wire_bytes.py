"""Wire bytes + accuracy per policy across the explicit TP wire.

For each quantized policy (``hfp8`` per-tensor scales, ``hfp8_block``
f32 scale grids, ``mxfp8``/``mxfp6``/``mxfp4`` narrow payloads — native
fp8 bytes or packed sub-byte codec lanes — + packed E8M0 byte grids —
DESIGN.md §9/§10), the fwd+bwd column-parallel TP GEMM is compiled on a
forced (data=2, model=4) host mesh and its optimized HLO is fed through
``launch/hlo_analysis`` — the same trip-count-weighted collective-byte
accounting the dry-run cells use, now with fractional sub-byte element
sizes.  Reported per policy: total collective wire bytes, the per-type
breakdown, and forward accuracy (row-normalized MSE vs an f64 oracle)
on group-granular outlier data.

A second section reports the packed sub-byte storage layer
(``kernels/codec.py``): payload bytes and elements/byte for every MX
format — FP4 must measure 2 elements per byte, FP6 four per three.

A third section (``kernel_hbm``) measures the packed *pipeline* HBM
footprint per MX policy: the bytes every GEMM-operand payload + scale
grid of one fwd+bwd step actually occupies under
``mx_quantize(packed=True)`` — the buffers the packed Pallas kernels
emit and consume.  FP4 payload buffers must measure 0.5 B/elem (FP6
0.75) end to end; no byte-wide intermediate exists between quantize
and GEMM.

A fourth section (``attn_kv``) measures the packed attention-KV tiles
(DESIGN.md §11): the k + v payload + scale bytes the flash sweep
streams per layer under each MX policy's ``mx_attn`` format — mxfp4 KV
must measure 0.53125 B/elem, same arithmetic as the GEMM payloads but
with groups along the head dimension.

A fifth section (``dp_grad``) measures the compressed DP gradient wire
(DESIGN.md §13): bytes one replica ships per step (packed payloads +
E8M0 grids under ``Policy.mx_dp_grad``, per-leaf fp8 otherwise) and the
single-step NMSE vs the exact mean on an outlier-heavy gradient tree —
packed MXFP6 must ship <=0.40x the bf16 bytes at NMSE no worse than the
per-leaf fp8 path.  A sixth (``moe_a2a``) compiles the expert-parallel
MoE dispatch per policy and counts its all-to-all bytes plus the
dispatch wire's roundtrip NMSE.

This doubles as CI's regression gate: ``--check BASELINE`` fails
(exit 1) if any policy's wire bytes — or its packed-pipeline HBM /
packed-KV / DP-gradient / MoE-dispatch bytes and NMSE — regress >10%
over the committed baseline (``benchmarks/baselines/wire_bytes.json``).

Run:
    PYTHONPATH=src python -m benchmarks.wire_bytes [--quick]
        [--out BENCH_wire.json] [--check benchmarks/baselines/wire_bytes.json]
"""
from __future__ import annotations

import json
import sys


def measure(quick=False):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.compat import make_mesh, set_mesh
    from repro.core.formats import MX_FORMATS
    from repro.core.policy import get_policy
    from repro.kernels import ops
    from repro.launch.hlo_analysis import analyze
    from repro.parallel.sharding import make_rules
    from repro.parallel.tp_gemm import tp_column_linear

    assert len(jax.devices()) >= 8, "run via __main__ (forces 8 devices)"
    mesh = make_mesh((2, 4), ("data", "model"))
    rules = make_rules(mesh, seq_shard=True)
    b, s, k, n = (4, 32, 64, 128) if quick else (4, 64, 256, 256)
    rng = np.random.default_rng(0)

    # group-granular outliers: one hot 32-span per row — the regime
    # per-tensor scaling flushes and MX groups resolve
    x = rng.normal(0, 1, (b, s, k))
    for i in range(b * s // 4):
        bi, si = rng.integers(b), rng.integers(s)
        j = 32 * rng.integers(k // 32)
        x[bi, si, j:j + 32] *= 2.0 ** 16
    w = rng.normal(0, 0.3, (k, n))
    xj = jnp.asarray(x, jnp.bfloat16)
    wj = jnp.asarray(w, jnp.bfloat16)
    exact = (np.asarray(xj, np.float64).reshape(-1, k)
             @ np.asarray(wj, np.float64))

    report = {"shape": {"B": b, "S": s, "K": k, "N": n,
                        "mesh": "data=2,model=4"},
              "policies": {}}
    for pname in ("hfp8", "hfp8_block", "mxfp8", "mxfp6", "mxfp4"):
        pol = get_policy(pname)

        def loss(x, w):
            return (tp_column_linear(x, w, pol, rules)
                    .astype(jnp.float32) ** 2).sum()

        with set_mesh(mesh):
            fn = jax.jit(jax.value_and_grad(loss, (0, 1)))
            hlo = fn.lower(xj, wj).compile().as_text()
            y = jax.jit(lambda x, w: tp_column_linear(x, w, pol, rules))(
                xj, wj)
        res = analyze(hlo)
        err = np.asarray(y, np.float64).reshape(-1, n) - exact
        pw = (exact ** 2).sum(1)
        nz = pw > 0
        nmse = float(np.mean((err ** 2).sum(1)[nz] / pw[nz]))
        report["policies"][pname] = {
            "coll_total": res["coll_total"],
            "coll_bytes": {t: v for t, v in res["coll_bytes"].items() if v},
            "nmse": nmse,
        }

    # packed storage: the honest bytes-per-element table
    report["packed"] = {}
    xq = jnp.asarray(rng.normal(0, 1, (s, k)), jnp.float32)
    for name, mx in MX_FORMATS.items():
        p, s8 = ops.mx_quantize(xq, name, impl="xla", packed=True)
        elems = s * k
        report["packed"][name] = {
            "elements": elems,
            "payload_bytes": int(np.prod(p.shape)),
            "scale_bytes": int(np.prod(s8.shape)),
            "elems_per_payload_byte": elems / int(np.prod(p.shape)),
            "bytes_per_element": (int(np.prod(p.shape))
                                  + int(np.prod(s8.shape))) / elems,
        }

    # packed-pipeline HBM footprint per MX policy (DESIGN.md §10): the
    # payload + scale buffers one fwd+bwd qlinear step materializes —
    # exactly what the packed quantize kernels emit and the packed GEMM
    # consumes.  Deterministic (array-level, not fusion-dependent), so
    # the >10% gate also covers memory-footprint regressions.
    report["kernel_hbm"] = {}
    x3 = jnp.asarray(rng.normal(0, 1, (b, s, k)), jnp.float32)
    w2 = jnp.asarray(rng.normal(0, 0.3, (k, n)), jnp.float32)
    g3 = jnp.asarray(rng.normal(0, 1, (b, s, n)), jnp.float32)
    for pname in ("mxfp8", "mxfp6", "mxfp4"):
        pol = get_policy(pname)
        bufs = {
            # fwd: x along K, w.T along K; dgrad: g along N, w along N;
            # wgrad: x.T and g.T along tokens (the linear.py roles)
            "fwd_act": ops.mx_quantize(x3, pol.mx_fwd, impl="xla",
                                       packed=True),
            "fwd_w": ops.mx_quantize(w2.T, pol.mx_fwd, impl="xla",
                                     packed=True),
            "dgrad_grad": ops.mx_quantize(g3, pol.mx_bwd_name, impl="xla",
                                          packed=True),
            "dgrad_w": ops.mx_quantize(w2, pol.mx_fwd, impl="xla",
                                       packed=True),
            "wgrad_act": ops.mx_quantize(
                x3.reshape(-1, k).T, pol.mx_wgrad_act_name, impl="xla",
                packed=True),
            "wgrad_grad": ops.mx_quantize(
                g3.reshape(-1, n).T, pol.mx_wgrad_grad_name, impl="xla",
                packed=True),
        }
        rec = {}
        total = 0
        for role, (p, s8) in bufs.items():
            pb, sb = int(np.prod(p.shape)), int(np.prod(s8.shape))
            rec[role] = {"payload_bytes": pb, "scale_bytes": sb}
            total += pb + sb
        elems_fwd = b * s * k
        rec["fwd_act_bytes_per_element"] = (
            bufs["fwd_act"][0].size + bufs["fwd_act"][1].size) / elems_fwd
        rec["total_bytes"] = total
        report["kernel_hbm"][pname] = rec

    # packed attention-KV tiles (DESIGN.md §11): the k + v payload and
    # scale buffers one attention layer's flash sweep streams from HBM
    # (and stores as the backward residuals) under each MX policy's
    # ``mx_attn`` format — groups of 32 along the head dimension.
    report["attn_kv"] = {}
    bh, t, hd = (4, 32, 64) if quick else (8, 128, 64)
    kv = jnp.asarray(rng.normal(0, 1, (bh, t, hd)), jnp.float32)
    for pname in ("mxfp8", "mxfp6", "mxfp4"):
        pol = get_policy(pname)
        kp, ks8 = ops.mx_quantize_kv(kv, pol.mx_attn_name, impl="xla")
        vp, vs8 = ops.mx_quantize_kv(kv, pol.mx_attn_name, impl="xla")
        payload = int(np.prod(kp.shape)) + int(np.prod(vp.shape))
        scales = int(np.prod(ks8.shape)) + int(np.prod(vs8.shape))
        report["attn_kv"][pname] = {
            "format": pol.mx_attn_name,
            "elements": 2 * bh * t * hd,
            "payload_bytes": payload,
            "scale_bytes": scales,
            "total_bytes": payload + scales,
            "bytes_per_element": (payload + scales) / (2 * bh * t * hd),
        }

    # compressed DP gradient wire (DESIGN.md §13): bytes one replica
    # ships per step and single-step NMSE vs the exact mean, on an
    # outlier-heavy gradient tree — the regime where the per-leaf f32
    # scale flushes everything but the hot leaf's outlier and the
    # group-32 E8M0 grids keep resolving the rest.
    from repro.optim.grad_compress import (compressed_psum_mean,
                                           dp_wire_bytes_per_step,
                                           error_feedback_init)
    gshapes = {"w_in": (64, 256), "w_out": (256, 64), "bias": (256,),
               "emb": (96, 64)}
    gtree = {}
    for gname, sh in gshapes.items():
        g = rng.normal(0, 1e-3, sh)
        flatg = g.reshape(-1)
        # sparse, *severe* outliers (2^36: enough to push the rest of
        # the leaf below fp8-e5m2's subnormal floor under one shared
        # f32 scale) — the laundering regime group-32 grids resolve
        hot = rng.integers(flatg.size, size=max(1, flatg.size // 4096))
        flatg[hot] *= 2.0 ** 36
        gtree[gname] = jnp.asarray(flatg.reshape(sh), jnp.float32)
    n_elems = sum(int(np.prod(sh)) for sh in gshapes.values())
    bf16_bytes = 2 * n_elems

    def row_nmse(red):
        # row-normalized (256-element spans) so the handful of outliers
        # can't launder the flushed mass out of the metric — same
        # normalization idea as the TP section's per-row MSE
        ratios = []
        for gname, g in gtree.items():
            ref = np.asarray(g, np.float64).reshape(-1)
            err = np.asarray(red[gname], np.float64).reshape(-1) - ref
            rows = -(-ref.size // 256) * 256
            refp = np.zeros(rows); refp[:ref.size] = ref
            errp = np.zeros(rows); errp[:ref.size] = err
            pw = (refp.reshape(-1, 256) ** 2).sum(1)
            ratios.append(((errp.reshape(-1, 256) ** 2).sum(1)[pw > 0]
                           / pw[pw > 0]))
        return float(np.mean(np.concatenate(ratios)))

    report["dp_grad"] = {"elements": n_elems, "bf16_bytes": bf16_bytes}
    ef0 = error_feedback_init(gtree)
    for pname in ("fp8_leaf", "mxfp8", "mxfp6", "mxfp4"):
        mx = None if pname == "fp8_leaf" else get_policy(pname).mx_dp_grad
        with set_mesh(mesh):
            red, _ = jax.jit(lambda g, e: compressed_psum_mean(
                g, e, mesh, "data", mx=mx))(gtree, ef0)
        wire = dp_wire_bytes_per_step(gtree, mx=mx)
        report["dp_grad"][pname] = {
            "format": mx or "fp8e5m2_per_leaf",
            "wire_bytes": wire,
            "bytes_vs_bf16": wire / bf16_bytes,
            "nmse": row_nmse(red),
        }
    # the tentpole's acceptance bar: packed MXFP6 gradient wire ships
    # <=0.40x the bf16 bytes at NMSE no worse than the per-leaf fp8 path
    assert report["dp_grad"]["mxfp6"]["bytes_vs_bf16"] <= 0.40, \
        report["dp_grad"]["mxfp6"]
    assert (report["dp_grad"]["mxfp6"]["nmse"]
            <= report["dp_grad"]["fp8_leaf"]["nmse"]), report["dp_grad"]

    # MoE dispatch all-to-all (DESIGN.md §13): compile the EP path per
    # policy on the same mesh and count its all-to-all bytes through
    # hlo_analysis (packed payloads + E8M0 grids under MX policies, raw
    # carrier bf16 otherwise), plus the dispatch wire's roundtrip NMSE
    # on the send buffer.
    import dataclasses as _dc

    from repro.configs import get_arch
    from repro.models import moe as MOE
    from repro.parallel.tp_gemm import _deq_mx, _quant_mx
    from repro.core.formats import get_mx_format
    mcfg = get_arch("granite-moe-3b-a800m")
    mcfg = _dc.replace(mcfg, d_model=64, d_ff=128, n_experts=8, top_k=2,
                       capacity_factor=1.5, moe_dense_ff=0)
    mp = MOE.init_moe(jax.random.PRNGKey(0), mcfg, jnp.bfloat16)
    xm = jnp.asarray(rng.normal(0, 1, (4, 32, mcfg.d_model)), jnp.bfloat16)
    buf = jnp.asarray(rng.normal(0, 1, (4, 96, mcfg.d_model)), jnp.float32)
    report["moe_a2a"] = {}
    for pname in ("bf16", "mxfp8", "mxfp6", "mxfp4"):
        pol = get_policy(pname)
        with set_mesh(mesh):
            fn = jax.jit(lambda x, p: MOE.moe_ffn_ep(
                x, p, mcfg, pol, rules=rules)[0])
            hlo = fn.lower(xm, mp).compile().as_text()
        res = analyze(hlo)
        a2a = res["coll_bytes"].get("all-to-all", 0.0)
        if pol.mx:
            mxf = get_mx_format(pol.mx_fwd)
            deq = _deq_mx(*_quant_mx(buf, mxf), mxf)
            nmse = float(jnp.mean((deq - buf) ** 2)
                         / jnp.mean(buf ** 2))
        else:
            nmse = float(jnp.mean(
                (buf.astype(jnp.bfloat16).astype(jnp.float32) - buf) ** 2)
                / jnp.mean(buf ** 2))
        report["moe_a2a"][pname] = {
            "format": pol.mx_fwd or "bf16",
            "a2a_bytes": a2a,
            "coll_total": res["coll_total"],
            "dispatch_nmse": nmse,
        }
    # packed wires must actually shrink the hop vs the carrier a2a
    assert (report["moe_a2a"]["mxfp6"]["a2a_bytes"]
            < report["moe_a2a"]["bf16"]["a2a_bytes"]), report["moe_a2a"]
    return report


def check(report, baseline_path, tol=1.10):
    """>10% wire-byte regression vs the committed baseline fails."""
    with open(baseline_path) as f:
        base = json.load(f)
    failed = []
    for pname, rec in report["policies"].items():
        b = base.get("policies", {}).get(pname)
        if b is None:
            continue
        ratio = rec["coll_total"] / max(b["coll_total"], 1.0)
        status = "OK" if ratio <= tol else "REGRESSED"
        print(f"wire-bytes {pname}: {rec['coll_total']:.0f} vs baseline "
              f"{b['coll_total']:.0f} ({ratio:.3f}x) {status}")
        if ratio > tol:
            failed.append(pname)
    for name, rec in report["packed"].items():
        b = base.get("packed", {}).get(name)
        if b and rec["elems_per_payload_byte"] < b["elems_per_payload_byte"]:
            print(f"packed {name}: {rec['elems_per_payload_byte']} "
                  f"elems/byte < baseline {b['elems_per_payload_byte']}")
            failed.append(name)
    # packed-pipeline HBM footprint: a policy's per-step payload+scale
    # bytes growing >10% means something un-packed (or re-widened)
    for pname, rec in report.get("kernel_hbm", {}).items():
        b = base.get("kernel_hbm", {}).get(pname)
        if b is None:
            continue
        ratio = rec["total_bytes"] / max(b["total_bytes"], 1.0)
        status = "OK" if ratio <= tol else "REGRESSED"
        print(f"kernel-hbm {pname}: {rec['total_bytes']} vs baseline "
              f"{b['total_bytes']} ({ratio:.3f}x) {status}")
        if ratio > tol:
            failed.append(f"kernel_hbm:{pname}")
    # packed attention-KV tiles (§11): the flash sweep's HBM operands —
    # growth means the KV payloads stopped being packed
    for pname, rec in report.get("attn_kv", {}).items():
        b = base.get("attn_kv", {}).get(pname)
        if b is None:
            continue
        ratio = rec["total_bytes"] / max(b["total_bytes"], 1.0)
        status = "OK" if ratio <= tol else "REGRESSED"
        print(f"attn-kv {pname}: {rec['total_bytes']} vs baseline "
              f"{b['total_bytes']} ({ratio:.3f}x) {status}")
        if ratio > tol:
            failed.append(f"attn_kv:{pname}")
    # compressed DP gradient wire (§13): both the shipped bytes and the
    # outlier-sweep NMSE are gated — un-packing the payload or breaking
    # the group grids shows up in one or the other
    for pname, rec in report.get("dp_grad", {}).items():
        b = base.get("dp_grad", {}).get(pname)
        if not isinstance(rec, dict) or b is None:
            continue
        br = rec["wire_bytes"] / max(b["wire_bytes"], 1.0)
        nr = rec["nmse"] / max(b["nmse"], 1e-300)
        status = "OK" if (br <= tol and nr <= tol) else "REGRESSED"
        print(f"dp-grad {pname}: {rec['wire_bytes']} B ({br:.3f}x), "
              f"nmse {rec['nmse']:.3e} ({nr:.3f}x) {status}")
        if br > tol:
            failed.append(f"dp_grad:{pname}:bytes")
        if nr > tol:
            failed.append(f"dp_grad:{pname}:nmse")
    # MoE dispatch all-to-all (§13): same two-sided gate on the EP
    # path's collective bytes and the dispatch roundtrip NMSE
    for pname, rec in report.get("moe_a2a", {}).items():
        b = base.get("moe_a2a", {}).get(pname)
        if b is None:
            continue
        br = rec["a2a_bytes"] / max(b["a2a_bytes"], 1.0)
        nr = rec["dispatch_nmse"] / max(b["dispatch_nmse"], 1e-300)
        status = "OK" if (br <= tol and nr <= tol) else "REGRESSED"
        print(f"moe-a2a {pname}: {rec['a2a_bytes']:.0f} B ({br:.3f}x), "
              f"nmse {rec['dispatch_nmse']:.3e} ({nr:.3f}x) {status}")
        if br > tol:
            failed.append(f"moe_a2a:{pname}:bytes")
        if nr > tol:
            failed.append(f"moe_a2a:{pname}:nmse")
    return failed


def main():
    import os
    # the 8-host-device sweep runs on the CPU only: pin the platform
    # before any backend starts so neither it nor a child takes a chip
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        # must happen before the first jax import (measure imports lazily)
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    args = sys.argv[1:]

    def opt(name, default=None):
        if name in args:
            return args[args.index(name) + 1]
        return default

    report = measure(quick="--quick" in args)
    out = opt("--out", "BENCH_wire.json")
    with open(out, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    print(json.dumps(report, indent=2, sort_keys=True))
    baseline = opt("--check")
    if baseline:
        failed = check(report, baseline)
        if failed:
            print(f"wire-byte regression gate FAILED: {failed}")
            raise SystemExit(1)
        print("wire-byte regression gate passed")


if __name__ == "__main__":
    main()
