"""QLinear — every GEMM in the framework routes through the paper's
expanding-dot-product primitive.

Forward (HFP8): x, W are quantized per-tensor (or per-block) into FP8alt
(E4M3), multiplied narrow, accumulated fp32, rounded once into the carrier
dtype — a GEMM-sized ExSdotp chain.  Backward: gradients are quantized into
FP8 (E5M2, wider range) for both dgrad and wgrad GEMMs, again with fp32
accumulation.  This is Sun et al.'s HFP8 recipe, the workload the
MiniFloat-NN ISA was designed for, expressed as a ``jax.custom_vjp``.

Each pass runs under a ``jax.named_scope`` of ``repro.scopes.GEMM``:
``quant_x``, ``quant_w`` and ``gemm`` forward; ``quant_g``, ``dgrad`` and
``wgrad`` in the backward, set inside ``_qlinear_nd_bwd`` itself.

First/last layers (embedding, logits) conventionally stay un-quantized;
models decide via config flags.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..kernels import ops
from ..kernels.ops import resolve_impl
from .policy import Policy, get_policy

__all__ = ["qlinear", "linear"]


def _gemm(a, b, scale, out_dtype, impl):
    return ops.exsdotp_gemm(a, b, scale, out_dtype=out_dtype, impl=impl)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _qlinear_nd(x, w, policy: Policy, impl: str):
    y, _ = _qlinear_nd_fwd(x, w, policy, impl)
    return y


def _qlinear_nd_fwd(x, w, policy: Policy, impl: str):
    """x [..., K] @ w [K, N] — native rank: no reshape, so sharded leading
    dims (batch/sequence-parallel) survive into the GEMM instead of being
    all-gathered by a flatten (§Perf iteration D1)."""
    if policy.mx:
        # packed MX pipeline (DESIGN.md §10): quantize kernels emit the
        # packed uint8 payloads + E8M0 byte grids directly, the GEMM
        # consumes packed refs and decodes in-register — the operands
        # exist in HBM only at width/8 (+1/32) bytes per element.  The
        # activation residual is that same packed payload (0.53 B/elem
        # for FP4 vs 2 B bf16), re-grouped along the token axis in bwd
        # for wgrad.  Native rank: MX scales are per-row, so leading
        # dims stay batch dims.
        mxf = policy.mx_fwd
        with jax.named_scope("quant_x"):
            xp, sx8 = ops.mx_quantize(x, mxf, impl=impl, packed=True)
        with jax.named_scope("quant_w"):
            wp, sw8 = ops.mx_quantize(w.T, mxf, impl=impl, packed=True)
        with jax.named_scope("gemm"):
            y = ops.mx_gemm_packed(xp, sx8, wp, sw8, mx_a=mxf,
                                   out_dtype=policy.compute_dtype,
                                   impl=impl)
        return y, (xp, sx8, w)
    cfg = policy.block_cfg
    if cfg is not None:
        # fused block-scaled path (DESIGN.md §3): per-(row-tile × K-tile)
        # scales, cast in VMEM inside the GEMM — no separate quantize pass
        # over HBM, and no quantized residuals (bwd re-quantizes fused too).
        # Native rank: row tiles live on the unflattened token axes
        # (per-(batch, seq-tile) granularity), so sequence-sharded leading
        # dims survive into the GEMM like the per-tensor branch (D1) —
        # no flatten-induced GSPMD reshard.
        with jax.named_scope("gemm"):
            y = ops.blockscale_gemm(
                x, w, q_dtype_a=policy.fwd_dtype,
                cfg=cfg, out_dtype=policy.compute_dtype, impl=impl)
        return y, (x, w)
    with jax.named_scope("quant_x"):
        xq, sx = ops.quantize_tensor(x, policy.fwd_dtype)
    with jax.named_scope("quant_w"):
        wq, sw = ops.quantize_tensor(w, policy.fwd_dtype)
    with jax.named_scope("gemm"):
        if resolve_impl(impl) == "xla":
            acc = jnp.dot(xq.astype(jnp.float32), wq.astype(jnp.float32),
                          preferred_element_type=jnp.float32)
            y = (acc * (sx * sw)).astype(policy.compute_dtype)
        else:
            lead = x.shape[:-1]
            y = _gemm(xq.reshape(-1, x.shape[-1]), wq, sx * sw,
                      policy.compute_dtype, impl).reshape(*lead, w.shape[-1])
    return y, (xq, sx, wq, sw)


def _qlinear_nd_bwd(policy: Policy, impl: str, res, g):
    if policy.mx:
        xp, sx8, w = res
        cd = policy.compute_dtype
        mxf, mxb = policy.mx_fwd, policy.mx_bwd_name
        mxwa = policy.mx_wgrad_act_name
        mxwg = policy.mx_wgrad_grad_name
        k, n = w.shape
        # dgrad: bwd-format grads × fwd-format weights, groups of 32
        # along the contracted N axis on both packed operands.
        with jax.named_scope("quant_g"):
            gp, sg8 = ops.mx_quantize(g, mxb, impl=impl, packed=True)
        with jax.named_scope("dgrad"):
            with jax.named_scope("quant_w"):
                wnp, swn8 = ops.mx_quantize(w, mxf, impl=impl, packed=True)
            dx = ops.mx_gemm_packed(gp, sg8, wnp, swn8, mx_a=mxb, mx_b=mxf,
                                    out_dtype=cd, impl=impl)
        # wgrad (possibly in wider "master" formats — mx_wgrad_*): both
        # operands re-group along the contracted token axis (dW sums
        # over all tokens, so the flatten is by construction).  x comes
        # from its packed fwd payload — the one fwd rounding the narrow
        # residual implies, exactly like the per-tensor path's fp8
        # residuals; the raw cotangent takes no extra rounding.
        with jax.named_scope("wgrad"):
            with jax.named_scope("quant_x"):
                xf = ops.mx_dequantize_packed(xp, sx8, mxf, k=k)
                x2 = xf.reshape(-1, k)
                xtp, sxt8 = ops.mx_quantize(x2.T, mxwa, impl=impl,
                                            packed=True)
            with jax.named_scope("quant_g"):
                g2 = g.astype(jnp.float32).reshape(-1, n)
                gtp, sgt8 = ops.mx_quantize(g2.T, mxwg, impl=impl,
                                            packed=True)
            dw = ops.mx_gemm_packed(xtp, sxt8, gtp, sgt8, mx_a=mxwa,
                                    mx_b=mxwg, out_dtype=cd, impl=impl)
        return dx, dw
    cfg = policy.block_cfg
    if cfg is not None:
        x, w = res
        cd = policy.compute_dtype
        # dgrad: E5M2 grads × E4M3 weights, native rank (sequence shards
        # survive); wgrad: E4M3 acts × E5M2 grads — the token contraction
        # flattens by construction (dW sums over all tokens anyway).
        with jax.named_scope("dgrad"):
            dx = ops.blockscale_gemm(
                g, w.T, q_dtype_a=policy.bwd_dtype,
                q_dtype_b=policy.fwd_dtype, cfg=cfg, out_dtype=cd, impl=impl)
        with jax.named_scope("wgrad"):
            g2 = g.reshape(-1, g.shape[-1])
            x2 = x.reshape(-1, x.shape[-1])
            dw = ops.blockscale_gemm(
                x2.T, g2, q_dtype_a=policy.fwd_dtype,
                q_dtype_b=policy.bwd_dtype, cfg=cfg, out_dtype=cd, impl=impl)
        return dx, dw
    xq, sx, wq, sw = res
    cd = policy.compute_dtype  # x and w were cast to this before the vjp
    with jax.named_scope("quant_g"):
        gq, sg = ops.quantize_tensor(g, policy.bwd_dtype)
    nbatch = xq.ndim - 1
    if resolve_impl(impl) == "xla":
        # dgrad: dx[..., K] = g[..., N] @ W^T
        with jax.named_scope("dgrad"):
            dx = (jnp.dot(gq.astype(jnp.float32), wq.astype(jnp.float32).T,
                          preferred_element_type=jnp.float32)
                  * (sg * sw)).astype(cd)
        # wgrad: dW[K, N] = sum_... x[..., K] g[..., N]
        with jax.named_scope("wgrad"):
            dw = (jnp.tensordot(xq.astype(jnp.float32),
                                gq.astype(jnp.float32),
                                axes=(list(range(nbatch)),
                                      list(range(nbatch))))
                  * (sx * sg)).astype(cd)
        return dx, dw
    k = xq.shape[-1]
    n = gq.shape[-1]
    g2 = gq.reshape(-1, n)
    x2 = xq.reshape(-1, k)
    with jax.named_scope("dgrad"):
        dx = _gemm(g2, wq.T, sg * sw, cd, impl).reshape(xq.shape)
    with jax.named_scope("wgrad"):
        dw = _gemm(x2.T, g2, sx * sg, cd, impl)
    return dx, dw


_qlinear_nd.defvjp(_qlinear_nd_fwd, _qlinear_nd_bwd)


def qlinear(x: jax.Array, w: jax.Array, policy, *, impl: str = "auto") -> jax.Array:
    """y[..., N] = x[..., K] @ w[K, N] under the mixed-precision policy."""
    policy = get_policy(policy)
    if not policy.quantized:
        cd = policy.compute_dtype
        with jax.named_scope("gemm"):
            return jnp.dot(x.astype(cd), w.astype(cd),
                           preferred_element_type=jnp.float32).astype(cd)
    return _qlinear_nd(x.astype(policy.compute_dtype),
                       w.astype(policy.compute_dtype), policy, impl)


def linear(x: jax.Array, w: jax.Array, b=None, *, policy, impl: str = "auto",
           quantized: bool = True) -> jax.Array:
    """Linear layer with optional bias; ``quantized=False`` opts a layer out
    (embedding/logits heads, norms' affine params, routers)."""
    policy = get_policy(policy)
    if quantized and policy.quantized:
        y = qlinear(x, w, policy, impl=impl)
    else:
        cd = policy.compute_dtype
        with jax.named_scope("gemm"):
            y = jnp.dot(x.astype(cd), w.astype(cd),
                        preferred_element_type=jnp.float32).astype(cd)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y
