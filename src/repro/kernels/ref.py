"""Pure-jnp oracles for every Pallas kernel (bit-faithful semantics).

``mx_flash_attention_ref`` is the one numpy-carried oracle: it leans on
the numpy format mirrors (``mx_quantize_np``/``mx_dequantize_np``) so
the attention test harness has a reference with no JAX ops at all.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core import formats as F
from ..core.formats import get_mx_format, quantize
from ..core.scaling import expand_group_scales

__all__ = ["exsdotp_gemm_ref", "quant_blockwise_ref", "blockscale_gemm_ref",
           "mx_quant_ref", "mx_gemm_ref", "flash_attention_ref",
           "mx_flash_attention_ref", "decode_attention_ref",
           "mx_decode_attention_ref", "compressed_mean_mx_ref",
           "mx_dispatch_wire_ref"]

#: the attention p·v dot keeps its f32 probabilities (a default TPU dot
#: rounds them to bf16): the precision the Pallas kernels pin
_F32 = jax.lax.Precision.HIGHEST


def exsdotp_gemm_ref(a: jax.Array, b: jax.Array, scale=1.0,
                     *, out_dtype=jnp.float32) -> jax.Array:
    """Expanding GEMM oracle: upcast, fp32 accumulate, scale, single downcast.

    Matches the kernel exactly when the fp32 accumulation itself is exact
    (e.g. integer-valued inputs); otherwise to within fp32 summation-order
    rounding (tested with tight tolerances).
    """
    acc = jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    return (acc * jnp.float32(scale)).astype(out_dtype)


def quant_blockwise_ref(x: jax.Array, *, q_dtype, block_m=128, block_n=128,
                        margin=1.0):
    m, n = x.shape
    gm, gn = m // block_m, n // block_n
    xb = x.astype(jnp.float32).reshape(gm, block_m, gn, block_n)
    amax = jnp.max(jnp.abs(xb), axis=(1, 3))
    max_normal = float(jnp.finfo(q_dtype).max)
    # non-finite amax -> scale 1: poison propagates instead of zeroing
    s = jnp.where((amax > 0) & jnp.isfinite(amax),
                  amax / (max_normal * margin), 1.0)
    q = (xb / s[:, None, :, None]).astype(q_dtype)
    return q.reshape(m, n), s


def blockscale_gemm_ref(a: jax.Array, b: jax.Array, sa: jax.Array,
                        sb: jax.Array, *, q_dtype_a, q_dtype_b,
                        block_m=128, block_n=128, block_k=128,
                        out_dtype=jnp.float32) -> jax.Array:
    """Oracle for the fused block-scaled GEMM (same math, pure jnp).

    Quantize each (row-tile × K-tile) of ``a`` (K-tile × col-tile of
    ``b``) with its own scale, dequantize, fp32-accumulate, round once.
    Bit-identical to the kernel whenever fp32 accumulation is exact.

    ``a``/``sa`` may carry leading batch dims (``a[..., M, K]``,
    ``sa[..., M/bm, K/bk]``): row tiles never cross them, and the
    contraction keeps native rank (no flatten — sharded leading dims
    survive under GSPMD).
    """
    *lead, m, k = a.shape
    _, n = b.shape
    gm, gk, gn = m // block_m, k // block_k, n // block_n

    def deq(x, s, br, bc, q_dtype):
        xb = x.astype(jnp.float32).reshape(
            *x.shape[:-2], x.shape[-2] // br, br, x.shape[-1] // bc, bc)
        st = s[..., :, None, :, None]
        q = (xb / st).astype(q_dtype).astype(jnp.float32)
        return (q * st).reshape(x.shape)

    assert (*lead, gm, gk) == sa.shape and (gk, gn) == sb.shape, (
        sa.shape, sb.shape)
    af = deq(a, sa.astype(jnp.float32), block_m, block_k, q_dtype_a)
    bf = deq(b, sb.astype(jnp.float32), block_k, block_n, q_dtype_b)
    acc = jnp.einsum("...mk,kn->...mn", af, bf,
                     preferred_element_type=jnp.float32)
    return acc.astype(out_dtype)


def mx_quant_ref(x: jax.Array, *, mx):
    """Oracle for the fused MX quantize kernel (same math, pure jnp).

    Per-(row × group-of-32-along-K) E8M0 scales + value-space element
    cast; returns ``(q[..., K] f32, s[..., K/group] f32)``.
    """
    from ..core.scaling import apply_group_scales, compute_group_scales
    mx = get_mx_format(mx)
    xf = x.astype(jnp.float32)
    s = compute_group_scales(xf, mx.group, mx.elem.max_normal)
    q = quantize(apply_group_scales(xf, s, mx.group, inverse=True), mx.elem)
    return q, s


def mx_gemm_ref(a: jax.Array, b: jax.Array, sa: jax.Array, sb: jax.Array,
                *, mx_a, mx_b=None, out_dtype=jnp.float32) -> jax.Array:
    """Oracle for the fused MX GEMM (same math, pure jnp).

    Quantize each 1×group strip of ``a`` along K (group × column strip of
    ``b``) with its own E8M0 scale, dequantize (exact — pow2 scales),
    fp32-accumulate, round once.  Bit-identical to the kernel whenever
    fp32 accumulation is exact.  ``a``/``sa`` may carry leading batch
    dims (``a[..., M, K]``, ``sa[..., M, K/g]``).
    """
    mx_a = get_mx_format(mx_a)
    mx_b = mx_a if mx_b is None else get_mx_format(mx_b)
    g = mx_a.group

    def deq_rows(x, s, fmt):  # groups along the last axis
        se = expand_group_scales(s.astype(jnp.float32), g).reshape(x.shape)
        return quantize(x.astype(jnp.float32) / se, fmt) * se

    af = deq_rows(a, sa, mx_a.elem)
    bf = deq_rows(b.T, sb.T, mx_b.elem).T  # b groups run along K, per column
    acc = jnp.einsum("...mk,kn->...mn", af, bf,
                     preferred_element_type=jnp.float32)
    return acc.astype(out_dtype)


def flash_attention_ref(q, k, v, *, causal=True):
    """q [BH,S,hd], k/v [BH,T,hd] — exact softmax attention oracle."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        sq, tk = s.shape[-2:]
        mask = jnp.arange(tk)[None, :] <= jnp.arange(sq)[:, None]
        s = jnp.where(mask[None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", w, v.astype(jnp.float32),
                      precision=_F32).astype(q.dtype)


def decode_attention_ref(q, k, v, lens, *, neg=-1e30):
    """Decode attention oracle (pure jnp — the serving xla branch).

    ``q [BH, S, hd]`` rows sit at absolute cache slots ``lens + i``
    against a cache ``k/v [BH, T, hd]`` whose live prefix is
    ``lens + S`` per sequence-head; garbage slots beyond it are zeroed
    *structurally* (both operands, before any dot) so stale non-finite
    trash in dead cache slots cannot reach live rows.  Mirrors the
    kernel's operation order — masked logits at ``-1e30`` (not -inf),
    row max, ``p = exp(s - m)``, one division by ``max(l, 1e-30)`` —
    so exact-arithmetic operands reproduce it bitwise.
    """
    bh, s, hd = q.shape
    t = k.shape[1]
    lens = jnp.asarray(lens, jnp.int32)
    cols = jnp.arange(t)[None, :]                      # [1, T]
    good = cols < (lens[:, None] + s)                  # [BH, T] live prefix
    kf = jnp.where(good[..., None], k.astype(jnp.float32), 0.0)
    vf = jnp.where(good[..., None], v.astype(jnp.float32), 0.0)
    scale = jnp.float32(hd ** -0.5)
    sc = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32), kf) * scale
    rows = lens[:, None, None] + jnp.arange(s)[None, :, None]  # [BH, S, 1]
    sc = jnp.where(cols[:, None, :] <= rows, sc, jnp.float32(neg))
    m = jnp.max(sc, axis=-1, keepdims=True)
    p = jnp.exp(sc - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("bqk,bkd->bqd", p, vf, precision=_F32)
    return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)


def mx_decode_attention_ref(q, k, v, lens, *, mx_k, mx_v=None):
    """Numpy oracle for the packed-cache decode attention kernel.

    Takes *high-precision* cache contents ``k/v [BH, T, hd]``,
    quantizes them with the numpy MX mirrors (per row × group-of-32
    along hd — exactly what ``ops.mx_quantize_kv`` stores in the page
    pool), and computes the base-offset masked attention of
    ``decode_attention_ref`` in pure numpy, mirroring the kernel's
    operation order (m → p → l → Σp·v → one division).

    Masked and garbage keys are excluded from the weighted sum
    *structurally* (the p·v products are zeroed, not merely weighted by
    an underflowed exp) — matching the kernel's carry/page-skip and
    garbage masking.  NaN-scale poison inside the *fully visible*
    region propagates identically in both; tests keep poison out of
    the partially-masked diagonal band (same §11 caveat as
    ``mx_flash_attention_ref``).  Returns ``[BH, S, hd]`` as q.dtype.
    """
    mx_k = get_mx_format(mx_k)
    mx_v = mx_k if mx_v is None else get_mx_format(mx_v)
    qf = np.asarray(q, np.float32)
    lens = np.asarray(lens, np.int32)
    bh, s, hd = qf.shape
    t = np.asarray(k).shape[1]
    kq, ks = F.mx_quantize_np(np.asarray(k, np.float32), mx_k)
    vq, vs = F.mx_quantize_np(np.asarray(v, np.float32), mx_v)
    kf = F.mx_dequantize_np(kq, ks, mx_k).astype(np.float32)
    vf = F.mx_dequantize_np(vq, vs, mx_v).astype(np.float32)
    cols = np.arange(t)[None, :]                       # [1, T]
    good = cols < (lens[:, None] + s)                  # [BH, T]
    kf = np.where(good[..., None], kf, np.float32(0))
    vf = np.where(good[..., None], vf, np.float32(0))
    scale = np.float32(hd ** -0.5)
    with np.errstate(invalid="ignore", over="ignore"):
        sc = np.einsum("bqd,bkd->bqk", qf, kf).astype(np.float32) * scale
        rows = lens[:, None, None] + np.arange(s)[None, :, None]
        valid = cols[:, None, :] <= rows               # [BH, S, T]
        sc = np.where(valid, sc, np.float32(-1e30))
        m = sc.max(axis=-1, keepdims=True)
        p = np.exp(sc - m)
        l = p.sum(axis=-1, keepdims=True, dtype=np.float32)
        pv = p[..., None] * vf[:, None, :, :]          # [BH, S, T, hd]
        pv = np.where(valid[..., None], pv, np.float32(0))
        acc = pv.sum(axis=-2, dtype=np.float32)
        out = acc / np.maximum(l, np.float32(1e-30))
    return out.astype(np.asarray(q).dtype)


def compressed_mean_mx_ref(grads, efs, *, mx):
    """Numpy oracle for the MX DP gradient wire (DESIGN.md §13).

    ``grads``/``efs`` are length-``n`` lists of same-shaped arrays, one
    per source replica.  Mirrors ``optim.grad_compress._leaf_mx``
    source by source: ``gc = g + e`` flattens, zero-pads to whole
    groups of ``mx.group``, quantizes with the numpy MX mirrors
    (E8M0 pow2 scales, NaN-scale poison for non-finite groups), and the
    mean of the *dequantized* streams — sliced back to the original
    shape — is what every receiver computes.  New error feedback is the
    local residual, reset to zero when non-finite (the wire's carried
    state must stay clean even on poisoned steps).

    Returns ``(mean, new_efs)``; pure numpy, f64 accumulation — exact
    whenever the jax path's chunked f32 accumulation is (the
    exact-arithmetic operand harness guarantees both).
    """
    mx = get_mx_format(mx)
    shape = np.asarray(grads[0]).shape
    size = int(np.prod(shape))
    kp = -(-size // mx.group) * mx.group
    deqs, new_efs = [], []
    with np.errstate(invalid="ignore", over="ignore"):
        for g, e in zip(grads, efs):
            gc = np.asarray(g, np.float32) + np.asarray(e, np.float32)
            fp = np.zeros(kp, np.float32)
            fp[:size] = gc.reshape(-1)
            q, s = F.mx_quantize_np(fp, mx)
            deq = F.mx_dequantize_np(q, s, mx).astype(np.float32)
            ne = (fp - deq)[:size].reshape(shape)
            if not np.all(np.isfinite(ne)):
                ne = np.zeros_like(ne)
            deqs.append(deq)
            new_efs.append(ne)
        mean = (np.sum(np.stack(deqs).astype(np.float64), axis=0)
                / len(grads)).astype(np.float32)
    return mean[:size].reshape(shape), new_efs


def mx_dispatch_wire_ref(x, *, mx):
    """Numpy oracle for one hop of the MoE packed dispatch wire: MX
    quantize over groups along the last axis (numpy mirrors, NaN-scale
    poison included), dequantize.  The all-to-all itself is a block
    permutation — bytes move, values don't — so the wire's value
    transform is exactly this roundtrip, and tests compare the on-mesh
    ``mx_dispatch_a2a`` output against the permuted roundtrip."""
    mx = get_mx_format(mx)
    with np.errstate(invalid="ignore", over="ignore"):
        q, s = F.mx_quantize_np(np.asarray(x, np.float32), mx)
        return F.mx_dequantize_np(q, s, mx).astype(np.float32)


def mx_flash_attention_ref(q, k, v, *, mx_k, mx_v=None, causal=True):
    """Numpy oracle for the MX-quantized KV flash attention kernel.

    Quantizes k/v per (row × group-along-hd) with the numpy MX mirrors
    (one E8M0 pow2 scale per 32 head-dim elements — lossless to undo),
    then computes f32 softmax attention mirroring the kernel's
    operation order: logits → row max → ``p = exp(s - m)`` →
    ``acc = Σ p·v`` → one division by ``max(l, 1e-30)``.  Bit-identical
    to ``mx_flash_attention_pallas`` whenever every f32 intermediate is
    exact (``tests/fuzz.exact_attention_operands`` constructs such
    operands: the per-block row max then equals the global max, so the
    online rescale factors are exactly 0 or 1).

    Masked (structurally-zero) keys are excluded from the weighted sum
    entirely — the ``p·v`` products are zeroed by the mask, not merely
    weighted by ``exp(-inf) = 0`` — matching the carry-skip kernel for
    every tile beyond the causal diagonal.  Poison (NaN-scale) groups
    in the *valid* region propagate identically in both; tests keep
    poison out of the partially-masked diagonal band, where the kernel
    necessarily still streams the masked columns of a live tile.

    Returns ``out [BH, S, hd]`` as ``q.dtype``; pure numpy throughout.
    """
    mx_k = get_mx_format(mx_k)
    mx_v = mx_k if mx_v is None else get_mx_format(mx_v)
    qf = np.asarray(q, np.float32)
    kq, ks = F.mx_quantize_np(np.asarray(k, np.float32), mx_k)
    vq, vs = F.mx_quantize_np(np.asarray(v, np.float32), mx_v)
    kf = F.mx_dequantize_np(kq, ks, mx_k).astype(np.float32)
    vf = F.mx_dequantize_np(vq, vs, mx_v).astype(np.float32)
    scale = np.float32(qf.shape[-1] ** -0.5)
    with np.errstate(invalid="ignore", over="ignore"):
        s = np.einsum("bqd,bkd->bqk", qf, kf).astype(np.float32) * scale
        sq, t = s.shape[-2], s.shape[-1]
        valid = None
        if causal:
            valid = np.arange(t)[None, :] <= np.arange(sq)[:, None]
            s = np.where(valid[None], s, -np.inf)
        m = s.max(axis=-1, keepdims=True)
        p = np.exp(s - m)
        l = p.sum(axis=-1, keepdims=True, dtype=np.float32)
        pv = p[..., None] * vf[:, None, :, :]            # [BH, S, T, hd]
        if valid is not None:
            pv = np.where(valid[None, :, :, None], pv, np.float32(0))
        acc = pv.sum(axis=-2, dtype=np.float32)
        out = acc / np.maximum(l, np.float32(1e-30))
    return out.astype(np.asarray(q).dtype)
