"""Public jit'd wrappers for the kernel layer: dispatch + padding + autotune.

``impl`` resolution:
  * 'auto'              -> compiled Pallas on TPU, XLA fallback elsewhere
  * 'pallas'            -> compiled Pallas (TPU)
  * 'pallas_interpret'  -> Pallas interpret mode (CPU correctness runs/tests)
  * 'xla'               -> pure-jnp reference semantics (exact same math)

All entry points accept arbitrary (M, K, N); non-aligned shapes are padded
up to block multiples (zero padding is exact for GEMM and for amax).
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
import ml_dtypes

from ..core.formats import decode, e8m0_decode, e8m0_encode, encode, \
    get_mx_format
from ..core.scaling import (BlockScaleConfig, apply_group_scales,
                            compute_block_scales, compute_group_scales,
                            expand_group_scales)
from . import autotune, ref
from .blockscale_gemm import (blockscale_gemm_pallas, mx_gemm_packed_pallas,
                              mx_gemm_pallas)
from .codec import get_codec
from .exsdotp_gemm import exsdotp_gemm_pallas, default_blocks
from .quant import (mx_quant_packed_pallas, mx_quant_pallas,
                    quant_blockwise_pallas)

__all__ = ["exsdotp_gemm", "blockscale_gemm", "blockscale_blocks",
           "quantize_tensor", "quantize_blockwise", "dequantize_blockwise",
           "mx_quantize", "mx_dequantize", "mx_dequantize_packed",
           "mx_gemm", "mx_blocks", "mx_packed_blocks",
           "mx_pack", "mx_unpack", "mx_gemm_packed",
           "mx_quantize_kv", "mx_flash_attention",
           "mx_flash_attention_packed", "attention_blocks",
           "decode_attention", "mx_decode_attention_packed",
           "decode_attention_blocks", "resolve_impl"]


def resolve_impl(impl: str) -> str:
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return impl


def _tune_sweep_enabled() -> bool:
    """Whether ``tiles='auto'`` may *measure* on a cache miss.

    Default: sweep only on a real TPU backend — CPU/interp runs (tests,
    CI) answer from the committed cache or fall back to the static
    heuristic, so they stay deterministic and never burn minutes timing
    interpret-mode kernels.  ``REPRO_TUNE_SWEEP=1`` forces sweeping
    anywhere (how the committed cache is populated); ``=0`` forbids it
    even on TPU (DESIGN.md §14).
    """
    env = os.environ.get("REPRO_TUNE_SWEEP")
    if env is not None:
        return env not in ("", "0")
    return jax.default_backend() == "tpu"


def _pad2(x, bm, bn):
    m, n = x.shape
    pm, pn = (-m) % bm, (-n) % bn
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)))
    return x


def _pad_last2(x, br, bc):
    """Zero-pad the last two dims of ``x[..., R, C]`` to tile multiples
    (per-batch padding: leading dims untouched)."""
    r, c = x.shape[-2], x.shape[-1]
    pr, pc = (-r) % br, (-c) % bc
    if pr or pc:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, pr), (0, pc)])
    return x


def exsdotp_gemm(a: jax.Array, b: jax.Array, scale=1.0, *,
                 out_dtype=jnp.float32, impl: str = "auto",
                 blocks=None) -> jax.Array:
    """Expanding GEMM: downcast(scale * A @ B) with fp32 accumulation."""
    impl = resolve_impl(impl)
    if impl == "xla":
        return ref.exsdotp_gemm_ref(a, b, scale, out_dtype=out_dtype)
    m, k = a.shape
    _, n = b.shape
    bm, bn, bk = blocks or default_blocks(m, n, k, a.dtype.itemsize)
    a, fmt_a = _kernel_operand(_pad2(a, bm, bk))
    b, fmt_b = _kernel_operand(_pad2(b, bk, bn))
    out = exsdotp_gemm_pallas(
        a, b, jnp.asarray(scale, jnp.float32).reshape(1, 1),
        out_dtype=out_dtype, block_m=bm, block_n=bn, block_k=bk,
        fmt_a=fmt_a, fmt_b=fmt_b, interpret=(impl == "pallas_interpret"))
    return out[:m, :n]


def _kernel_operand(x: jax.Array):
    """IEEE ``float8_e4m3`` (fp8alt) enters Pallas as its ``uint8`` bit
    patterns plus the format name, and is decoded in-register: Mosaic
    cannot load that dtype on v5e.  Other dtypes pass through."""
    if x.dtype == jnp.dtype(ml_dtypes.float8_e4m3):
        return jax.lax.bitcast_convert_type(x, jnp.uint8), "fp8alt"
    return x, None


def blockscale_blocks(m: int, n: int, k: int,
                      cfg: BlockScaleConfig) -> tuple[int, int, int]:
    """Tile sizes for a block-scaled (M, K) × (K, N) GEMM.

    When a dim is smaller than the configured block, the block shrinks —
    but only down to a *legal* Pallas tile: M is sublane-only (unit 8),
    while N and K land on a lane axis of some operand tile (N for B and
    the output, K for A), where compiled TPU Pallas requires multiples
    of 128.  A narrow-N GEMM (MoE router, small heads) therefore pads N
    up to 128 instead of picking an illegal ``block_n=8``; the padded
    columns are zero, so scales and the GEMM are unaffected.
    """
    bm = min(cfg.block_m, _ceil_mult(m, 8))
    bn = min(cfg.block_n, _ceil_mult(n, 128))
    bk = min(cfg.block_k, _ceil_mult(k, 128))
    return bm, bn, bk


def blockscale_gemm(a: jax.Array, b: jax.Array, *, q_dtype_a, q_dtype_b=None,
                    cfg: BlockScaleConfig = BlockScaleConfig(),
                    out_dtype=jnp.float32, impl: str = "auto",
                    tiles=None) -> jax.Array:
    """Fused block-scaled expanding GEMM (DESIGN.md §3).

    Takes *high-precision* ``a[..., M, K]`` / ``b[K, N]`` (fp32/bf16),
    computes per-(row-tile × K-tile) scales, and quantizes into
    ``q_dtype_a``/``q_dtype_b`` inside the GEMM itself — the quantized
    tensors never round-trip HBM.  fp32 accumulation, one final rounding.

    ``a`` keeps native rank: leading dims are batch, row tiles are
    per-(leading index, row-tile) and never cross a batch/sequence
    boundary, so sharded leading dims survive into the GEMM (no flatten
    before the xla branch; the Pallas branch flattens payload *and*
    scale grid identically, so granularity is the same across impls).

    ``tiles='auto'`` (DESIGN.md §14) looks up tuned *compute* tiles for
    the problem from the autotune cache.  The scale grid stays the
    config's block sizes — candidates only subdivide it (the
    ``scale_block_*`` mechanism), so quantization granularity and the
    results are unchanged; the default (``tiles=None``) is the original
    static heuristic, bit-for-bit.
    """
    impl = resolve_impl(impl)
    q_dtype_b = q_dtype_a if q_dtype_b is None else q_dtype_b
    *lead, m, k = a.shape
    _, n = b.shape
    bm, bn, bk = blockscale_blocks(m, n, k, cfg)
    a = _pad_last2(a, bm, bk)
    b = _pad2(b, bk, bn)
    sa = compute_block_scales(a, bm, bk, q_dtype_a,
                              margin=cfg.margin, pow2=cfg.pow2)
    sb = compute_block_scales(b, bk, bn, q_dtype_b,
                              margin=cfg.margin, pow2=cfg.pow2)
    if impl == "xla":
        out = ref.blockscale_gemm_ref(
            a, b, sa, sb, q_dtype_a=q_dtype_a, q_dtype_b=q_dtype_b,
            block_m=bm, block_n=bn, block_k=bk, out_dtype=out_dtype)
    else:
        mp, kp = a.shape[-2], a.shape[-1]
        cbm, cbn, cbk = bm, bn, bk
        skw = {}
        if tiles == "auto":
            (cbm, cbn, cbk), _ = autotune.blockscale_tiles(
                math.prod(lead) * mp, b.shape[1], kp, (bm, bn, bk),
                q_dtype_a, q_dtype_b, impl=impl,
                sweep=_tune_sweep_enabled())
            skw = dict(scale_block_m=bm, scale_block_n=bn,
                       scale_block_k=bk)
        out = blockscale_gemm_pallas(
            a.reshape(-1, kp), b, sa.reshape(-1, sa.shape[-1]), sb,
            q_dtype_a=q_dtype_a, q_dtype_b=q_dtype_b,
            out_dtype=out_dtype, block_m=cbm, block_n=cbn, block_k=cbk,
            interpret=(impl == "pallas_interpret"), **skw)
        out = out.reshape(*lead, mp, out.shape[-1])
    return out[..., :m, :n]


# ------------------------------------------------------------------ MX ----

def mx_blocks(m: int, n: int, k: int, group: int) -> tuple[int, int, int]:
    """Tile sizes for an MX (M, K) × (K, N) GEMM.

    Same legality rules as ``blockscale_blocks`` (lane axes N/K round to
    128, sublane M to 8), plus ``block_k`` must contain whole groups —
    with the standard group of 32 the 128-lane floor already does.
    """
    bm = min(128, _ceil_mult(m, 8))
    bn = min(128, _ceil_mult(n, 128))
    lk = 128 * group // math.gcd(128, group)   # lcm: lane-legal, whole groups
    bk = min(lk, _ceil_mult(k, lk))
    return bm, bn, bk


def mx_packed_blocks(m: int, n: int, group: int,
                     *codecs) -> tuple[int, int, int]:
    """Tile sizes for the *packed-ref* MX kernels (DESIGN.md §10).

    M/N follow the ``blockscale_blocks`` rules; ``block_k`` must contain
    whole groups AND yield lane-legal packed byte runs for every codec
    involved (``codec.lane_unit``: 128 for FP8, 256 for FP4, 512 for
    FP6 — a 128-multiple of bytes after packing).
    """
    bm = min(128, _ceil_mult(m, 8))
    bn = min(128, _ceil_mult(n, 128))
    bk = group
    for unit in [c.lane_unit for c in codecs] + [128]:
        bk = bk * unit // math.gcd(bk, unit)   # lcm
    return bm, bn, bk


def _pad_group(x: jax.Array, group: int) -> jax.Array:
    """Zero-pad the last axis up to a whole number of groups (the
    ragged-K mask: zeros never raise a group amax, an all-pad group
    gets the neutral scale 1 and a zero payload, and its GEMM
    contribution is exactly 0)."""
    pad = (-x.shape[-1]) % group
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x


def mx_quantize(x: jax.Array, mx, *, impl: str = "auto",
                packed: bool = False):
    """Per-group MX quantization of ``x[..., M, K]`` (DESIGN.md §8).

    Returns ``(q, scales)``: ``q[..., M, K]`` f32 element-format values
    of ``x / s`` and ``scales[..., M, ⌈K/group⌉]`` E8M0 pow2 scales,
    with ``x ~= q * s`` broadcast per 1×group strip along K (exact
    rescale — pow2).  Groups never span rows, so leading dims are free
    batch dims.  A ragged K (not a whole number of groups) is
    zero-padded internally: ``q`` is sliced back to ``K`` and the last
    scale covers the partial tail group.

    With ``packed=True`` (DESIGN.md §10) the return is the *storage*
    layout instead: ``(payload, scales)`` where ``payload`` is the
    densely packed uint8 bit patterns (FP8: one byte per element, FP6:
    three bytes per four, FP4: one byte per two) covering
    ``group-padded`` K, and ``scales`` the E8M0 uint8 codes — the
    honest HBM/wire footprint.  On the Pallas impls the kernel *emits*
    the packed payload directly (``mx_quant_packed_pallas``): no byte-
    or f32-wide quantized intermediate exists between the quantize and
    the packed GEMM.  The round-trip through ``mx_unpack``/
    ``e8m0_decode`` is lossless, so ``mx_gemm_packed`` on packed
    operands is bit-identical to the value-space path.
    """
    impl = resolve_impl(impl)
    mx = get_mx_format(mx)
    *lead, m, k = x.shape
    x = _pad_group(x, mx.group)          # ragged K: pad-and-mask
    kg = x.shape[-1]
    if impl == "xla":
        q, s = ref.mx_quant_ref(x, mx=mx)
        if packed:
            return mx_pack(q, mx), e8m0_encode(s)
        return (q[..., :k] if kg != k else q), s
    interp = impl == "pallas_interpret"
    if packed:
        codec = get_codec(mx)
        bm, _, bk = mx_packed_blocks(m, 1, mx.group, codec)
        xp = _pad_last2(x.astype(jnp.float32), bm, bk)
        mp, kp = xp.shape[-2], xp.shape[-1]
        p, s8 = mx_quant_packed_pallas(xp.reshape(-1, kp), mx=mx,
                                       block_m=bm, block_k=bk,
                                       interpret=interp)
        p = p.reshape(*lead, mp, codec.packed_cols(kp))[
            ..., :m, :codec.packed_cols(kg)]
        s8 = s8.reshape(*lead, mp, kp // mx.group)[..., :m, :kg // mx.group]
        return p, s8
    bm, _, bk = mx_blocks(m, 1, kg, mx.group)
    xp = _pad_last2(x.astype(jnp.float32), bm, bk)
    mp, kp = xp.shape[-2], xp.shape[-1]
    q, s = mx_quant_pallas(xp.reshape(-1, kp), mx=mx, block_m=bm,
                           block_k=bk, interpret=interp)
    q = q.reshape(*lead, mp, kp)[..., :m, :k]
    s = s.reshape(*lead, mp, kp // mx.group)[..., :m, :kg // mx.group]
    return q, s


def mx_pack(q: jax.Array, mx) -> jax.Array:
    """Pack MX element values ``q[..., K]`` (f32 carrier, already in the
    element format's value set) into dense uint8 storage:
    ``[..., ⌈K/align⌉ * width / 8]`` bytes via the payload codec.  A
    ragged K is zero-padded to the pack alignment (zero codes decode to
    +0.0 — ``mx_unpack(..., k=K)`` slices the tail back off)."""
    mx = get_mx_format(mx)
    codec = get_codec(mx)
    pad = (-q.shape[-1]) % codec.pack_align
    if pad:
        q = jnp.pad(q, [(0, 0)] * (q.ndim - 1) + [(0, pad)])
    return codec.encode_lanes(q)


def mx_unpack(p: jax.Array, mx, *, k=None) -> jax.Array:
    """Unpack dense uint8 storage back to f32 element values
    (``[..., K]`` with ``K = bytes * 8 / width``, sliced to ``k`` when
    given — the ragged-shape inverse); exact inverse of ``mx_pack`` for
    every representable value."""
    vals = get_codec(get_mx_format(mx)).decode_lanes(p)
    return vals[..., :k] if k is not None else vals


def mx_gemm_packed(ap: jax.Array, sa8: jax.Array, bp: jax.Array,
                   sb8: jax.Array, *, mx_a, mx_b=None,
                   out_dtype=jnp.float32, impl: str = "auto",
                   tiles=None) -> jax.Array:
    """Expanding GEMM straight from packed MX storage (DESIGN.md §10).

    ``(ap, sa8)`` is ``mx_quantize(a[..., M, K], packed=True)``;
    ``(bp, sb8)`` is ``mx_quantize(b.T, packed=True)`` — B's groups run
    along K down each column, so its packed payload is stored
    transposed.  Unpack → exact pow2 dequant (E8M0 codes) → f32
    accumulation → one rounding: bit-identical to
    ``ops.mx_gemm(a, b, impl='xla')`` on the same operands, because the
    pack/unpack round-trip is lossless and the math after it is the
    same.  On the Pallas impls the packed refs enter the kernel as-is:
    VMEM holds ``width/8`` bytes per element and the unpack/decode
    happens in-register per K-tile (``mx_gemm_packed_pallas``) — the
    payloads never exist byte-wide outside the registers.  This is the
    memory model the wire-byte benchmark measures.  K may be
    group-padded relative to the logical shapes (``mx_quantize`` pads
    ragged K): padded groups contribute exactly zero.

    ``tiles='auto'`` (DESIGN.md §14) replaces the static
    ``mx_packed_blocks`` heuristic with tuned (block_m, block_n,
    block_k) tiles *and* the tuned K-loop streaming schedule
    (grid-pipelined vs double-buffered manual DMA) from the autotune
    cache.  MX group scales are a property of the layout (groups of 32
    along K), not of the tile grid, so any tuned choice is bit-exact vs
    the default on exact-arithmetic operands.
    """
    impl = resolve_impl(impl)
    mx_a = get_mx_format(mx_a)
    mx_b = mx_a if mx_b is None else get_mx_format(mx_b)
    g = mx_a.group
    assert mx_b.group == g, (mx_a.name, mx_b.name)
    if impl == "xla":
        af = apply_group_scales(mx_unpack(ap, mx_a), e8m0_decode(sa8), g)
        bf = apply_group_scales(mx_unpack(bp, mx_b), e8m0_decode(sb8), g).T
        acc = jnp.einsum("...mk,kn->...mn", af, bf,
                         preferred_element_type=jnp.float32)
        return acc.astype(out_dtype)
    ca, cb = get_codec(mx_a), get_codec(mx_b)
    *lead, m, _ = ap.shape
    n = bp.shape[0]
    k = sa8.shape[-1] * g
    assert ap.shape[-1] == ca.packed_cols(k), (ap.shape, k)
    assert bp.shape == (n, cb.packed_cols(k)), (bp.shape, (n, k))
    assert sb8.shape == (n, k // g), (sb8.shape, (n, k // g))
    bm, bn, bk = mx_packed_blocks(m, n, g, ca, cb)
    db = False
    if tiles == "auto":
        (bm, bn, bk), db, _ = autotune.gemm_packed_tiles(
            math.prod(lead) * m, n, k, mx_a, mx_b, impl=impl,
            sweep=_tune_sweep_enabled())
    # scale codes enter the kernel at element resolution (compact grids
    # would be lane-illegal on compiled TPU — the §8 rule, now one u8
    # per element instead of the value-path's f32)
    sae8 = jnp.repeat(sa8.reshape(-1, k // g), g, axis=-1)
    sbe8 = jnp.repeat(sb8, g, axis=-1)
    # pad rows to tile multiples and K to whole packed lane tiles; zero
    # payload bytes decode to +0.0 and zero scale codes to 2^-127, so
    # padded contributions are exactly 0
    ap2 = _pad2(ap.reshape(-1, ap.shape[-1]), bm, ca.packed_cols(bk))
    sae8 = _pad2(sae8, bm, bk)
    bp2 = _pad2(bp, bn, cb.packed_cols(bk))
    sbe8 = _pad2(sbe8, bn, bk)
    out = mx_gemm_packed_pallas(
        ap2, bp2, sae8, sbe8, mx_a=mx_a, mx_b=mx_b, out_dtype=out_dtype,
        block_m=bm, block_n=bn, block_k=bk, double_buffer=db,
        interpret=(impl == "pallas_interpret"))
    return out[:ap.reshape(-1, ap.shape[-1]).shape[0], :n].reshape(
        *lead, m, n)


# --------------------------------------------------- MX attention ----

def attention_blocks(s: int, t: int) -> "tuple[int, int] | None":
    """(block_q, block_k) for a flash-attention sweep over S × T, or
    None when no legal tiling exists.

    Picks the largest power-of-two tile ≤ 128 that divides each length
    (floor 8 — the sublane unit; the kernels assert exact divisibility
    rather than padding, because attention masks are positional and a
    padded length would need an extra in-kernel mask).
    """
    def pick(n):
        for b in (128, 64, 32, 16, 8):
            if n % b == 0:
                return b
        return None

    bq, bk = pick(s), pick(t)
    return (bq, bk) if bq and bk else None


def mx_quantize_kv(kv: jax.Array, mx, *, impl: str = "auto"):
    """Attention-shaped packed MX quantize: ``kv[..., T, hd]`` with
    E8M0 group scales over the *head* dimension (DESIGN.md §11).

    Thin shape-checked wrapper over ``mx_quantize(packed=True)`` — hd
    must be a whole number of groups (no ragged tail: the head axis is
    the q·kᵀ contraction, and a padded head dim would change
    ``scale = hd**-0.5``).  Returns ``(payload [..., T, hd·w/8] u8,
    scales [..., T, hd/group] u8)``.
    """
    mx = get_mx_format(mx)
    hd = kv.shape[-1]
    assert hd % mx.group == 0, (hd, mx.group)
    return mx_quantize(kv, mx, impl=impl, packed=True)


def mx_flash_attention_packed(q: jax.Array, kp: jax.Array, ks8: jax.Array,
                              vp: jax.Array, vs8: jax.Array, *, mx_k,
                              mx_v=None, causal: bool = True,
                              block_q=None, block_k=None,
                              impl: str = "auto",
                              tiles=None) -> jax.Array:
    """Flash attention straight from packed MX KV storage (DESIGN.md
    §11) — the attention analogue of ``mx_gemm_packed``.

    ``q [BH, S, hd]`` carrier precision; ``(kp, ks8)`` / ``(vp, vs8)``
    from ``mx_quantize_kv``.  On the Pallas impls the packed refs enter
    the kernel as-is and decode in-register per KV tile
    (``mx_flash_attention_pallas``); the xla branch dequantizes (exact
    — pow2 scales) and runs the straight-softmax reference — identical
    math up to f32 summation order and the online-softmax rescale,
    which exact-arithmetic operands make bitwise equal.

    ``tiles='auto'`` (DESIGN.md §14) replaces the static
    ``attention_blocks`` tile pick with the tuned (block_q, block_k)
    from the autotune cache — candidates divide S/T exactly, so the
    sweep visits the same (query, KV) pairs in the same online-softmax
    order per q row; explicit ``block_q``/``block_k`` still win.
    """
    from .flash_attention import mx_flash_attention_pallas
    impl = resolve_impl(impl)
    mx_k = get_mx_format(mx_k)
    mx_v = mx_k if mx_v is None else get_mx_format(mx_v)
    hd = q.shape[-1]
    if impl == "xla":
        kf = mx_dequantize_packed(kp, ks8, mx_k, k=hd).astype(jnp.float32)
        vf = mx_dequantize_packed(vp, vs8, mx_v, k=hd).astype(jnp.float32)
        return ref.flash_attention_ref(q, kf, vf, causal=causal)
    if tiles == "auto":
        (bq, bk), _ = autotune.attention_tiles(
            "mx_flash", q.shape[0], q.shape[1], kp.shape[1], hd,
            fmt_k=mx_k, fmt_v=mx_v, causal=causal, impl=impl,
            sweep=_tune_sweep_enabled())
    else:
        blocks = attention_blocks(q.shape[1], kp.shape[1])
        assert blocks is not None, (q.shape, kp.shape)
        bq, bk = blocks
    return mx_flash_attention_pallas(
        q, kp, ks8, vp, vs8, mx_k=mx_k, mx_v=mx_v, causal=causal,
        block_q=block_q or bq, block_k=block_k or bk,
        interpret=(impl == "pallas_interpret"))


def decode_attention_blocks(s: int, t: int) -> tuple[int, int]:
    """(block_q, block_k) for a decode sweep over S query rows × T cache
    slots.  Unlike ``attention_blocks`` this never fails: decode S is
    often 1 (or a prompt length with no structure), so the q tile falls
    through the pow2 ladder down to 1 and the KV tile down to 8.  Tiles
    below the sublane/lane units are interpret/CPU-only — the same
    legality convention as the §11 kernels; real-TPU serving picks
    aligned page sizes.
    """
    def pick(n, floor):
        for b in (128, 64, 32, 16, 8, 4, 2, 1):
            if b >= floor and n % b == 0:
                return b
        return 1

    return pick(s, 1), pick(t, 8)


def _compiled_q_tile(s: int, bq: int, impl: str) -> int:
    """Compiled TPU takes a q tile of the whole S=1 row or a sublane
    8-multiple; the decode wrappers pad S up to it (a prompt of 17 rows
    runs as 24)."""
    if impl == "pallas" and s > 1 and bq % 8:
        return 8
    return bq


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     lens: jax.Array, *, block_q=None, block_k=None,
                     impl: str = "auto", tiles=None) -> jax.Array:
    """Serving attention over a carrier-precision cache (DESIGN.md §12).

    ``q [BH, S, hd]`` rows at absolute slots ``lens + i`` against cache
    ``k/v [BH, T, hd]``; slots beyond the live prefix ``lens + S`` are
    structurally excluded (garbage pages).  Pallas impls run the
    base-offset online-softmax sweep with the page-skip; the xla branch
    is ``ref.decode_attention_ref`` — identical math.  ``tiles='auto'``
    swaps the static ``decode_attention_blocks`` pick for the tuned
    (block_q, block_k) from the autotune cache (DESIGN.md §14).
    """
    from .decode_attention import decode_attention_pallas
    impl = resolve_impl(impl)
    if impl == "xla":
        return ref.decode_attention_ref(q, k, v, lens)
    if tiles == "auto":
        (bq, bk), _ = autotune.attention_tiles(
            "decode", q.shape[0], q.shape[1], k.shape[1], q.shape[-1],
            impl=impl, sweep=_tune_sweep_enabled())
    else:
        bq, bk = decode_attention_blocks(q.shape[1], k.shape[1])
    bq = _compiled_q_tile(q.shape[1], bq, impl)
    return decode_attention_pallas(
        q, k, v, lens, block_q=block_q or bq, block_k=block_k or bk,
        interpret=(impl == "pallas_interpret"))


def mx_decode_attention_packed(q: jax.Array, kp: jax.Array, ks8: jax.Array,
                               vp: jax.Array, vs8: jax.Array,
                               lens: jax.Array, *, mx_k, mx_v=None,
                               block_q=None, block_k=None,
                               impl: str = "auto", tiles=None) -> jax.Array:
    """Serving attention straight from the packed paged KV cache
    (DESIGN.md §12) — the decode analogue of
    ``mx_flash_attention_packed``.

    ``(kp, ks8)`` / ``(vp, vs8)`` are gathered page slots in
    ``mx_quantize_kv`` layout; ``lens [BH]`` the live lengths.  On the
    Pallas impls the packed slots decode in-register per KV tile
    (``mx_decode_attention_pallas``); the xla branch dequantizes (exact
    — pow2 scales) and runs the masked reference.  Garbage slots beyond
    ``lens + S`` are excluded structurally on every impl, so stale
    NaN-scale poison in freed pages never reaches live rows.
    ``tiles='auto'`` swaps the static ``decode_attention_blocks`` pick
    for the tuned (block_q, block_k) from the autotune cache
    (DESIGN.md §14); explicit ``block_q``/``block_k`` still win.
    """
    from .decode_attention import mx_decode_attention_pallas
    impl = resolve_impl(impl)
    mx_k = get_mx_format(mx_k)
    mx_v = mx_k if mx_v is None else get_mx_format(mx_v)
    hd = q.shape[-1]
    if impl == "xla":
        kf = mx_dequantize_packed(kp, ks8, mx_k, k=hd)
        vf = mx_dequantize_packed(vp, vs8, mx_v, k=hd)
        return ref.decode_attention_ref(q, kf, vf, lens)
    if tiles == "auto":
        (bq, bk), _ = autotune.attention_tiles(
            "mx_decode", q.shape[0], q.shape[1], kp.shape[1], hd,
            fmt_k=mx_k, fmt_v=mx_v, impl=impl,
            sweep=_tune_sweep_enabled())
    else:
        bq, bk = decode_attention_blocks(q.shape[1], kp.shape[1])
    bq = _compiled_q_tile(q.shape[1], bq, impl)
    return mx_decode_attention_pallas(
        q, kp, ks8, vp, vs8, lens, mx_k=mx_k, mx_v=mx_v,
        block_q=block_q or bq, block_k=block_k or bk,
        interpret=(impl == "pallas_interpret"))


def mx_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *, mx_k,
                       mx_v=None, causal: bool = True, block_q=None,
                       block_k=None, impl: str = "auto",
                       tiles=None) -> jax.Array:
    """Quantized-KV flash attention from high-precision operands:
    ``mx_quantize_kv`` both KV tensors (groups of 32 along hd, E8M0
    scales, packed payloads), then ``mx_flash_attention_packed``.
    q and the online-softmax state stay wide — only the streamed KV
    operands narrow (the forward-path regime of Noune et al.
    2206.02915).  ``tiles='auto'`` passes through to the packed sweep.
    """
    mx_k = get_mx_format(mx_k)
    mx_v = mx_k if mx_v is None else get_mx_format(mx_v)
    kp, ks8 = mx_quantize_kv(k, mx_k, impl=impl)
    vp, vs8 = mx_quantize_kv(v, mx_v, impl=impl)
    return mx_flash_attention_packed(
        q, kp, ks8, vp, vs8, mx_k=mx_k, mx_v=mx_v, causal=causal,
        block_q=block_q, block_k=block_k, impl=impl, tiles=tiles)


def mx_dequantize(q: jax.Array, s: jax.Array, mx) -> jax.Array:
    """``q * s`` per 1×group strip along the last axis (exact for pow2)."""
    mx = get_mx_format(mx)
    return apply_group_scales(q.astype(jnp.float32), s, mx.group)


def mx_dequantize_packed(p: jax.Array, s8: jax.Array, mx, *,
                         k=None) -> jax.Array:
    """Packed payload + E8M0 codes → f32 values: unpack, decode the
    byte grid (exact — pow2; 0xFF → NaN) and rescale per group, slicing
    a group-padded K back to ``k`` when given.  The storage-layer
    inverse of ``mx_quantize(packed=True)``."""
    mx = get_mx_format(mx)
    x = apply_group_scales(mx_unpack(p, mx), e8m0_decode(s8), mx.group)
    return x[..., :k] if k is not None else x


def mx_gemm(a: jax.Array, b: jax.Array, *, mx_a, mx_b=None,
            out_dtype=jnp.float32, impl: str = "auto") -> jax.Array:
    """Fused MX expanding GEMM (DESIGN.md §8).

    Takes *high-precision* ``a[..., M, K]`` / ``b[K, N]``, computes
    per-(row × group-of-32-along-K) E8M0 scales for ``a`` (per
    (group × column) for ``b``), and quantizes into the MX element
    formats inside the GEMM itself; fp32 accumulation, one final
    rounding.  Leading dims of ``a`` are batch: MX scales are per-row, so
    flattening for the Pallas branch never mixes batches.
    """
    impl = resolve_impl(impl)
    mx_a = get_mx_format(mx_a)
    mx_b = mx_a if mx_b is None else get_mx_format(mx_b)
    g = mx_a.group
    assert mx_b.group == g, (mx_a.name, mx_b.name)
    *lead, m, k = a.shape
    _, n = b.shape
    bm, bn, bk = mx_blocks(m, n, k, g)
    a = _pad_last2(a, bm, bk)
    b = _pad2(b, bk, bn)
    sa = compute_group_scales(a, g, mx_a.elem.max_normal)
    sb = compute_group_scales(b.T, g, mx_b.elem.max_normal).T
    if impl == "xla":
        out = ref.mx_gemm_ref(a, b, sa, sb, mx_a=mx_a, mx_b=mx_b,
                              out_dtype=out_dtype)
    else:
        mp, kp = a.shape[-2], a.shape[-1]
        # scales enter the kernel at element resolution (compact grids
        # would put a 4-lane axis on the scale tiles — compiled-TPU
        # illegal); the expansion is exact, f32, emulation-path only
        sae = expand_group_scales(sa.reshape(-1, sa.shape[-1]), g)
        sbe = expand_group_scales(sb.T, g).T
        out = mx_gemm_pallas(
            a.reshape(-1, kp), b, sae, sbe,
            mx_a=mx_a, mx_b=mx_b, out_dtype=out_dtype,
            block_m=bm, block_n=bn, block_k=bk,
            interpret=(impl == "pallas_interpret"))
        out = out.reshape(*lead, mp, out.shape[-1])
    return out[..., :m, :n]


def _ceil_mult(dim: int, unit: int = 8) -> int:
    """Smallest block size for a dim smaller than the configured block:
    round the dim up to ``unit``.  Sublane axes use the default 8; lane
    axes (the last dim of any operand tile) must pass ``unit=128`` —
    compiled TPU Pallas rejects lane tiles that are not 128-multiples
    (masked on CPU CI because the xla/interpret impls accept them)."""
    return max(unit, dim + (-dim) % unit)


@functools.partial(jax.jit, static_argnames=("q_dtype", "margin"))
def quantize_tensor(x: jax.Array, q_dtype, margin: float = 1.0):
    """Per-tensor scaled quantization (classic FP8 recipe, XLA-fused).

    Returns (q, scale) with x ~= q.astype(f32) * scale.

    A non-finite amax (any ``inf``/``NaN`` element) gets scale 1 so the
    poison propagates through quantize → dequant to the output — an
    ``inf`` scale would silently flush the whole tensor to zero.
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf))
    max_normal = jnp.float32(jnp.finfo(q_dtype).max)
    s = jnp.where((amax > 0) & jnp.isfinite(amax),
                  amax / (max_normal * margin), 1.0)
    return (xf / s).astype(q_dtype), s


def quantize_blockwise(x: jax.Array, q_dtype, *, block_m=128, block_n=128,
                       margin: float = 1.0, impl: str = "auto"):
    """Per-block scaled quantization. Returns (q[M,N], scales[gm,gn])."""
    impl = resolve_impl(impl)
    m, n = x.shape
    if impl == "xla":
        x = _pad2(x, block_m, block_n)
        q, s = ref.quant_blockwise_ref(x, q_dtype=q_dtype, block_m=block_m,
                                       block_n=block_n, margin=margin)
        return q[:m, :n], s
    # the kernel pads ragged shapes itself and slices the payload back
    return quant_blockwise_pallas(x, q_dtype=q_dtype, block_m=block_m,
                                  block_n=block_n, margin=margin,
                                  interpret=(impl == "pallas_interpret"))


@functools.partial(jax.jit, static_argnames=("block_m", "block_n"))
def dequantize_blockwise(q: jax.Array, s: jax.Array, *, block_m=128,
                         block_n=128) -> jax.Array:
    m, n = q.shape
    qp = _pad2(q.astype(jnp.float32), block_m, block_n)
    gm, gn = qp.shape[0] // block_m, qp.shape[1] // block_n
    xb = qp.reshape(gm, block_m, gn, block_n) * s[:, None, :, None]
    return xb.reshape(qp.shape)[:m, :n]
