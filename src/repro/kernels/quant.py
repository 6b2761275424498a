"""Minifloat quantization kernels — the CAST unit of the extended FPU.

Three granularities:

* per-tensor: one scale for the whole tensor (classic FP8 recipes; the
  amax reduce runs in XLA, the cast is trivially fused by XLA too);
* per-block (Pallas): each (bm, bn) tile computes its own amax, scale and
  cast in one VMEM pass — a beyond-paper optimization matching how modern
  FP8 training (e.g. 128x128 block scaling) bounds quantization error, and
  the natural granularity for the ExSdotp GEMM's tiles;
* per-group MX (Pallas): groups of 32 consecutive elements along the last
  (contraction) axis share one E8M0 power-of-two scale (DESIGN.md §8) —
  amax, pow2 scale and the value-space element cast all fused in VMEM.

The kernels fuse amax + scale + cast so the tensor is read once from HBM
and written once at a fraction of the bytes: a pure memory-roofline win.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.formats import _quantize_f32, e8m0_encode, get_mx_format
from ..core.scaling import group_scales_from_amax, pow2_reciprocal
from .codec import get_codec

__all__ = ["quant_blockwise_pallas", "mx_quant_pallas",
           "mx_quant_packed_pallas"]


def _kernel(x_ref, q_ref, s_ref, *, max_normal: float, margin: float):
    x = x_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    # dequant scale s: quantized = x / s fills the format's range.
    # Non-finite amax -> scale 1 so inf/NaN propagate to the output
    # instead of an inf scale flushing the whole tile to zero.
    s = jnp.where((amax > 0) & jnp.isfinite(amax),
                  amax / (max_normal * margin), 1.0)
    q_ref[...] = (x / s).astype(q_ref.dtype)
    s_ref[0, 0] = s


@functools.partial(
    jax.jit,
    static_argnames=("q_dtype", "block_m", "block_n", "margin", "interpret"))
def quant_blockwise_pallas(x: jax.Array, *, q_dtype,
                           block_m: int = 128, block_n: int = 128,
                           margin: float = 1.0,
                           interpret: bool = False):
    """Quantize x[M,N] into ``q_dtype`` with one scale per (bm, bn) block.

    Returns (q[M,N], scales[ceil(M/bm), ceil(N/bn)]) with
    x ~= q.astype(f32) * scale broadcast per block.  Non-multiple shapes
    are zero-padded up to block multiples (exact for amax — zeros never
    raise it — and sliced back off the payload; fully-padded blocks get
    the neutral scale 1).  ``margin`` < 1 reserves headroom below
    max_normal.

    Tile-legality contract (DESIGN.md §3/§14): ``block_m`` is a sublane
    8-multiple, ``block_n`` a lane 128-multiple on compiled TPU
    (interp/CPU CI masks violations).  Blocks ARE the scale granularity
    here — changing them changes the quantization, so the §14 autotuner
    never sweeps this kernel's blocks (see ``blockscale_gemm_pallas``'s
    ``scale_block_*`` for how the GEMM side keeps the grid fixed).
    """
    m, n = x.shape
    pm, pn = (-m) % block_m, (-n) % block_n
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)))
    mp, np_ = x.shape
    grid = (mp // block_m, np_ // block_n)
    max_normal = float(jnp.finfo(q_dtype).max)
    kern = functools.partial(_kernel, max_normal=max_normal, margin=margin)
    q, s = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec((block_m, block_n), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (i, j), memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, np_), q_dtype),
            jax.ShapeDtypeStruct(grid, jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x)
    return q[:m, :n], s


# --------------------------------------------------------------- MX path --

def _group_amax_lanes(y: jax.Array, group: int) -> jax.Array:
    """Max of ``y [bm, bk]`` over each aligned 1×``group`` lane strip,
    broadcast back to every lane of the strip.

    An XOR butterfly of lane rolls: after step ``sh`` each lane holds
    the max over the ``2·sh`` lanes sharing its high index bits.  A
    rolled lane iota picks, per lane, whichever roll brought its
    partner ``lane ^ sh`` (so the roll's direction convention does not
    matter).  Mosaic cannot reshape a tile into ``[bm, bk/group,
    group]``; this keeps the reduction on native ``[bm, bk]`` vregs and
    yields the scales already at element resolution.
    """
    n = y.shape[-1]
    ax = y.ndim - 1
    lane = jax.lax.broadcasted_iota(jnp.int32, y.shape, ax)
    sh = 1
    while sh < group:
        fwd, bwd = pltpu.roll(y, sh, ax), pltpu.roll(y, n - sh, ax)
        src = pltpu.roll(lane, sh, ax)
        y = jnp.maximum(y, jnp.where(src == (lane ^ sh), fwd, bwd))
        sh *= 2
    return y


def _group_scales_lanes(x: jax.Array, group: int, elem_max: float):
    """Element-resolution E8M0 scales of ``x [bm, bk]`` (in-kernel
    ``expand_group_scales(compute_group_scales(x))``, bit-identical:
    max is exact in any order, and the scale formula is shared)."""
    assert group & (group - 1) == 0, group
    return group_scales_from_amax(_group_amax_lanes(jnp.abs(x), group),
                                  elem_max)


def _mx_kernel(x_ref, q_ref, se_ref, *, fmt, group: int):
    """Fused MX group quantize for one (bm, bk) tile.

    Per 1×group strip: amax (``_group_amax_lanes``) -> E8M0 pow2 scale
    (non-finite -> NaN scale, zero -> neutral 1, via
    ``group_scales_from_amax`` — the single source of the E8M0 formula)
    -> exact pow2 divide -> value-space element
    cast (`_quantize_f32`, bit-identical to a native cast where one
    exists).  The scale output is written at *element resolution*
    (``se[bm, bk]``): a compact ``(bm, bk//32)`` tile would put a
    4-lane axis on the output — illegal on compiled TPU Pallas (lane
    dims must be 128-multiples; masked on CPU CI) — so the wrapper
    compacts with a strided slice instead.
    """
    x = x_ref[...].astype(jnp.float32)
    se = _group_scales_lanes(x, group, fmt.max_normal)
    q_ref[...] = _quantize_f32(x * pow2_reciprocal(se), fmt)
    se_ref[...] = se


@functools.partial(
    jax.jit,
    static_argnames=("mx", "block_m", "block_k", "interpret"))
def mx_quant_pallas(x: jax.Array, *, mx, block_m: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """Quantize ``x[M, K]`` into the MX format ``mx`` (name or MXFormat).

    Returns ``(q[M, K] f32, scales[M, K/group] f32)``: ``q`` holds the
    element-format values of ``x / s`` (value-space emulation — FP6/FP4
    have no native jnp dtype, so the payload stays f32 on the emulation
    path) and ``s`` the per-(row × group) E8M0 scales.

    Tile-legality contract (DESIGN.md §8/§14): shapes must be multiples
    of the blocks (``ops.mx_quantize`` pads); ``block_k`` must contain
    whole groups, and on compiled TPU ``block_m`` is a sublane
    8-multiple / ``block_k`` a lane 128-multiple (interp/CPU CI masks
    violations).  Scales are per group-of-32 regardless of the tiles,
    so any legal block choice quantizes identically.
    """
    mx = get_mx_format(mx)
    m, k = x.shape
    assert m % block_m == 0 and k % block_k == 0, ((m, k), (block_m, block_k))
    assert block_k % mx.group == 0, (block_k, mx.group)
    grid = (m // block_m, k // block_k)
    kern = functools.partial(_mx_kernel, fmt=mx.elem, group=mx.group)
    q, se = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec((block_m, block_k), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j: (i, j)),
            pl.BlockSpec((block_m, block_k), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, k), jnp.float32),
            jax.ShapeDtypeStruct((m, k), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x)
    # compact the element-resolution scales back to one per group
    return q, se[:, ::mx.group]


# ----------------------------------------------------- packed MX path --

def _mx_packed_kernel(x_ref, p_ref, s8_ref, *, codec, group: int):
    """Fused packed MX quantize for one (bm, bk) tile (DESIGN.md §10).

    Same group amax → E8M0 pow2 scale → exact pow2 divide pipeline as
    ``_mx_kernel``, but the element cast lands straight in *packed*
    uint8 storage: ``codec.encode_tile`` quantizes, extracts the bit
    patterns and packs them into dense lanes in-register, so the
    payload leaves VMEM at ``width/8`` bytes per element — no byte- or
    f32-wide quantized intermediate ever reaches HBM.  Scales are
    written as E8M0 *codes* at element resolution (``s8[bm, bk]``
    uint8; one byte instead of the f32 path's four) for the same
    lane-legality reason as ``_mx_kernel``: a compact ``(bm, bk//32)``
    output tile would be lane-illegal on compiled TPU.  A non-finite
    group encodes scale 0xFF (NaN) and a max-magnitude payload pattern
    — the §8 poison convention, byte-level.
    """
    x = x_ref[...].astype(jnp.float32)
    se = _group_scales_lanes(x, group, codec.fmt.max_normal)
    s8_ref[...] = e8m0_encode(se)
    p_ref[...] = codec.encode_tile(x * pow2_reciprocal(se))


@functools.partial(
    jax.jit,
    static_argnames=("mx", "block_m", "block_k", "interpret"))
def mx_quant_packed_pallas(x: jax.Array, *, mx, block_m: int = 128,
                           block_k: int = 512, interpret: bool = False):
    """Quantize ``x[M, K]`` into *packed* MX storage (DESIGN.md §10).

    Returns ``(payload[M, K·w/8] u8, s8[M, K/group] u8)``: the densely
    packed element bit patterns and the E8M0 scale codes — the honest
    HBM footprint, emitted directly by the kernel.

    Tile-legality contract (DESIGN.md §10/§14): shapes must be
    multiples of the blocks (``ops.mx_quantize`` pads); ``block_k``
    must be a multiple of the group *and* of the codec's ``lane_unit``
    (packed byte runs must be legal 128-multiple lane tiles on compiled
    TPU — FP8: 128, FP4: 256, FP6: 512 elements; masked on CPU CI).
    Group scales are tile-independent, so any legal block choice packs
    identical bytes.
    """
    mx = get_mx_format(mx)
    codec = get_codec(mx)
    m, k = x.shape
    assert m % block_m == 0 and k % block_k == 0, ((m, k), (block_m, block_k))
    assert block_k % mx.group == 0, (block_k, mx.group)
    assert block_k % codec.lane_unit == 0, (block_k, codec.lane_unit)
    grid = (m // block_m, k // block_k)
    bkb = codec.packed_cols(block_k)
    kern = functools.partial(_mx_packed_kernel, codec=codec, group=mx.group)
    p, s8 = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec((block_m, block_k), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((block_m, bkb), lambda i, j: (i, j)),
            pl.BlockSpec((block_m, block_k), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, codec.packed_cols(k)), jnp.uint8),
            jax.ShapeDtypeStruct((m, k), jnp.uint8),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x)
    # compact the element-resolution scale codes back to one per group
    return p, s8[:, ::mx.group]
