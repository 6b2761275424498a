"""ExSdotp GEMM — Pallas TPU kernel (the SIMD ExSdotp unit writ MXU-large).

Mapping of the paper's unit onto the TPU memory/compute hierarchy
(DESIGN.md §2):

  * narrow source operands (fp8/fp8alt/fp16/fp16alt) live in HBM and are
    streamed tile-by-tile into VMEM — the paper's register-file-packing win
    (Fig. 2) becomes a 2x HBM-bandwidth win;
  * the MXU multiplies narrow inputs and accumulates *expanded* into an
    fp32 VMEM scratch accumulator — the paper's e_2w accumulator, kept at
    full width across the whole K loop (a many-term ExSdotp chain with no
    intermediate rounding, i.e. even stronger than eq. 1);
  * the single downcast on the final K step is the unit's one
    normalization/rounding stage;
  * BlockSpec index maps play the role of Snitch's SSR streamers and the
    grid that of FREP hardware loops.

Widening: the MXU takes bf16 or f32 operands, so each source tile is
widened in VMEM first.  Every value of fp8 (E5M2) and fp8alt (E4M3) is
exactly a *normal* bf16 (at most 4 significant bits, exponents well
inside bf16's), so those sources, and bf16 itself, widen to bf16 and the
MXU runs one bf16 x bf16 -> f32 pass.  The product of two such values has at most 16
significant bits and is exact in the f32 accumulator: only the f32
summation order differs from an f32 upcast.  fp16 (11 significant bits)
is not exact in bf16 and keeps the f32 upcast (a multi-pass f32 matmul).

Tiling (``default_blocks``): the fixed cost of a grid step (~0.35 us) and
the widening of each operand tile do not scale with MXU work, so tiles are
as large as the shape and a VMEM budget allow — up to 1024 x 1024 output
tiles and 2 KiB of source bytes along K — and each widened A element
feeds ``bn`` MACs, each widened B element ``bm``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import ml_dtypes
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.formats import decode, get_format

__all__ = ["exsdotp_gemm_pallas", "default_blocks", "widen_dtype",
           "vmem_bytes"]

#: M and N tiles, largest first (lane and MXU multiples)
_MN_TILES = (1024, 512, 256, 128)
#: source bytes of one A row (B column) a K tile holds, most and least
_K_TILE_BYTES, _K_TILE_BYTES_MIN = 2048, 512
#: VMEM the tiles may claim (``vmem_bytes``): Mosaic's default scoped
#: limit.  A higher ``vmem_limit_bytes`` would not hold where XLA fuses
#: the kernel with its neighbours (a ``kCustom`` fusion, such as the
#: wgrad's write into the scanned layers' stacked gradient): that fusion
#: gets 16 MiB whatever the kernel asks for
VMEM_BUDGET = 16 << 20
_BF16 = ml_dtypes.finfo(jnp.bfloat16)


def _fits_bf16(dtype, fmt) -> bool:
    """Whether every value of the source is exactly a normal bf16 (or
    the source is bf16 itself)."""
    if jnp.dtype(dtype) == jnp.bfloat16:
        return True
    if fmt is not None:
        f = get_format(fmt)
        man, tiny, big = f.man_bits, f.min_subnormal, f.max_normal
    else:
        fi = ml_dtypes.finfo(dtype)
        man, tiny, big = fi.nmant, float(fi.smallest_subnormal), float(fi.max)
    return (man <= _BF16.nmant and tiny >= float(_BF16.smallest_normal)
            and big <= float(_BF16.max))


def widen_dtype(dtype_a, fmt_a, dtype_b, fmt_b):
    """The MXU operand dtype: bf16 when both sources widen to it
    exactly, else f32."""
    if _fits_bf16(dtype_a, fmt_a) and _fits_bf16(dtype_b, fmt_b):
        return jnp.bfloat16
    return jnp.float32


def vmem_bytes(bm: int, bn: int, bk: int, src_bytes: int, wide_bytes: int,
               out_bytes: int = 4) -> int:
    """VMEM a (bm, bk) x (bk, bn) grid step holds: double-buffered source
    tiles, their widened copies, the f32 accumulator and dot result, and
    the double-buffered output tile."""
    tiles = (bm + bn) * bk
    return (tiles * (2 * src_bytes + wide_bytes)
            + bm * bn * (4 + 4 + 2 * out_bytes))


def _ceil_mult(x: int, m: int) -> int:
    return -(-x // m) * m


def _largest_tile(d: int, tiles) -> int:
    """Largest of ``tiles`` that divides ``d`` (a multiple of the last)."""
    return next(t for t in tiles if d % t == 0)


def default_blocks(m: int, n: int, k: int, src_bytes: int,
                   wide_bytes: int = 2,
                   out_bytes: int = 4) -> tuple[int, int, int]:
    """Tiles for an (m, k) x (k, n) GEMM, from its shape alone.

    N and K land on lane axes: they pad to 128 and take the largest tile
    in ``_MN_TILES`` (N) or of at most ``_K_TILE_BYTES`` source bytes (K)
    that divides the padded size.  M is sublane-only: a small M pads to
    a multiple of 8 and is one tile; a larger one pads to 128 and picks
    like N.  While the tiles overrun ``VMEM_BUDGET``, K shrinks to
    ``_K_TILE_BYTES_MIN`` bytes, then the larger of M and N halves.
    ``ops.exsdotp_gemm`` pads the operands to the blocks.
    """
    lane = _MN_TILES[-1]
    bm = (_ceil_mult(m, 8) if m <= lane
          else _largest_tile(_ceil_mult(m, lane), _MN_TILES))
    bn = _largest_tile(_ceil_mult(n, lane), _MN_TILES)
    k_tiles = tuple(t for t in (2048, 1024, 512, 256, 128)
                    if t * src_bytes <= _K_TILE_BYTES)
    bk = _largest_tile(_ceil_mult(k, lane), k_tiles)
    while vmem_bytes(bm, bn, bk, src_bytes, wide_bytes,
                     out_bytes) > VMEM_BUDGET:
        if bk * src_bytes > _K_TILE_BYTES_MIN:
            bk //= 2
        elif bn >= bm and bn > lane:
            bn //= 2
        elif bm > lane:
            bm //= 2
        else:
            break
    return bm, bn, bk


def _widen_bits_bf16(code, fmt):
    """8-bit IEEE minifloat codes → bf16, exactly, specials included.

    The f32 pattern of a normal value is the code's exponent and
    mantissa fields shifted into place plus the exponent-bias offset; an
    exponent-field-0 code read that way is ``2^-bias + m 2^-(bias+M)``,
    from which the subnormal ``m 2^(1-bias-M)`` follows exactly; the top
    exponent becomes f32's (inf, or NaN with the mantissa kept).  No
    intermediate is an f32 subnormal, so nothing is flushed.
    """
    f = get_format(fmt)
    c = code.astype(jnp.int32)
    mag = c & 0x7F
    shift = 23 - f.man_bits
    top = ((1 << f.exp_bits) - 1) << f.man_bits
    bits = jnp.where(mag >= top, (mag << shift) | 0x7F800000,
                     (mag << shift) + ((127 - f.bias) << 23))
    v = jax.lax.bitcast_convert_type(bits, jnp.float32)
    v = jnp.where(mag < (1 << f.man_bits),
                  (v - jnp.float32(2.0 ** -f.bias)) * 2.0, v)
    v = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(v, jnp.int32) | ((c & 0x80) << 24),
        jnp.float32)
    return v.astype(jnp.bfloat16)


def _widen(tile, fmt, wide):
    """Source tile → ``wide`` (bf16 or f32).  ``fmt`` names the format of
    a ``uint8`` tile holding raw bit patterns (decoded in-register,
    exactly, specials included); ``None`` means the dtype is native and
    casts directly."""
    if fmt is None:
        return tile.astype(wide)
    if wide == jnp.bfloat16:
        return _widen_bits_bf16(tile, fmt)
    return decode(tile, fmt)


def _kernel(a_ref, b_ref, scale_ref, o_ref, acc_ref, *, fmt_a, fmt_b, wide):
    """One (i, j, k) grid step: acc += A_ik @ B_kj (fp32), write on last k."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # expanding multiply: widen the narrow tiles for the MXU
    a = _widen(a_ref[...], fmt_a, wide)
    b = _widen(b_ref[...], fmt_b, wide)
    acc_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _write():
        # single rounding into the destination format (+ dequant rescale)
        o_ref[...] = (acc_ref[...] * scale_ref[0, 0]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("out_dtype", "block_m", "block_n", "block_k", "fmt_a",
                     "fmt_b", "interpret"))
def exsdotp_gemm_pallas(a: jax.Array, b: jax.Array, scale: jax.Array,
                        *, out_dtype=jnp.float32,
                        block_m: int = 128, block_n: int = 128,
                        block_k: int = 512, fmt_a=None, fmt_b=None,
                        interpret: bool = False) -> jax.Array:
    """C[M,N] = downcast(scale * sum_k A[M,K] B[K,N]) with fp32 accumulation.

    ``a``/``b`` may be any narrow dtype XLA can upcast (float8_e5m2,
    float8_e4m3, float16, bfloat16), or ``uint8`` bit patterns of the
    minifloat format named by ``fmt_a``/``fmt_b`` — how ``ops`` ships
    IEEE ``float8_e4m3``, which Mosaic cannot load on v5e (it loads
    ``float8_e4m3fn``/``float8_e5m2``, whose value sets differ from
    fp8alt's).  ``scale`` is a (1,1) f32 dequant factor
    (product of the per-tensor quantization scales), fused into the final
    write — the paper's ExSdotp structure (DESIGN.md §2): multiply
    narrow, accumulate f32 across the K grid, round once.  The tiles
    widen to ``widen_dtype`` of the two sources.

    Tile-legality contract (DESIGN.md §2/§14): shapes must be multiples
    of the blocks (``ops.exsdotp_gemm`` pads); ``block_m`` is a sublane
    8-multiple while ``block_n``/``block_k`` land on lane axes and must
    be 128-multiples on compiled TPU (interp/CPU CI masks violations).
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        (m, n, k), (block_m, block_n, block_k))
    wide = widen_dtype(a.dtype, fmt_a, b.dtype, fmt_b)
    grid = (m // block_m, n // block_n, k // block_k)
    return pl.pallas_call(
        functools.partial(_kernel, fmt_a=fmt_a, fmt_b=fmt_b, wide=wide),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_BUDGET),
        interpret=interpret,
    )(a, b, jnp.asarray(scale, jnp.float32).reshape(1, 1))
