"""Fused block-scaled ExSdotp GEMM — Pallas TPU kernel (DESIGN.md §3).

The per-tensor pipeline costs an extra HBM round-trip: quantize writes
``q`` (and re-reads ``x``), then the GEMM reads ``q`` again.  Here the
cast happens *inside* the GEMM kernel: high-precision (fp32/bf16) tiles
stream HBM→VMEM once, are divided by their per-block scale and cast to
the minifloat format in VMEM, multiplied on the MXU, and the partial
product is rescaled by ``sa * sb`` into the fp32 accumulator.  The
quantized tensor never exists in HBM.

Scales are precomputed per (row-tile × K-tile) by
``core.scaling.compute_block_scales`` — a tiny reduce, grid-mapped into
SMEM so each (i, j, k) step reads exactly the two scalars it needs.
Because the rescale is applied at *accumulator granularity* (once per
K-tile partial product, inside the fp32 accumulator), the ExSdotp
structure of eq. 1 is preserved per block: multiply narrow, accumulate
wide across the whole K loop, round once on the final write.

With pow2 scales (the default) the divide and the rescale are exact, so
the only rounding anywhere is (a) the mantissa cast into the minifloat
format and (b) the single final downcast — the same two roundings the
paper's hardware performs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.formats import _quantize_f32, e8m0_decode, get_mx_format
from .codec import get_codec

__all__ = ["blockscale_gemm_pallas", "mx_gemm_pallas",
           "mx_gemm_packed_pallas"]


def _kernel(a_ref, b_ref, sa_ref, sb_ref, o_ref, acc_ref,
            *, q_dtype_a, q_dtype_b):
    """One (i, j, k) grid step of the fused quantize+GEMM.

    acc += dequant(cast(A_ik / sa), cast(B_kj / sb)) with the per-block
    rescale ``sa * sb`` folded into the accumulator update; single
    rounding into ``o_ref.dtype`` on the last K step.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    sa = sa_ref[0, 0]
    sb = sb_ref[0, 0]
    # quantize in VMEM: one scale per (block_m, block_k) / (block_k,
    # block_n) tile — the CAST unit fused into the GEMM's stream
    aq = (a_ref[...].astype(jnp.float32) / sa).astype(q_dtype_a)
    bq = (b_ref[...].astype(jnp.float32) / sb).astype(q_dtype_b)
    # expanding multiply + per-block dequant at accumulator granularity
    acc_ref[...] += jnp.dot(
        aq.astype(jnp.float32), bq.astype(jnp.float32),
        preferred_element_type=jnp.float32) * (sa * sb)

    @pl.when(k == pl.num_programs(2) - 1)
    def _write():
        # the single rounding of the whole per-output-tile ExSdotp chain
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("q_dtype_a", "q_dtype_b", "out_dtype",
                     "block_m", "block_n", "block_k", "scale_block_m",
                     "scale_block_n", "scale_block_k", "interpret"))
def blockscale_gemm_pallas(a: jax.Array, b: jax.Array,
                           sa: jax.Array, sb: jax.Array, *,
                           q_dtype_a, q_dtype_b, out_dtype=jnp.float32,
                           block_m: int = 128, block_n: int = 128,
                           block_k: int = 128,
                           scale_block_m=None, scale_block_n=None,
                           scale_block_k=None,
                           interpret: bool = False) -> jax.Array:
    """C = downcast(sum_k (A_ik/sa→q)·(B_kj/sb→q) · sa·sb), fp32 accum.

    ``a[M, K]``/``b[K, N]`` are high-precision (fp32/bf16) operands;
    ``sa[M/sm, K/sk]``/``sb[K/sk, N/sn]`` are per-block dequant scales
    (f32, from ``core.scaling.compute_block_scales``).

    Tile-legality contract (DESIGN.md §3/§14): shapes must be multiples
    of the compute tiles (``ops.py`` pads); ``block_m`` is a sublane
    8-multiple while ``block_n``/``block_k`` land on lane axes and must
    be 128-multiples on compiled TPU (interp/CPU CI masks violations —
    the ``ops.blockscale_blocks`` convention).  The *scale* blocks
    ``scale_block_*`` (default: the compute tiles — the original
    kernel) may be coarser than the compute tiles as long as each
    compute tile sits inside exactly one scale block (``sm % bm == 0``
    etc., so every (i, kk) step still reads one scalar per operand from
    SMEM): that is how the §14 autotuner sweeps compute tiles without
    touching the scale-granularity numerics contract.
    """
    sm = block_m if scale_block_m is None else scale_block_m
    sn = block_n if scale_block_n is None else scale_block_n
    sk = block_k if scale_block_k is None else scale_block_k
    assert sm % block_m == 0 and sn % block_n == 0 and sk % block_k == 0, (
        (sm, sn, sk), (block_m, block_n, block_k))
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        (m, n, k), (block_m, block_n, block_k))
    assert m % sm == 0 and n % sn == 0 and k % sk == 0, ((m, n, k),
                                                        (sm, sn, sk))
    assert sa.shape == (m // sm, k // sk), (sa.shape, (m // sm, k // sk))
    assert sb.shape == (k // sk, n // sn), (sb.shape, (k // sk, n // sn))
    grid = (m // block_m, n // block_n, k // block_k)
    kern = functools.partial(_kernel, q_dtype_a=jnp.dtype(q_dtype_a),
                             q_dtype_b=jnp.dtype(q_dtype_b))
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, 1),
                         lambda i, j, kk: (i * block_m // sm,
                                           kk * block_k // sk),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1),
                         lambda i, j, kk: (kk * block_k // sk,
                                           j * block_n // sn),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b, sa.astype(jnp.float32), sb.astype(jnp.float32))


# ----------------------------------------------------------------- MX ------
# Same fused structure at MX granularity (DESIGN.md §8).  Scales enter
# at *element resolution* (sae[M, K], sbe[K, N] — each group's scale
# pre-broadcast over its 32 elements): compact (M, K/32) grids would put
# a 4-lane axis on the scale tiles, which compiled TPU Pallas rejects
# (lane dims must be 128-multiples — the blockscale_blocks rule; masked
# on CPU CI).  The f32 expansion costs emulation-path bandwidth only; a
# production kernel would carry packed E8M0 bytes.  Because E8M0 scales
# are powers of two, multiplying the *elements* by their group scale
# before the MXU dot is bit-identical to rescaling each group's partial
# product after it: per-group dequant at accumulator granularity with no
# per-group inner loop.

def _mx_kernel(a_ref, b_ref, sae_ref, sbe_ref, o_ref, acc_ref,
               *, fmt_a, fmt_b):
    """One (i, j, k) grid step of the fused MX quantize+GEMM.

    acc += dequant(cast(A/sa), cast(B/sb)) with each element carrying its
    own group's exact pow2 rescale into the f32 accumulator; a NaN (E8M0
    0xFF) group scale poisons exactly that group's contributions.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    sae = sae_ref[...]
    sbe = sbe_ref[...]
    # quantize in VMEM: value-space element cast (bit-identical to the
    # native cast where one exists; FP6/FP4 have none)
    aq = _quantize_f32(a_ref[...].astype(jnp.float32) / sae, fmt_a)
    bq = _quantize_f32(b_ref[...].astype(jnp.float32) / sbe, fmt_b)
    # per-group dequant folded into the operands: exact for pow2 scales,
    # so the accumulator sees each partial product rescaled by its own
    # group's sa*sb — eq. 1's structure per 32-element strip
    acc_ref[...] += jnp.dot(aq * sae, bq * sbe,
                            preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _write():
        # the single rounding of the whole per-output-tile ExSdotp chain
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("mx_a", "mx_b", "out_dtype",
                     "block_m", "block_n", "block_k", "interpret"))
def mx_gemm_pallas(a: jax.Array, b: jax.Array,
                   sae: jax.Array, sbe: jax.Array, *,
                   mx_a, mx_b=None, out_dtype=jnp.float32,
                   block_m: int = 128, block_n: int = 128,
                   block_k: int = 128,
                   interpret: bool = False) -> jax.Array:
    """C = downcast(sum_k (A/sa→elem)·(B/sb→elem) · sa·sb), fp32 accum.

    ``a[M, K]``/``b[K, N]`` are high-precision operands; ``sae[M, K]``/
    ``sbe[K, N]`` are the per-(row × K-group) / (K-group × column) E8M0
    scales broadcast to element resolution (f32, from
    ``core.scaling.compute_group_scales`` + ``apply_group_scales``-style
    repeat — ``ops.mx_gemm`` prepares them).

    Tile-legality contract (DESIGN.md §8/§14): shapes must be multiples
    of the block sizes and ``block_k`` a multiple of the group
    (``ops.mx_gemm`` pads); on compiled TPU ``block_m`` is a sublane
    8-multiple and ``block_n``/``block_k`` lane 128-multiples
    (interp/CPU CI masks violations).  Group scales are a property of
    the operands, not the tiles, so every legal tile choice accumulates
    the same f32 partials in the same order — bitwise-equal output.
    """
    mx_a = get_mx_format(mx_a)
    mx_b = mx_a if mx_b is None else get_mx_format(mx_b)
    g = mx_a.group
    assert mx_b.group == g, (mx_a, mx_b)
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        (m, n, k), (block_m, block_n, block_k))
    assert block_k % g == 0, (block_k, g)
    assert sae.shape == a.shape, (sae.shape, a.shape)
    assert sbe.shape == b.shape, (sbe.shape, b.shape)
    grid = (m // block_m, n // block_n, k // block_k)
    kern = functools.partial(_mx_kernel, fmt_a=mx_a.elem, fmt_b=mx_b.elem)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b, sae.astype(jnp.float32), sbe.astype(jnp.float32))


# --------------------------------------------------------- packed MX ------
# The storage-resident MX GEMM (DESIGN.md §10): operands arrive as the
# *packed* uint8 payloads ``mx_quant_packed_pallas`` emitted, with their
# E8M0 scale codes.  VMEM holds packed bytes (width/8 B per element);
# the unpack + bit-pattern decode happens in-register, per K-tile, right
# next to the E8M0 dequant — ExSdotp's narrow-in/wide-accumulate
# structure, with HBM and VMEM traffic at the format's true width.
# Scale codes enter at element resolution (``sae8[M, K]`` uint8 — the
# compact [M, K/32] grid would be lane-illegal on compiled TPU, and a
# byte is 4x narrower than the f32 expansion the value-path kernel
# carries).  B's payload is stored transposed ([N, K·w/8]: groups run
# along K down each column), so both operands unpack along their lane
# axis and the MXU contracts their last dims.

def _mx_packed_gemm_kernel(ap_ref, bp_ref, sa8_ref, sb8_ref, o_ref, acc_ref,
                           *, codec_a, codec_b):
    """One (i, j, k) grid step of the packed-ref MX GEMM.

    acc += (decode(A_packed) · sa) @ (decode(B_packed) · sb)^T with the
    per-group pow2 rescale folded into the operands (exact — E8M0), f32
    accumulation across the K grid, single rounding on the last step.
    A 0xFF scale code decodes to NaN and poisons exactly its group's
    contributions — §8's convention, straight from the byte grid.
    """
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # in-register unpack + decode + E8M0 dequant: the packed bytes are
    # the only operand representation VMEM ever holds
    av = codec_a.decode_tile(ap_ref[...]) * e8m0_decode(sa8_ref[...])
    bv = codec_b.decode_tile(bp_ref[...]) * e8m0_decode(sb8_ref[...])
    acc_ref[...] += jax.lax.dot_general(
        av, bv, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kk == pl.num_programs(2) - 1)
    def _write():
        # the single rounding of the whole per-output-tile ExSdotp chain
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _mx_packed_gemm_db_kernel(ap_hbm, bp_hbm, sa_hbm, sb_hbm, o_ref,
                              ap_s, bp_s, sa_s, sb_s, acc_ref, sems,
                              *, codec_a, codec_b, block_m, block_n,
                              block_k, nk):
    """One (i, j) output tile of the *double-buffered* packed MX GEMM
    (DESIGN.md §14).

    The K loop runs inside the kernel instead of on the grid: the four
    packed operand streams (A/B payloads + E8M0 code grids) stay in HBM
    (``memory_space=ANY``) and are copied tile-by-tile into two VMEM
    slots with explicit async DMAs — the copy for K-tile ``kk+1`` is
    issued *before* the compute for tile ``kk`` waits on its own copy,
    so the HBM→VMEM stream of the next packed tile overlaps the
    unpack/decode/MXU work of the current one.  Compute order, operands
    and the f32 accumulator update are identical to
    ``_mx_packed_gemm_kernel``'s grid pipeline, so the result is
    bitwise equal (tests/test_autotune.py holds it to that).
    """
    i, j = pl.program_id(0), pl.program_id(1)
    bkb_a = codec_a.packed_cols(block_k)
    bkb_b = codec_b.packed_cols(block_k)

    def dmas(slot, kk):
        """The four HBM→VMEM copies landing K-tile ``kk`` in ``slot``."""
        return (
            pltpu.make_async_copy(
                ap_hbm.at[pl.ds(i * block_m, block_m),
                          pl.ds(kk * bkb_a, bkb_a)],
                ap_s.at[slot], sems.at[0, slot]),
            pltpu.make_async_copy(
                bp_hbm.at[pl.ds(j * block_n, block_n),
                          pl.ds(kk * bkb_b, bkb_b)],
                bp_s.at[slot], sems.at[1, slot]),
            pltpu.make_async_copy(
                sa_hbm.at[pl.ds(i * block_m, block_m),
                          pl.ds(kk * block_k, block_k)],
                sa_s.at[slot], sems.at[2, slot]),
            pltpu.make_async_copy(
                sb_hbm.at[pl.ds(j * block_n, block_n),
                          pl.ds(kk * block_k, block_k)],
                sb_s.at[slot], sems.at[3, slot]),
        )

    for d in dmas(0, 0):                       # warm-up: first tile inbound
        d.start()
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(kk, carry):
        cur = jax.lax.rem(kk, 2)
        nxt = jax.lax.rem(kk + 1, 2)

        @pl.when(kk + 1 < nk)
        def _prefetch():                       # overlap: next tile inbound
            for d in dmas(nxt, kk + 1):
                d.start()

        for d in dmas(cur, kk):                # land the current tile
            d.wait()
        # in-register unpack + decode + E8M0 dequant — same fold point,
        # same accumulation order as the grid-pipelined kernel
        av = codec_a.decode_tile(ap_s[cur]) * e8m0_decode(sa_s[cur])
        bv = codec_b.decode_tile(bp_s[cur]) * e8m0_decode(sb_s[cur])
        acc_ref[...] += jax.lax.dot_general(
            av, bv, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, nk, body, 0)
    # the single rounding of the whole per-output-tile ExSdotp chain
    o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("mx_a", "mx_b", "out_dtype", "block_m", "block_n",
                     "block_k", "double_buffer", "interpret"))
def mx_gemm_packed_pallas(ap: jax.Array, bp: jax.Array,
                          sae8: jax.Array, sbe8: jax.Array, *,
                          mx_a, mx_b=None, out_dtype=jnp.float32,
                          block_m: int = 128, block_n: int = 128,
                          block_k: int = 512,
                          double_buffer: bool = False,
                          interpret: bool = False) -> jax.Array:
    """C = downcast(sum_k decode(A_p)·sa · (decode(B_p)·sb)^T), fp32 accum.

    ``ap[M, K·wa/8]`` / ``bp[N, K·wb/8]`` are packed uint8 payloads (B
    transposed — its groups run along K); ``sae8[M, K]`` / ``sbe8[N, K]``
    are E8M0 scale codes broadcast to element resolution
    (``ops.mx_gemm_packed`` expands the compact grids and pads).

    Tile-legality contract (DESIGN.md §10/§14): shapes must be
    multiples of the blocks; ``block_m`` is a sublane 8-multiple,
    ``block_n`` a lane 128-multiple, and ``block_k`` a multiple of the
    MX group *and* of both codecs' ``lane_unit`` (FP8 → 128, FP4 → 256,
    FP6 → 512 elements), so every packed K-tile is a 128-multiple byte
    run — the floor the §14 autotuner enumerates candidates above.
    Interp/CPU CI masks lane violations, same as every packed kernel.

    ``double_buffer=True`` swaps the grid-pipelined K loop for the
    in-kernel manual-DMA loop (``_mx_packed_gemm_db_kernel``): two VMEM
    slots per operand stream, the next packed tile's HBM→VMEM copy in
    flight while the current one multiplies.  Bitwise identical output
    (same compute order); it needs ≥ 1 K-tile and pays off when the
    K loop is long enough for the copy/compute overlap to matter.
    """
    mx_a = get_mx_format(mx_a)
    mx_b = mx_a if mx_b is None else get_mx_format(mx_b)
    g = mx_a.group
    assert mx_b.group == g, (mx_a, mx_b)
    ca, cb = get_codec(mx_a), get_codec(mx_b)
    m, k = sae8.shape
    n, k2 = sbe8.shape
    assert k == k2, (sae8.shape, sbe8.shape)
    assert ap.shape == (m, ca.packed_cols(k)), (ap.shape, (m, k))
    assert bp.shape == (n, cb.packed_cols(k)), (bp.shape, (n, k))
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        (m, n, k), (block_m, block_n, block_k))
    assert block_k % g == 0, (block_k, g)
    assert block_k % ca.lane_unit == 0 and block_k % cb.lane_unit == 0, (
        block_k, ca.lane_unit, cb.lane_unit)
    grid = (m // block_m, n // block_n, k // block_k)
    bkb_a = ca.packed_cols(block_k)
    bkb_b = cb.packed_cols(block_k)
    if double_buffer:
        nk = k // block_k
        kern = functools.partial(
            _mx_packed_gemm_db_kernel, codec_a=ca, codec_b=cb,
            block_m=block_m, block_n=block_n, block_k=block_k, nk=nk)
        return pl.pallas_call(
            kern,
            grid=(m // block_m, n // block_n),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 4,
            out_specs=pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
            out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
            scratch_shapes=[
                pltpu.VMEM((2, block_m, bkb_a), jnp.uint8),
                pltpu.VMEM((2, block_n, bkb_b), jnp.uint8),
                pltpu.VMEM((2, block_m, block_k), jnp.uint8),
                pltpu.VMEM((2, block_n, block_k), jnp.uint8),
                pltpu.VMEM((block_m, block_n), jnp.float32),
                pltpu.SemaphoreType.DMA((4, 2)),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret,
        )(ap, bp, sae8, sbe8)
    kern = functools.partial(_mx_packed_gemm_kernel, codec_a=ca, codec_b=cb)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, bkb_a), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_n, bkb_b), lambda i, j, kk: (j, kk)),
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_n, block_k), lambda i, j, kk: (j, kk)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(ap, bp, sae8, sbe8)
