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

Tiling: (bm, bk) x (bk, bn) blocks, 128-aligned for the 128x128 MXU.
Default bk is 512 for 1-byte sources / 256 for 2-byte sources, keeping the
working set (A + B + acc + out) under ~0.5 MiB of VMEM, far below the
16 MiB/core budget, leaving room for double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.formats import decode

__all__ = ["exsdotp_gemm_pallas", "default_blocks"]


def default_blocks(m: int, n: int, k: int, src_bytes: int) -> tuple[int, int, int]:
    """MXU-aligned block sizes; shrink to the problem if it is small."""
    bm = min(128, m)
    bn = min(128, n)
    bk = min(512 // src_bytes * 1 if src_bytes == 1 else 256, k)
    # blocks must divide padded dims; ops.py pads to multiples.
    return bm, bn, max(bk, 1)


def _widen(tile, fmt):
    """Minifloat tile → f32.  ``fmt`` names the format of a ``uint8``
    tile holding raw bit patterns (decoded in-register, exactly, specials
    included); ``None`` means the dtype is native and casts directly."""
    if fmt is None:
        return tile.astype(jnp.float32)
    return decode(tile, fmt)


def _kernel(a_ref, b_ref, scale_ref, o_ref, acc_ref, *, fmt_a, fmt_b):
    """One (i, j, k) grid step: acc += A_ik @ B_kj (fp32), write on last k."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # expanding multiply: decode the minifloat tiles into the wide datapath
    a = _widen(a_ref[...], fmt_a)
    b = _widen(b_ref[...], fmt_b)
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
    narrow, accumulate f32 across the K grid, round once.

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
    grid = (m // block_m, n // block_n, k // block_k)
    return pl.pallas_call(
        functools.partial(_kernel, fmt_a=fmt_a, fmt_b=fmt_b),
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
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b, jnp.asarray(scale, jnp.float32).reshape(1, 1))
