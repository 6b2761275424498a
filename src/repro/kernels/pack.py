"""Packed sub-byte payload storage (DESIGN.md §9).

The MX emulation (§8) keeps FP6/FP4 element *values* in f32 carriers —
fine for numerics, useless as a memory/bandwidth model.  This module is
the honest storage layer: element bit patterns (``core.formats.encode``)
pack densely into uint8 lanes, so an FP4 tensor really is two elements
per byte and an FP6 tensor four elements in three bytes — the byte
counts the paper's 8-bit-end-to-end story (and `launch/hlo_analysis`'s
fractional element sizes) are calibrated against.

Bit layout is little-endian within a lane: element ``i``'s code occupies
bits ``[i*w, (i+1)*w)`` of the ``ceil(K*w/8)``-byte run, matching the
OCP MX convention of packing along the contiguous (K) axis.  numpy
oracles (``*_np``) define the layout; the jnp versions are bit-identical
and jit-safe (pure uint8 shifts/ors — XLA fuses them into the
surrounding quantize/dequantize).

FP4 lane (2 codes/byte)::

    byte0 = c0 | c1 << 4

FP6 lane (4 codes / 3 bytes)::

    byte0 = c0       | (c1 & 0x03) << 6
    byte1 = c1 >> 2  | (c2 & 0x0f) << 4
    byte2 = c2 >> 4  |  c3         << 2
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["pack_codes_np", "unpack_codes_np", "pack_codes", "unpack_codes",
           "pack_codes_tile", "unpack_codes_tile",
           "pack4_np", "unpack4_np", "pack6_np", "unpack6_np",
           "pack4", "unpack4", "pack6", "unpack6", "packed_length"]


def packed_length(k: int, width: int) -> int:
    """Bytes holding ``k`` codes of ``width`` bits (k must tile whole
    bytes: k % 2 == 0 for FP4, k % 4 == 0 for FP6)."""
    assert (k * width) % 8 == 0, (k, width)
    return k * width // 8


# ------------------------------------------------------------- numpy ------

def pack4_np(codes: np.ndarray) -> np.ndarray:
    """[..., K] 4-bit codes -> [..., K/2] bytes (K even)."""
    c = np.asarray(codes).astype(np.uint8)
    assert c.shape[-1] % 2 == 0, c.shape
    return (c[..., 0::2] | (c[..., 1::2] << 4)).astype(np.uint8)


def unpack4_np(packed: np.ndarray) -> np.ndarray:
    """[..., B] bytes -> [..., 2B] 4-bit codes."""
    p = np.asarray(packed).astype(np.uint8)
    out = np.stack([p & 0x0F, p >> 4], axis=-1)
    return out.reshape(*p.shape[:-1], 2 * p.shape[-1])


def pack6_np(codes: np.ndarray) -> np.ndarray:
    """[..., K] 6-bit codes -> [..., 3K/4] bytes (K % 4 == 0)."""
    c = np.asarray(codes).astype(np.uint16)
    assert c.shape[-1] % 4 == 0, c.shape
    c0, c1, c2, c3 = (c[..., i::4] for i in range(4))
    b0 = c0 | (c1 & 0x03) << 6
    b1 = (c1 >> 2) | (c2 & 0x0F) << 4
    b2 = (c2 >> 4) | c3 << 2
    out = np.stack([b0, b1, b2], axis=-1)
    return out.reshape(*c.shape[:-1], 3 * c.shape[-1] // 4).astype(np.uint8)


def unpack6_np(packed: np.ndarray) -> np.ndarray:
    """[..., B] bytes (B % 3 == 0) -> [..., 4B/3] 6-bit codes."""
    p = np.asarray(packed).astype(np.uint16)
    assert p.shape[-1] % 3 == 0, p.shape
    b = p.reshape(*p.shape[:-1], p.shape[-1] // 3, 3)
    b0, b1, b2 = b[..., 0], b[..., 1], b[..., 2]
    c0 = b0 & 0x3F
    c1 = (b0 >> 6) | (b1 & 0x0F) << 2
    c2 = (b1 >> 4) | (b2 & 0x03) << 4
    c3 = b2 >> 2
    out = np.stack([c0, c1, c2, c3], axis=-1)
    return out.reshape(*p.shape[:-1], 4 * p.shape[-1] // 3).astype(np.uint8)


def pack_codes_np(codes: np.ndarray, width: int) -> np.ndarray:
    if width == 8:
        return np.asarray(codes).astype(np.uint8)
    return {4: pack4_np, 6: pack6_np}[width](codes)


def unpack_codes_np(packed: np.ndarray, width: int) -> np.ndarray:
    if width == 8:
        return np.asarray(packed).astype(np.uint8)
    return {4: unpack4_np, 6: unpack6_np}[width](packed)


# --------------------------------------------------------------- jnp ------

def pack4(codes: jax.Array) -> jax.Array:
    """jnp mirror of ``pack4_np`` (bit-identical)."""
    c = codes.astype(jnp.uint8)
    assert c.shape[-1] % 2 == 0, c.shape
    return c[..., 0::2] | (c[..., 1::2] << 4)


def unpack4(packed: jax.Array) -> jax.Array:
    p = packed.astype(jnp.uint8)
    out = jnp.stack([p & 0x0F, p >> 4], axis=-1)
    return out.reshape(*p.shape[:-1], 2 * p.shape[-1])


def pack6(codes: jax.Array) -> jax.Array:
    """jnp mirror of ``pack6_np`` (bit-identical)."""
    c = codes.astype(jnp.uint16)
    assert c.shape[-1] % 4 == 0, c.shape
    c0, c1, c2, c3 = (c[..., i::4] for i in range(4))
    b0 = c0 | (c1 & 0x03) << 6
    b1 = (c1 >> 2) | (c2 & 0x0F) << 4
    b2 = (c2 >> 4) | c3 << 2
    out = jnp.stack([b0, b1, b2], axis=-1)
    return out.reshape(*c.shape[:-1], 3 * c.shape[-1] // 4).astype(jnp.uint8)


def unpack6(packed: jax.Array) -> jax.Array:
    p = packed.astype(jnp.uint16)
    assert p.shape[-1] % 3 == 0, p.shape
    b = p.reshape(*p.shape[:-1], p.shape[-1] // 3, 3)
    b0, b1, b2 = b[..., 0], b[..., 1], b[..., 2]
    c0 = b0 & 0x3F
    c1 = (b0 >> 6) | (b1 & 0x0F) << 2
    c2 = (b1 >> 4) | (b2 & 0x03) << 4
    c3 = b2 >> 2
    out = jnp.stack([c0, c1, c2, c3], axis=-1)
    return out.reshape(*p.shape[:-1], 4 * p.shape[-1] // 3).astype(jnp.uint8)


def pack_codes(codes: jax.Array, width: int) -> jax.Array:
    if width == 8:
        return codes.astype(jnp.uint8)
    return {4: pack4, 6: pack6}[width](codes)


def unpack_codes(packed: jax.Array, width: int) -> jax.Array:
    if width == 8:
        return packed.astype(jnp.uint8)
    return {4: unpack4, 6: unpack6}[width](packed)


# ------------------------------------------------ in-kernel (Mosaic) ------
# Compiled TPU Pallas cannot gather lanes with a stride (``c[..., 0::2]``)
# nor reshape a tile's lane axis, which is what the jnp mirrors above
# lower to.  Inside kernels the same little-endian layout is produced
# by 0/1 selection matmuls on the MXU instead: every code splits into
# the bits that land in its first byte and the bits that spill into the
# next, and a matmul routes each piece to its byte.  Operands are small
# integers (< 256, exact in bf16) and each output sums disjoint bit
# fields, so the f32 accumulation is exact — bit-identical to the
# strided versions.  Tiles are processed in runs of ``lane_unit`` codes
# (whole 128-byte lane tiles), so the selection matrices stay small.

def _tile_run(n: int, width: int) -> int:
    unit = 8 * 128 // math.gcd(width, 8)   # codes per 128-byte multiple
    return unit if n % unit == 0 else n


def _field_shift(shape, width: int, axis: int):
    """Bit offset, within its first byte, of the code on each lane."""
    k = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    return (k * width) % 8


def _select(shape, width: int, code_axis: int, spill: int):
    """0/1 bf16 matrix routing code ``k`` to byte ``(k·w)//8 + spill``."""
    k = jax.lax.broadcasted_iota(jnp.int32, shape, code_axis)
    j = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - code_axis)
    return (j == (k * width) // 8 + spill).astype(jnp.bfloat16)


def _route(x, sel):
    y = jnp.dot(x.astype(jnp.float32).astype(jnp.bfloat16), sel,
                preferred_element_type=jnp.float32)
    return y.astype(jnp.int32)


def pack_codes_tile(codes: jax.Array, width: int) -> jax.Array:
    """``pack_codes`` for a 2-D kernel tile ``[rows, K]`` (uint8 codes →
    ``[rows, K·w/8]`` bytes), built from shifts and selection matmuls."""
    if width == 8:
        return codes.astype(jnp.uint8)
    rows, n = codes.shape
    run = _tile_run(n, width)
    nb = run * width // 8
    sh = _field_shift((rows, run), width, 1)
    lo = _select((run, nb), width, 0, 0)
    hi = _select((run, nb), width, 0, 1)
    out = []
    for i in range(n // run):
        c = codes[:, i * run:(i + 1) * run].astype(jnp.int32)
        b = _route((c << sh) & 0xFF, lo) + _route(c >> (8 - sh), hi)
        out.append(b.astype(jnp.uint32).astype(jnp.uint8))
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=-1)


def unpack_codes_tile(packed: jax.Array, width: int) -> jax.Array:
    """``unpack_codes`` for a 2-D kernel tile ``[rows, B]`` (bytes →
    ``[rows, 8B/w]`` uint8 codes): each code gathers its first byte and
    the next by selection matmuls, then shifts and masks."""
    if width == 8:
        return packed.astype(jnp.uint8)
    rows, nb = packed.shape
    run = _tile_run(nb * 8 // width, width)
    rb = run * width // 8
    sh = _field_shift((rows, run), width, 1)
    lo = _select((rb, run), width, 1, 0)
    hi = _select((rb, run), width, 1, 1)
    out = []
    for i in range(nb // rb):
        b = packed[:, i * rb:(i + 1) * rb].astype(jnp.int32)
        c = ((_route(b, lo) >> sh) | (_route(b, hi) << (8 - sh))) & (
            (1 << width) - 1)
        out.append(c.astype(jnp.uint32).astype(jnp.uint8))
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=-1)
