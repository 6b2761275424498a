"""Payload codecs — one narrow-storage layer from HBM to the MACs
(DESIGN.md §10).

A ``PayloadCodec`` describes, for one element format, everything the
rest of the stack needs to keep payloads *packed* end to end:

* the storage dtype (always uint8 lanes) and the packed-shape math
  (``packed_cols`` / ``logical_cols`` / ``pack_align``);
* the compiled-TPU lane-legality unit (``lane_unit``): the smallest
  K-tile, in elements, whose packed byte run is a 128-multiple — the
  tile floor every packed Pallas ref must respect;
* the codec itself, implemented twice and cross-tested bit for bit:
  a numpy oracle (``encode_pack_np`` / ``unpack_decode_np``, built on
  ``core.formats.encode_np``/``decode_np`` + ``kernels.pack``'s layout
  oracles) and **Pallas-inlinable lane ops** (``encode_lanes`` /
  ``decode_lanes`` / ``pack_lanes`` / ``unpack_lanes``) — pure jnp
  shifts/masks/bitcasts with no data-dependent shapes, so the same
  functions run at the XLA level *and* inside Pallas kernel bodies,
  where they are the in-register unpack/decode sitting next to the
  E8M0 dequant (ExSdotp's narrow-in / wide-accumulate structure).

This is the single place the packed layout is interpreted: the packed
quantize kernel (``kernels/quant.py``), the packed GEMM kernel
(``kernels/blockscale_gemm.py``), the storage wrappers
(``kernels/ops.py``) and the TP wire (``parallel/tp_gemm.py``) all
route through a codec instead of open-coding pack/encode calls, so a
future format (INT4 groups, two-level scales) lands as one codec + one
policy entry rather than another kernel fork.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..core import formats as F
from . import pack as packlib

__all__ = ["PayloadCodec", "get_codec"]


@dataclasses.dataclass(frozen=True)
class PayloadCodec:
    """Bit-pattern codec for one :class:`~repro.core.formats.MiniFloatFormat`.

    Hashable (frozen over a frozen format), so it can close over Pallas
    kernels and ride jit static arguments.
    """

    fmt: F.MiniFloatFormat

    # ---- shape math --------------------------------------------------
    @property
    def width(self) -> int:
        return self.fmt.width

    @property
    def pack_align(self) -> int:
        """Element-count multiple a packed run must be (FP4: 2, FP6: 4,
        byte-wide: 1) — one "word" of the packed stream."""
        return self.fmt.pack_align

    @property
    def word_bytes(self) -> int:
        """Bytes per packed word (FP4: 1, FP6: 3, FP8: 1)."""
        return self.pack_align * self.width // 8

    @property
    def elems_per_word(self) -> int:
        return self.pack_align

    @property
    def storage_dtype(self):
        """Packed payloads are always dense uint8 lanes."""
        return jnp.dtype(jnp.uint8)

    @property
    def lane_unit(self) -> int:
        """Smallest K-tile (in elements) whose packed byte run is a legal
        compiled-TPU lane tile: ``unit * width / 8`` must be a multiple
        of 128 (FP8 → 128, FP4 → 256, FP6 → 512).  Interp/CPU CI masks
        violations — same convention as ``ops.blockscale_blocks``."""
        return 8 * 128 // math.gcd(self.width, 8)

    def packed_cols(self, k: int) -> int:
        """Bytes holding ``k`` codes (``k`` must be pack-aligned)."""
        assert k % self.pack_align == 0, (k, self.pack_align)
        return k * self.width // 8

    def logical_cols(self, nbytes: int) -> int:
        """Elements held by ``nbytes`` packed bytes."""
        assert (nbytes * 8) % self.width == 0, (nbytes, self.width)
        return nbytes * 8 // self.width

    def pad_cols(self, k: int) -> int:
        """``k`` rounded up to the pack alignment."""
        return k + (-k) % self.pack_align

    # ---- numpy oracle ------------------------------------------------
    def encode_pack_np(self, values: np.ndarray) -> np.ndarray:
        """Values → fmt bit patterns → densely packed uint8 bytes."""
        codes = F.encode_np(values, self.fmt).astype(np.uint8)
        return packlib.pack_codes_np(codes, self.width)

    def unpack_decode_np(self, payload: np.ndarray) -> np.ndarray:
        """Packed uint8 bytes → fmt bit patterns → float values."""
        codes = packlib.unpack_codes_np(payload, self.width)
        return F.decode_np(codes, self.fmt)

    # ---- Pallas-inlinable lane ops (also jit-safe at the XLA level) --
    def pack_lanes(self, codes: jax.Array) -> jax.Array:
        """uint8 codes ``[..., K]`` → packed bytes ``[..., K·w/8]``."""
        return packlib.pack_codes(codes, self.width)

    def unpack_lanes(self, payload: jax.Array) -> jax.Array:
        """Packed bytes ``[..., B]`` → uint8 codes ``[..., 8B/w]``."""
        return packlib.unpack_codes(payload, self.width)

    def encode_lanes(self, values: jax.Array) -> jax.Array:
        """f32 values ``[..., K]`` → packed bytes ``[..., K·w/8]``.

        Quantizes to the representable set first (idempotent on already
        representable values), so it is safe directly on ``x / s``
        inside the fused quantize kernel.  Bit-identical to
        ``encode_pack_np``."""
        return self.pack_lanes(F.encode(values, self.fmt))

    def decode_lanes(self, payload: jax.Array) -> jax.Array:
        """Packed bytes → f32 values; exact inverse of ``encode_lanes``
        for every representable value.  Bit-identical to
        ``unpack_decode_np``."""
        return F.decode(self.unpack_lanes(payload), self.fmt)

    # ---- the same codec inside a compiled kernel (2-D tiles) ----------
    def encode_tile(self, values: jax.Array) -> jax.Array:
        """``encode_lanes`` for a ``[rows, K]`` kernel tile: the pack
        step routes bit fields with selection matmuls
        (``pack.pack_codes_tile``), which Mosaic lowers where the
        strided lane slices of ``pack_lanes`` do not.  Bit-identical."""
        return packlib.pack_codes_tile(F.encode(values, self.fmt),
                                       self.width)

    def decode_tile(self, payload: jax.Array) -> jax.Array:
        """``decode_lanes`` for a ``[rows, B]`` kernel tile (see
        ``encode_tile``).  Bit-identical."""
        return F.decode(packlib.unpack_codes_tile(payload, self.width),
                        self.fmt)

    def __str__(self) -> str:  # pragma: no cover - cosmetic
        return (f"codec({self.fmt.name}: {self.elems_per_word} elems / "
                f"{self.word_bytes} B)")


_CODECS: dict[str, PayloadCodec] = {}


def get_codec(fmt) -> PayloadCodec:
    """Codec for a format / MX format / name (width ≤ 8 — the packable
    set); instances are cached so identity works as a jit static arg."""
    if isinstance(fmt, PayloadCodec):
        return fmt
    if isinstance(fmt, F.MXFormat):
        fmt = fmt.elem
    fmt = F.get_format(fmt)
    assert fmt.width <= 8, f"no packed codec for {fmt}"
    c = _CODECS.get(fmt.name)
    if c is None:
        c = _CODECS[fmt.name] = PayloadCodec(fmt)
    return c
