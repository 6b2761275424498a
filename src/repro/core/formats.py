"""MiniFloat-NN format system (paper §III-A, Fig. 1).

Parameterized floating-point formats a la FPnew: any (exp_bits, man_bits)
pair defines a format; the paper's six formats are predefined. Two
implementations are provided and cross-tested:

  * a bit-exact *value-space* quantizer in pure JAX (`quantize`) — RNE,
    IEEE subnormals, overflow-to-inf — usable inside jit/pjit/Pallas;
  * exact bit-pattern `encode`/`decode` (numpy + JAX) for storage tests
    and for the integer-datapath ExSdotp oracle.

Native `ml_dtypes` counterparts (used on the performance path, where XLA/TPU
have hardware casts) are attached where they exist; the emulation layer is
authoritative for paper semantics.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

__all__ = [
    "MiniFloatFormat",
    "FP8", "FP8ALT", "FP16", "FP16ALT", "FP32", "FP64",
    "FP6E2M3", "FP6E3M2", "FP4E2M1",
    "FORMATS", "get_format", "quantize", "quantize_np",
    "encode_np", "decode_np", "encode", "decode",
    "MXFormat", "MXFP8E4M3", "MXFP8E5M2", "MXFP6E2M3", "MXFP6E3M2",
    "MXFP4E2M1", "MX_FORMATS", "get_mx_format",
    "E8M0_BIAS", "E8M0_NAN", "e8m0_encode_np", "e8m0_decode_np",
    "e8m0_encode", "e8m0_decode",
    "mx_group_scales_np", "mx_quantize_np", "mx_dequantize_np",
]


@dataclasses.dataclass(frozen=True)
class MiniFloatFormat:
    """An IEEE-754-style binary format with parametric field widths."""

    name: str
    exp_bits: int
    man_bits: int
    #: 'ieee'  -> overflow rounds to +-inf (paper semantics)
    #: 'saturate' -> overflow clamps to +-max_normal ("fn"-style, TPU casts)
    inf_behavior: str = "ieee"
    #: IEEE reserves the top exponent code for inf/NaN.  OCP MX sub-byte
    #: element formats (FP6/FP4) spend it on normals instead: no inf, no
    #: NaN — non-finite values are expressed at the *group* level via the
    #: E8M0 NaN scale.  With ``ieee_specials=False``, overflow (including
    #: true inf) clamps to ±max_normal and a NaN value encodes to the
    #: max-magnitude bit pattern (decode cannot round-trip it; the MX
    #: layer never encodes a NaN element because its group scale is NaN).
    ieee_specials: bool = True

    # ---- derived quantities ----------------------------------------
    @property
    def width(self) -> int:
        return 1 + self.exp_bits + self.man_bits

    @property
    def bias(self) -> int:
        return (1 << (self.exp_bits - 1)) - 1

    @property
    def max_exp(self) -> int:  # unbiased exponent of largest normal
        return (1 << self.exp_bits) - (2 if self.ieee_specials else 1) - self.bias

    @property
    def min_exp(self) -> int:  # unbiased exponent of smallest normal
        return 1 - self.bias

    @property
    def precision(self) -> int:  # p = man_bits + 1 (hidden one); paper's p_src/p_dst
        return self.man_bits + 1

    @property
    def max_normal(self) -> float:
        return float(2.0 ** self.max_exp * (2.0 - 2.0 ** (-self.man_bits)))

    @property
    def min_normal(self) -> float:
        return float(2.0 ** self.min_exp)

    @property
    def min_subnormal(self) -> float:
        return float(2.0 ** (self.min_exp - self.man_bits))

    @property
    def ml_dtype(self) -> Optional[np.dtype]:
        """Native ml_dtypes counterpart, if one exists (exact match)."""
        key = (self.exp_bits, self.man_bits)
        if not self.ieee_specials:
            # OCP "fn" dtypes: no inf/NaN, saturating casts — only present
            # in newer ml_dtypes releases, hence the getattr guards.
            fn_table = {
                (2, 3): getattr(ml_dtypes, "float6_e2m3fn", None),
                (3, 2): getattr(ml_dtypes, "float6_e3m2fn", None),
                (2, 1): getattr(ml_dtypes, "float4_e2m1fn", None),
            }
            t = fn_table.get(key)
            return np.dtype(t) if t is not None else None
        table = {
            (5, 2): np.dtype(ml_dtypes.float8_e5m2),
            (4, 3): np.dtype(ml_dtypes.float8_e4m3),
            (5, 10): np.dtype(np.float16),
            (8, 7): np.dtype(ml_dtypes.bfloat16),
            (8, 23): np.dtype(np.float32),
            (11, 52): np.dtype(np.float64),
        }
        return table.get(key)

    @property
    def storage_dtype(self):
        """jnp dtype used to *store* tensors in this format on the perf path.

        For formats with no native dtype we store uint bit patterns.
        """
        md = self.ml_dtype
        if md is not None:
            return md
        return np.dtype(f"uint{max(8, 1 << (self.width - 1).bit_length())}")

    # ---- packed sub-byte storage (DESIGN.md §9) ---------------------
    @property
    def packed_bytes_per_element(self) -> float:
        """Bytes per element in *packed* storage: ``width / 8``.

        Sub-byte formats pack densely (FP4: two elements per byte, FP6:
        four elements in three bytes — ``kernels/pack.py``), so the
        honest byte accounting is fractional.
        """
        return self.width / 8

    @property
    def pack_align(self) -> int:
        """Element-count multiple a packed run must be: the smallest n
        with ``n * width`` a whole number of bytes (FP4 → 2, FP6 → 4,
        byte-multiples → 1)."""
        n = 1
        while (n * self.width) % 8:
            n += 1
        return n

    def __str__(self) -> str:  # pragma: no cover - cosmetic
        return f"{self.name}(E{self.exp_bits}M{self.man_bits})"


# The paper's formats (Fig. 1 / §III-A). FP16ALT keeps bfloat16 widths but
# full IEEE rounding + subnormals, which ml_dtypes.bfloat16 implements.
FP8 = MiniFloatFormat("fp8", 5, 2)
FP8ALT = MiniFloatFormat("fp8alt", 4, 3)
FP16 = MiniFloatFormat("fp16", 5, 10)
FP16ALT = MiniFloatFormat("fp16alt", 8, 7)
FP32 = MiniFloatFormat("fp32", 8, 23)
FP64 = MiniFloatFormat("fp64", 11, 52)

# OCP MX sub-byte element formats (no inf/NaN; saturating overflow).
# Max normals: E2M3 -> 7.5, E3M2 -> 28, E2M1 -> 6.
FP6E2M3 = MiniFloatFormat("fp6e2m3", 2, 3, inf_behavior="saturate",
                          ieee_specials=False)
FP6E3M2 = MiniFloatFormat("fp6e3m2", 3, 2, inf_behavior="saturate",
                          ieee_specials=False)
FP4E2M1 = MiniFloatFormat("fp4e2m1", 2, 1, inf_behavior="saturate",
                          ieee_specials=False)

FORMATS = {f.name: f for f in (FP8, FP8ALT, FP16, FP16ALT, FP32, FP64,
                               FP6E2M3, FP6E3M2, FP4E2M1)}

#: ExSdotp source->destination pairing (paper Table I): expanding ops double
#: the width. 8-bit formats expand into FP16/FP16alt; 16-bit into FP32.
EXPANDING_DST = {
    "fp8": FP16, "fp8alt": FP16,
    "fp16": FP32, "fp16alt": FP32,
}


def get_format(name) -> MiniFloatFormat:
    if isinstance(name, MiniFloatFormat):
        return name
    return FORMATS[str(name).lower()]


# ---------------------------------------------------------------------------
# Value-space quantization (JAX, bit-exact, jit-safe)
# ---------------------------------------------------------------------------

def _exact_pow2(k: jax.Array) -> jax.Array:
    """2**k as f32, exact, for integer k in [-149, 127] (incl. subnormals).

    jnp.exp2 is an approximation on some backends (CPU XLA returns
    8192.004 for exp2(13)!), so powers of two are built from raw bits.
    """
    k = k.astype(jnp.int32)
    kn = jnp.clip(k, -126, 127)
    bits_norm = ((kn + 127) << 23).astype(jnp.uint32)
    val_norm = jax.lax.bitcast_convert_type(bits_norm, jnp.float32)
    shift = jnp.clip(k + 149, 0, 22).astype(jnp.uint32)
    val_sub = jax.lax.bitcast_convert_type(jnp.uint32(1) << shift, jnp.float32)
    return jnp.where(k < -126, val_sub, val_norm)


def _quantize_f32(x: jax.Array, fmt: MiniFloatFormat) -> jax.Array:
    """Round f32 values to the nearest representable value of ``fmt`` (RNE).

    Pure value-space arithmetic on exact powers of two, so every step is
    exact in f32 and the result is bit-identical to a hardware cast.
    """
    x = x.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    biased = ((bits >> 23) & jnp.uint32(0xFF)).astype(jnp.int32)
    e = biased - 127  # floor(log2|x|) for normal f32; -127 for f32 subnormals
    # quantization step: ulp at e, clamped at the subnormal plateau
    step_exp = jnp.maximum(e, fmt.min_exp) - fmt.man_bits
    # Scale by 2**(-step_exp), round, scale back. step_exp can reach -133
    # (fp16alt subnormals), beyond f32 exponent range, so split into two
    # exact power-of-two factors.
    half_a = step_exp // 2
    half_b = step_exp - half_a
    q = jnp.round(x * _exact_pow2(-half_a) * _exact_pow2(-half_b))
    q = q * _exact_pow2(half_a) * _exact_pow2(half_b)
    if fmt.min_exp - fmt.man_bits < -126:
        # fmt has representable values inside the f32-subnormal range
        # (fp16alt: down to 2^-133). XLA CPU runs with DAZ/FTZ, so those
        # must be produced via integer bit manipulation, not arithmetic.
        # Inputs with biased exponent 0 are exactly the affected set
        # (fp16alt.min_normal == f32 min normal).
        sub_step = fmt.min_exp - fmt.man_bits          # e.g. -133
        man = (bits & jnp.uint32(0x7FFFFF)).astype(jnp.float32)  # x = man*2^-149
        qi = jnp.round(man * _exact_pow2(jnp.full(x.shape, -149 - sub_step)))
        deep_bits = (qi.astype(jnp.uint32) << (149 + sub_step)) | (bits & jnp.uint32(0x80000000))
        deep = jax.lax.bitcast_convert_type(deep_bits, jnp.float32)
        q = jnp.where(biased == 0, deep, q)
    # overflow: beyond max_normal rounds to inf (ieee) or clamps (saturate);
    # formats with no inf encoding (ieee_specials=False) clamp true inf too
    max_normal = jnp.float32(fmt.max_normal)
    if fmt.inf_behavior == "ieee":
        over = jnp.where(jnp.isinf(x), x, jnp.sign(x) * jnp.inf)
    elif fmt.ieee_specials:
        over = jnp.where(jnp.isinf(x), x, jnp.sign(x) * max_normal)
    else:
        over = jnp.sign(x) * max_normal
    q = jnp.where(jnp.abs(q) > max_normal, over.astype(jnp.float32), q)
    # NaN propagates through the arithmetic already; +-0 preserved by round.
    return q


def quantize(x: jax.Array, fmt) -> jax.Array:
    """Quantize to ``fmt``'s representable set; returns float32 values."""
    fmt = get_format(fmt)
    if fmt.name == "fp32":
        return jnp.asarray(x, jnp.float32)
    if fmt.name == "fp64":
        return jnp.asarray(x, jnp.float32)  # f32 value already exact in f64
    return _quantize_f32(jnp.asarray(x), fmt)


# ---------------------------------------------------------------------------
# numpy mirror (oracle; float64 internal so it also serves 16/32-bit formats)
# ---------------------------------------------------------------------------

def quantize_np(x: np.ndarray, fmt) -> np.ndarray:
    fmt = get_format(fmt)
    x = np.asarray(x, np.float64)
    if fmt.name == "fp64":
        return x
    with np.errstate(all="ignore"):
        m, e = np.frexp(x)  # x = m * 2^e, 0.5<=|m|<1  => floor(log2|x|) = e-1
        e = e - 1
        step_exp = np.maximum(e, fmt.min_exp) - fmt.man_bits
        step = np.ldexp(1.0, step_exp.astype(np.int64))
        # np.round is round-half-even
        q = np.round(x / np.where(step == 0, 1.0, step)) * step
        if fmt.inf_behavior == "ieee":
            over = np.where(np.isinf(x), x, np.sign(x) * np.inf)
        elif fmt.ieee_specials:
            over = np.where(np.isinf(x), x, np.sign(x) * fmt.max_normal)
        else:
            over = np.sign(x) * fmt.max_normal
        q = np.where(np.abs(q) > fmt.max_normal, over, q)
        q = np.where(np.isnan(x), np.nan, q)
    return q


# ---------------------------------------------------------------------------
# Bit-pattern encode/decode (numpy; exact). Used by the ExSdotp oracle and
# storage round-trip tests for formats without a native dtype.
# ---------------------------------------------------------------------------

def encode_np(x: np.ndarray, fmt) -> np.ndarray:
    """Encode (already representable or arbitrary) values to fmt bit patterns."""
    fmt = get_format(fmt)
    q = quantize_np(np.asarray(x, np.float64), fmt)
    sign = (np.signbit(q)).astype(np.uint64)
    out = np.zeros(q.shape, np.uint64)
    aq = np.abs(q)
    nan = np.isnan(q)
    inf = np.isinf(q)
    sub = (aq < fmt.min_normal) & ~nan  # includes zero
    with np.errstate(all="ignore"):
        m, e = np.frexp(aq)
        e = e - 1
        # normals
        man_norm = np.rint((m * 2.0 - 1.0) * (1 << fmt.man_bits)).astype(np.uint64)
        exp_norm = (e + fmt.bias).astype(np.int64)
        # subnormals (and zero): value = man * 2^(min_exp - man_bits)
        man_sub = np.rint(aq / fmt.min_subnormal).astype(np.uint64)
    exp_field = np.where(sub, 0, np.clip(exp_norm, 0, (1 << fmt.exp_bits) - 1)).astype(np.uint64)
    man_field = np.where(sub, man_sub, man_norm).astype(np.uint64)
    if fmt.ieee_specials:
        exp_field = np.where(inf | nan, (1 << fmt.exp_bits) - 1, exp_field)
        man_field = np.where(inf, 0, man_field)
        man_field = np.where(nan, 1 << (fmt.man_bits - 1), man_field)  # quiet NaN
    else:
        # no special codes: quantize already clamped inf, NaN encodes to
        # the max-magnitude pattern (the MX group scale carries the NaN)
        exp_field = np.where(nan, (1 << fmt.exp_bits) - 1, exp_field)
        man_field = np.where(nan, (1 << fmt.man_bits) - 1, man_field)
    out = (sign << (fmt.exp_bits + fmt.man_bits)) | (exp_field << fmt.man_bits) | man_field
    nbytes = max(8, 1 << (fmt.width - 1).bit_length())
    return out.astype(np.dtype(f"uint{nbytes}"))


def decode_np(bits: np.ndarray, fmt) -> np.ndarray:
    fmt = get_format(fmt)
    bits = np.asarray(bits).astype(np.uint64)
    sign = ((bits >> (fmt.exp_bits + fmt.man_bits)) & 1).astype(np.int64)
    exp_f = ((bits >> fmt.man_bits) & ((1 << fmt.exp_bits) - 1)).astype(np.int64)
    man_f = (bits & ((1 << fmt.man_bits) - 1)).astype(np.int64)
    is_sub = exp_f == 0
    is_special = (exp_f == (1 << fmt.exp_bits) - 1) & fmt.ieee_specials
    with np.errstate(all="ignore"):
        val_norm = np.ldexp(1.0 + man_f / (1 << fmt.man_bits), exp_f - fmt.bias)
        val_sub = man_f * fmt.min_subnormal
    val = np.where(is_sub, val_sub, val_norm)
    val = np.where(is_special & (man_f == 0), np.inf, val)
    val = np.where(is_special & (man_f != 0), np.nan, val)
    return np.where(sign == 1, -val, val)


# ---------------------------------------------------------------------------
# Bit-pattern encode/decode (JAX; width <= 8). The jit-safe mirror of
# encode_np/decode_np, used by the packed sub-byte storage layer
# (kernels/pack.py): values <-> uint8 codes, then codes pack densely.
# ---------------------------------------------------------------------------

def encode(x: jax.Array, fmt) -> jax.Array:
    """Encode values to ``fmt`` bit patterns (uint8 codes; width <= 8).

    ``x`` is quantized to the representable set first, so arbitrary f32
    input is accepted; on already-representable values the cast is
    exact.  Bit-identical to ``encode_np``.
    """
    fmt = get_format(fmt)
    assert fmt.width <= 8, fmt
    q = _quantize_f32(jnp.asarray(x, jnp.float32), fmt)
    bits = jax.lax.bitcast_convert_type(q, jnp.uint32)
    aq = jnp.abs(q)
    nan = jnp.isnan(q)
    inf = jnp.isinf(q)
    # encode_np canonicalizes NaN to +nan (quantize_np); XLA keeps the
    # input NaN's sign bit, so drop it here to stay bit-identical
    sign = jnp.where(nan, 0, bits >> 31).astype(jnp.uint32)
    sub = (aq < jnp.float32(fmt.min_normal)) & ~nan  # includes zero
    # q is representable in fmt, hence f32-normal (or zero) for width<=8:
    # the fields fall straight out of the f32 bit pattern
    e = ((bits >> 23) & jnp.uint32(0xFF)).astype(jnp.int32) - 127
    man_norm = ((bits & jnp.uint32(0x7FFFFF))
                >> (23 - fmt.man_bits)).astype(jnp.uint32)
    exp_norm = jnp.clip(e + fmt.bias, 0, (1 << fmt.exp_bits) - 1)
    # subnormals (and zero): value = man * min_subnormal, exact pow2 ratio
    # (float -> int32 -> uint32: Mosaic has no direct f32 -> u32 cast)
    man_sub = jnp.round(
        aq * _exact_pow2(jnp.full(q.shape, fmt.man_bits - fmt.min_exp,
                                  jnp.int32))).astype(jnp.int32).astype(
                                      jnp.uint32)
    exp_field = jnp.where(sub, 0, exp_norm).astype(jnp.uint32)
    man_field = jnp.where(sub, man_sub, man_norm)
    top = jnp.uint32((1 << fmt.exp_bits) - 1)
    if fmt.ieee_specials:
        exp_field = jnp.where(inf | nan, top, exp_field)
        man_field = jnp.where(inf, 0, man_field)
        man_field = jnp.where(nan, 1 << (fmt.man_bits - 1), man_field)
    else:
        # no special codes: quantize already clamped inf, NaN encodes to
        # the max-magnitude pattern (the MX group scale carries the NaN)
        exp_field = jnp.where(nan, top, exp_field)
        man_field = jnp.where(nan, (1 << fmt.man_bits) - 1, man_field)
    out = ((sign << (fmt.exp_bits + fmt.man_bits))
           | (exp_field << fmt.man_bits) | man_field)
    return out.astype(jnp.uint8)


def decode(code: jax.Array, fmt) -> jax.Array:
    """Decode ``fmt`` bit patterns (uint8 codes) to f32 values.

    Bit-identical to ``decode_np`` (and to ``quantize``'s value set).
    """
    fmt = get_format(fmt)
    assert fmt.width <= 8, fmt
    c = jnp.asarray(code).astype(jnp.int32)
    sign = (c >> (fmt.exp_bits + fmt.man_bits)) & 1
    exp_f = (c >> fmt.man_bits) & ((1 << fmt.exp_bits) - 1)
    man_f = c & ((1 << fmt.man_bits) - 1)
    # exact in f32: mantissa fits, exponents are normal-range
    val_norm = ((1.0 + man_f.astype(jnp.float32) * (2.0 ** -fmt.man_bits))
                * _exact_pow2(exp_f - fmt.bias))
    val_sub = man_f.astype(jnp.float32) * jnp.float32(fmt.min_subnormal)
    val = jnp.where(exp_f == 0, val_sub, val_norm)
    if fmt.ieee_specials:
        sp = exp_f == (1 << fmt.exp_bits) - 1
        val = jnp.where(sp & (man_f == 0), jnp.float32(jnp.inf), val)
        val = jnp.where(sp & (man_f != 0), jnp.float32(jnp.nan), val)
    return jnp.where(sign == 1, -val, val)


# ---------------------------------------------------------------------------
# MX formats: element format × E8M0 shared scale × group size (DESIGN.md §8)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MXFormat:
    """An OCP-MX-style block format: ``group`` consecutive elements along
    the contraction (K) axis share one E8M0 scale (8 exponent bits, no
    mantissa, no sign — a pure power of two), each element stored in
    ``elem``.  The shared scale is the Flexpoint/Graphcore mechanism that
    makes sub-byte training survive real activation distributions: the
    dynamic-range window tracks each 32-element group, not the tensor.

    Differences from ``BlockScaleConfig`` tiles (DESIGN.md §3): groups are
    1×``group`` strips along K only (not 2-D tiles), the scale is a
    *storable 8-bit* E8M0 code rather than a free f32, and a non-finite
    group encodes scale=NaN (E8M0 0xFF) — the whole group reads back NaN —
    instead of the neutral-scale poison-propagation of the f32 path.
    """

    name: str
    elem: MiniFloatFormat
    group: int = 32

    @property
    def bits_per_element(self) -> float:
        """Storage cost incl. the amortized shared scale."""
        return self.elem.width + 8 / self.group

    @property
    def packed_bytes_per_element(self) -> float:
        """Bytes per element in packed storage, incl. the amortized E8M0
        byte (one uint8 per ``group`` elements): the wire/HBM cost the
        packed payload layer (``kernels/pack.py``) actually realizes."""
        return self.elem.packed_bytes_per_element + 1.0 / self.group

    def __str__(self) -> str:  # pragma: no cover - cosmetic
        return f"{self.name}({self.elem.name}xg{self.group})"


MXFP8E4M3 = MXFormat("mxfp8e4m3", FP8ALT)
MXFP8E5M2 = MXFormat("mxfp8e5m2", FP8)
MXFP6E2M3 = MXFormat("mxfp6e2m3", FP6E2M3)
MXFP6E3M2 = MXFormat("mxfp6e3m2", FP6E3M2)
MXFP4E2M1 = MXFormat("mxfp4e2m1", FP4E2M1)

MX_FORMATS = {f.name: f for f in (MXFP8E4M3, MXFP8E5M2, MXFP6E2M3,
                                  MXFP6E3M2, MXFP4E2M1)}


def get_mx_format(name) -> MXFormat:
    if isinstance(name, MXFormat):
        return name
    return MX_FORMATS[str(name).lower()]


# E8M0 scale encoding: value = 2**(code - 127) for code 0..254; 255 = NaN.
E8M0_BIAS = 127
E8M0_NAN = 255


def e8m0_encode_np(s: np.ndarray) -> np.ndarray:
    """Encode power-of-two f32 scales (or NaN) to E8M0 uint8 codes."""
    s = np.asarray(s, np.float64)
    nan = ~np.isfinite(s)
    with np.errstate(all="ignore"):
        m, e = np.frexp(s)  # s = m * 2^e, m == 0.5 exactly for pow2 s
    assert np.all(nan | ((m == 0.5) & (s > 0))), "E8M0 scales must be pow2"
    code = np.clip(e - 1 + E8M0_BIAS, 0, 254)
    return np.where(nan, E8M0_NAN, code).astype(np.uint8)


def e8m0_decode_np(code: np.ndarray) -> np.ndarray:
    code = np.asarray(code).astype(np.int64)
    val = np.ldexp(1.0, np.clip(code, 0, 254) - E8M0_BIAS)
    return np.where(code == E8M0_NAN, np.nan, val)


def e8m0_encode(s: jax.Array) -> jax.Array:
    """JAX mirror of ``e8m0_encode_np``: pow2 f32 scales (or NaN) to
    E8M0 uint8 codes.  For a normal pow2 the code *is* the f32 biased
    exponent field; NaN's all-ones exponent field is exactly the E8M0
    NaN code (255), so the encode is a single bit extraction.  This is
    what lets scale grids ride collectives at one byte per group.
    """
    bits = jax.lax.bitcast_convert_type(s.astype(jnp.float32), jnp.uint32)
    return ((bits >> 23) & jnp.uint32(0xFF)).astype(jnp.uint8)


def e8m0_decode(code: jax.Array) -> jax.Array:
    """JAX mirror of ``e8m0_decode_np``: uint8 codes to f32 scales
    (exact — pow2), code 255 to NaN."""
    c = jnp.asarray(code).astype(jnp.int32)
    val = _exact_pow2(jnp.clip(c, 0, 254) - E8M0_BIAS)
    return jnp.where(c == E8M0_NAN, jnp.float32(jnp.nan), val)


def _pow2_ceil_np(v: np.ndarray) -> np.ndarray:
    """Smallest power of two >= v for finite v > 0 (exact, via frexp)."""
    with np.errstate(all="ignore"):
        m, e = np.frexp(v)
    return np.where(m == 0.5, np.ldexp(1.0, e - 1), np.ldexp(1.0, e))


def mx_group_scales_np(x: np.ndarray, mx) -> np.ndarray:
    """E8M0 group scales for ``x[..., K]`` — the numpy oracle.

    Mirrors ``core.scaling.compute_group_scales`` bit for bit: the
    amax/max_normal division is performed in float32 (matching the
    kernel's arithmetic), the pow2-ceil is exact, and the result is
    clamped to the E8M0-representable [2^-126, 2^127] window the JAX
    ``_pow2_ceil`` produces.  amax == 0 -> neutral scale 1; non-finite
    amax -> NaN (the E8M0 NaN encoding: the whole group reads back NaN).
    """
    mx = get_mx_format(mx)
    *lead, k = x.shape
    assert k % mx.group == 0, (k, mx.group)
    xg = np.abs(np.asarray(x, np.float32)).reshape(*lead, k // mx.group,
                                                   mx.group)
    amax = xg.max(axis=-1)
    with np.errstate(all="ignore"):
        r = (amax / np.float32(mx.elem.max_normal)).astype(np.float32)
        s = _pow2_ceil_np(np.maximum(r.astype(np.float64), 2.0 ** -126))
    s = np.minimum(s, 2.0 ** 127)
    s = np.where(amax == 0, 1.0, s)
    return np.where(np.isfinite(amax), s, np.nan)


def mx_quantize_np(x: np.ndarray, mx):
    """Group-quantize ``x[..., K]``: returns ``(q, s)`` with ``q`` the
    element-format values of ``x / s`` (value space, float64 carrier) and
    ``s`` the per-group scales (``x.shape[:-1] + (K//group,)``).  The
    division is done in float32 — exact for pow2 scales — so the kernel
    path is bit-comparable.  A NaN scale poisons its whole group."""
    mx = get_mx_format(mx)
    s = mx_group_scales_np(x, mx)
    se = np.repeat(s, mx.group, axis=-1).reshape(x.shape)
    with np.errstate(all="ignore"):
        scaled = (np.asarray(x, np.float32) / se.astype(np.float32))
    return quantize_np(scaled.astype(np.float64), mx.elem), s


def mx_dequantize_np(q: np.ndarray, s: np.ndarray, mx) -> np.ndarray:
    mx = get_mx_format(mx)
    se = np.repeat(np.asarray(s, np.float64), mx.group, axis=-1).reshape(
        q.shape)
    with np.errstate(all="ignore"):
        return np.asarray(q, np.float64) * se
