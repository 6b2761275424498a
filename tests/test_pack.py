"""Packed sub-byte payload storage (DESIGN.md §9).

Three layers:

1. the bit-packing itself, exhaustively: every FP4 byte pattern (256)
   and every FP6 3-byte lane (2^24) round-trips through
   unpack -> pack unchanged, and every code vector through
   pack -> unpack;
2. the JAX codecs (``formats.encode``/``decode``, jnp pack/unpack,
   ``e8m0_encode``/``decode``) are bit-identical to their numpy
   oracles on all codes and on random values;
3. the wired path: ``mx_quantize(packed=True)`` payloads measure the
   real sub-byte footprint (FP4: 2 elements/byte, FP6: 4 per 3 bytes),
   unpack losslessly, and ``mx_gemm_packed`` is bit-identical to
   ``ops.mx_gemm`` on the same operands.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import formats as F
from repro.kernels import ops
from repro.kernels import pack as P

MX_NAMES = list(F.MX_FORMATS)


# ----------------------------------------------- exhaustive round trips --

def test_fp4_all_256_byte_patterns_round_trip():
    b = np.arange(256, dtype=np.uint8)
    codes = P.unpack4_np(b)
    assert codes.shape == (512,) and codes.max() < 16
    np.testing.assert_array_equal(P.pack4_np(codes), b)
    # and the jnp path, bit-identical
    np.testing.assert_array_equal(
        np.asarray(P.pack4(P.unpack4(jnp.asarray(b)))), b)


def test_fp4_all_code_pairs_round_trip():
    c = np.stack(np.meshgrid(np.arange(16), np.arange(16)),
                 -1).reshape(-1, 2).astype(np.uint8)
    np.testing.assert_array_equal(P.unpack4_np(P.pack4_np(c)), c)


@pytest.mark.exhaustive
def test_fp6_all_3byte_lanes_round_trip():
    """Every possible 3-byte lane (2^24 of them): unpack to four 6-bit
    codes and repack — identity, so no bit of the lane is lost or
    aliased.  ``exhaustive``: these sweeps run in the nightly CI leg;
    tier-1 covers the boundary-lane sample (tests/test_codec.py via
    ``fuzz.fp6_lanes``)."""
    v = np.arange(2 ** 24, dtype=np.uint32)
    lanes = np.stack([v & 0xFF, (v >> 8) & 0xFF, (v >> 16) & 0xFF],
                     -1).astype(np.uint8)
    codes = P.unpack6_np(lanes)
    assert codes.shape == (2 ** 24, 4) and codes.max() < 64
    np.testing.assert_array_equal(P.pack6_np(codes), lanes)


@pytest.mark.exhaustive
def test_fp6_all_code_quads_round_trip():
    c = np.arange(2 ** 24, dtype=np.uint32)
    quads = np.stack([(c >> (6 * i)) & 0x3F for i in range(4)],
                     -1).astype(np.uint8)
    np.testing.assert_array_equal(P.unpack6_np(P.pack6_np(quads)), quads)


def test_jnp_pack_matches_numpy_oracle():
    rng = np.random.default_rng(0)
    c4 = rng.integers(0, 16, (5, 7, 64)).astype(np.uint8)
    c6 = rng.integers(0, 64, (5, 7, 64)).astype(np.uint8)
    np.testing.assert_array_equal(P.pack4_np(c4),
                                  np.asarray(P.pack4(jnp.asarray(c4))))
    np.testing.assert_array_equal(P.pack6_np(c6),
                                  np.asarray(P.pack6(jnp.asarray(c6))))
    np.testing.assert_array_equal(
        P.unpack6_np(P.pack6_np(c6)),
        np.asarray(P.unpack6(P.pack6(jnp.asarray(c6)))))


# ------------------------------------------------------------ jnp codecs --

@pytest.mark.parametrize("name", ["fp8", "fp8alt", "fp6e2m3", "fp6e3m2",
                                  "fp4e2m1"])
def test_jax_encode_decode_matches_numpy(name):
    fmt = F.get_format(name)
    codes = np.arange(1 << fmt.width, dtype=np.uint8)
    vn = F.decode_np(codes, fmt)
    vj = np.asarray(F.decode(jnp.asarray(codes), fmt), np.float64)
    np.testing.assert_array_equal(np.isnan(vn), np.isnan(vj))
    np.testing.assert_array_equal(vn[~np.isnan(vn)], vj[~np.isnan(vj)])
    # encode round-trips every decodable value to its own code (NaN
    # codes collapse to the canonical quiet NaN in both impls)
    ej = np.asarray(F.encode(jnp.asarray(vj, jnp.float32), fmt))
    np.testing.assert_array_equal(F.encode_np(vn, fmt).astype(np.uint8), ej)
    np.testing.assert_array_equal(codes[~np.isnan(vn)], ej[~np.isnan(vn)])
    # arbitrary (non-representable) values quantize-and-encode the same
    rng = np.random.default_rng(1)
    x = np.concatenate([rng.normal(0, fmt.max_normal / 2, 2048),
                        [0.0, -0.0, np.inf, -np.inf, np.nan,
                         fmt.max_normal * 4, fmt.min_subnormal / 3]])
    x = x.astype(np.float32)
    np.testing.assert_array_equal(
        F.encode_np(x, fmt).astype(np.uint8),
        np.asarray(F.encode(jnp.asarray(x), fmt)))


def test_e8m0_jnp_codecs_match_numpy():
    s = np.asarray([2.0 ** -126, 0.25, 0.5, 1.0, 2.0, 2.0 ** 127, np.nan],
                   np.float32)
    np.testing.assert_array_equal(F.e8m0_encode_np(s),
                                  np.asarray(F.e8m0_encode(jnp.asarray(s))))
    codes = np.arange(256, dtype=np.uint8)
    dn = F.e8m0_decode_np(codes)
    dj = np.asarray(F.e8m0_decode(jnp.asarray(codes)), np.float64)
    np.testing.assert_array_equal(np.isnan(dn), np.isnan(dj))
    np.testing.assert_array_equal(dn[:255], dj[:255])


def test_packed_bytes_per_element():
    assert F.FP4E2M1.packed_bytes_per_element == 0.5
    assert F.FP6E2M3.packed_bytes_per_element == 0.75
    assert F.FP8.packed_bytes_per_element == 1.0
    assert F.FP4E2M1.pack_align == 2 and F.FP6E2M3.pack_align == 4
    assert F.FP8.pack_align == 1
    # MX adds one E8M0 byte per group of 32
    assert F.MXFP4E2M1.packed_bytes_per_element == 0.5 + 1 / 32
    assert P.packed_length(64, 4) == 32 and P.packed_length(64, 6) == 48


# ------------------------------------------------------- MX wired path ----

@pytest.mark.parametrize("name", MX_NAMES)
def test_mx_quantize_packed_is_lossless(name):
    mx = F.get_mx_format(name)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(0, 8, (3, 16, 64)), jnp.float32)
    q, s = ops.mx_quantize(x, name, impl="xla")
    p, s8 = ops.mx_quantize(x, name, impl="xla", packed=True)
    assert p.dtype == jnp.uint8 and s8.dtype == jnp.uint8
    # the honest footprint: width/8 bytes per element, 1 byte per group
    assert p.shape == (3, 16, 64 * mx.elem.width // 8)
    assert s8.shape == (3, 16, 64 // mx.group)
    np.testing.assert_array_equal(np.asarray(ops.mx_unpack(p, name)),
                                  np.asarray(q))
    sd = np.asarray(F.e8m0_decode(s8), np.float64)
    sn = np.asarray(s, np.float64)
    np.testing.assert_array_equal(np.isnan(sn), np.isnan(sd))
    np.testing.assert_array_equal(sn[~np.isnan(sn)], sd[~np.isnan(sd)])


@pytest.mark.parametrize("name", MX_NAMES)
def test_mx_gemm_packed_bit_exact_vs_mx_gemm(name):
    """Storage-path GEMM == value-path GEMM bit for bit on arbitrary
    float data: pack/unpack is lossless and the math after it is the
    same (NaN rows positionally equal via array_equal)."""
    rng = np.random.default_rng(3)
    a = jnp.asarray(rng.normal(0, 4, (2, 16, 64)), jnp.float32)
    b = jnp.asarray(rng.normal(0, 4, (64, 24)), jnp.float32)
    want = ops.mx_gemm(a, b, mx_a=name, impl="xla")
    ap, sa8 = ops.mx_quantize(a, name, impl="xla", packed=True)
    bp, sb8 = ops.mx_quantize(b.T, name, impl="xla", packed=True)
    got = ops.mx_gemm_packed(ap, sa8, bp, sb8, mx_a=name)
    assert got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))


def test_mx_gemm_packed_mixed_formats_and_poison():
    """E4M3 × E5M2 pairing from packed storage, with a non-finite group:
    the NaN travels as the 0xFF scale byte and poisons its row."""
    rng = np.random.default_rng(4)
    a = rng.normal(0, 2, (8, 64)).astype(np.float32)
    a[1, 5] = np.inf
    aj = jnp.asarray(a)
    b = jnp.asarray(rng.normal(0, 2, (64, 16)), jnp.float32)
    want = ops.mx_gemm(aj, b, mx_a="mxfp8e4m3", mx_b="mxfp8e5m2",
                       impl="xla")
    ap, sa8 = ops.mx_quantize(aj, "mxfp8e4m3", impl="xla", packed=True)
    bp, sb8 = ops.mx_quantize(b.T, "mxfp8e5m2", impl="xla", packed=True)
    assert int(np.asarray(sa8)[1, 0]) == F.E8M0_NAN
    got = ops.mx_gemm_packed(ap, sa8, bp, sb8, mx_a="mxfp8e4m3",
                             mx_b="mxfp8e5m2")
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
    assert np.isnan(np.asarray(got)[1]).all()


@pytest.mark.parametrize("width", [4, 6])
@pytest.mark.parametrize("k", [64, 1024, 2048], ids=["one_run", "runs", "runs2"])
def test_tile_pack_matches_strided(width, k):
    """The in-kernel (selection-matmul) pack/unpack produce the same
    bytes and codes as the strided jnp layout, in one run or several."""
    rng = np.random.default_rng(width * k)
    codes = jnp.asarray(rng.integers(0, 1 << width, (8, k)), jnp.uint8)
    packed = P.pack_codes(codes, width)
    np.testing.assert_array_equal(np.asarray(P.pack_codes_tile(codes, width)),
                                  np.asarray(packed))
    np.testing.assert_array_equal(
        np.asarray(P.unpack_codes_tile(packed, width)), np.asarray(codes))
