"""Pallas kernel validation: interpret-mode vs pure-jnp oracles.

Per instructions: sweep shapes/dtypes and assert_allclose against ref.py.
Bit-exactness is asserted on integer-valued inputs (fp32 accumulation is
then exact in both implementations regardless of summation order).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.formats import get_format
from repro.kernels import ops, ref

RNG = np.random.default_rng(3)

SRC_DTYPES = [jnp.float8_e5m2, jnp.float8_e4m3, jnp.float16, jnp.bfloat16]
SHAPES = [(8, 16, 8), (128, 128, 128), (64, 256, 32), (100, 130, 50),
          (1, 512, 1), (256, 64, 512)]


@pytest.mark.parametrize("src", SRC_DTYPES, ids=lambda d: d.__name__)
@pytest.mark.parametrize("shape", SHAPES, ids=str)
def test_gemm_matches_ref(src, shape):
    m, k, n = shape
    a = jnp.asarray(RNG.normal(0, 1, (m, k)), src)
    b = jnp.asarray(RNG.normal(0, 1, (k, n)), src)
    out = ops.exsdotp_gemm(a, b, 0.5, out_dtype=jnp.float32,
                           impl="pallas_interpret", blocks=(8, 8, 16))
    want = ref.exsdotp_gemm_ref(a, b, 0.5, out_dtype=jnp.float32)
    # fp32 accumulation order differs (tiled partial sums vs full-K dot):
    # worst-case relative drift ~ K * 2^-24.
    tol = max(k * 2.0 ** -24, 1e-6)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=tol, atol=tol * np.sqrt(k))


@pytest.mark.parametrize("src", SRC_DTYPES, ids=lambda d: d.__name__)
@pytest.mark.parametrize("out_dtype", [jnp.float16, jnp.bfloat16, jnp.float32],
                         ids=lambda d: d.__name__)
def test_gemm_bit_exact_on_integer_inputs(src, out_dtype):
    """Integer-valued operands make fp32 accumulation exact -> bit equality,
    including the single final downcast (the ExSdotp rounding step)."""
    m, k, n = 48, 96, 32
    a = jnp.asarray(RNG.integers(-4, 5, (m, k)), src)
    b = jnp.asarray(RNG.integers(-4, 5, (k, n)), src)
    out = ops.exsdotp_gemm(a, b, 1.0, out_dtype=out_dtype,
                           impl="pallas_interpret", blocks=(16, 16, 32))
    want = ref.exsdotp_gemm_ref(a, b, 1.0, out_dtype=out_dtype)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(want, np.float32))


def test_gemm_expanding_accumulation_beats_dst_accumulation():
    """The point of the unit (paper Fig. 9): wide accumulation wins.

    Accumulating fp8 products in fp16 (non-expanding chain) drifts; the
    kernel's fp32 VMEM accumulator with one final rounding stays within
    1 fp16 ulp of the exact result.
    """
    k = 4096
    a = jnp.asarray(RNG.normal(0, 1, (1, k)), jnp.float8_e4m3)
    b = jnp.asarray(RNG.normal(0, 1, (k, 1)), jnp.float8_e4m3)
    out = ops.exsdotp_gemm(a, b, 1.0, out_dtype=jnp.float16,
                           impl="pallas_interpret", blocks=(1, 1, 64))
    exact = (np.asarray(a, np.float64) @ np.asarray(b, np.float64)).item()
    # naive fp16 running accumulation
    acc = np.float16(0)
    af = np.asarray(a, np.float32)[0]
    bf = np.asarray(b, np.float32)[:, 0]
    for i in range(k):
        acc = np.float16(acc + np.float16(af[i] * bf[i]))
    ulp = abs(exact) * 2.0 ** -10
    assert abs(float(np.asarray(out, np.float32)[0, 0]) - exact) <= ulp
    assert abs(float(acc) - exact) > ulp  # the naive chain actually drifts


@pytest.mark.parametrize("q_dtype", [jnp.float8_e5m2, jnp.float8_e4m3],
                         ids=lambda d: d.__name__)
@pytest.mark.parametrize("shape", [(128, 128), (256, 384), (100, 70)], ids=str)
def test_quant_blockwise_matches_ref(q_dtype, shape):
    x = jnp.asarray(RNG.normal(0, 5, shape), jnp.float32)
    q, s = ops.quantize_blockwise(x, q_dtype, block_m=32, block_n=32,
                                  impl="pallas_interpret")
    qr, sr = ops.quantize_blockwise(x, q_dtype, block_m=32, block_n=32,
                                    impl="xla")
    np.testing.assert_array_equal(np.asarray(q, np.float32),
                                  np.asarray(qr, np.float32))
    # scale may differ by 1 f32 ulp (XLA may fuse /s as *rcp(s))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=3e-7)


@pytest.mark.parametrize("shape", [(100, 70), (1, 1), (33, 129), (8, 200)],
                         ids=str)
def test_quant_blockwise_pallas_ragged_direct(shape):
    """Regression: ``quant_blockwise_pallas`` used to assert divisibility
    and rely on the caller to pad; it now pads ragged M/N itself (like
    ``ops`` does for the GEMMs) and slices the payload back."""
    from repro.kernels.quant import quant_blockwise_pallas
    m, n = shape
    x = jnp.asarray(RNG.normal(0, 5, shape), jnp.float32)
    q, s = quant_blockwise_pallas(x, q_dtype=jnp.float8_e4m3, block_m=32,
                                  block_n=32, interpret=True)
    assert q.shape == shape
    assert s.shape == ((m + 31) // 32, (n + 31) // 32)
    qr, sr = ops.quantize_blockwise(x, jnp.float8_e4m3, block_m=32,
                                    block_n=32, impl="xla")
    np.testing.assert_array_equal(np.asarray(q, np.float32),
                                  np.asarray(qr, np.float32))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=3e-7)


def test_quant_roundtrip_error_bound():
    """|x - dequant(quant(x))| <= 2^-m * blockmax for every block."""
    x = jnp.asarray(RNG.normal(0, 3, (256, 256)), jnp.float32)
    for q_dtype, man in [(jnp.float8_e5m2, 2), (jnp.float8_e4m3, 3)]:
        q, s = ops.quantize_blockwise(x, q_dtype, block_m=64, block_n=64,
                                      impl="pallas_interpret")
        back = ops.dequantize_blockwise(q, s, block_m=64, block_n=64)
        err = np.abs(np.asarray(back) - np.asarray(x))
        bmax = np.abs(np.asarray(x)).reshape(4, 64, 4, 64).max((1, 3))
        bound = np.repeat(np.repeat(bmax, 64, 0), 64, 1) * 2.0 ** (-man) * 1.01
        assert (err <= bound).all()


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 5), st.integers(1, 5), st.integers(1, 5),
       st.integers(0, 2**31 - 1))
def test_property_gemm_any_shape(mb, kb, nb, seed):
    """Property: kernel == oracle for random block-multiple shapes."""
    rng = np.random.default_rng(seed)
    m, k, n = 8 * mb, 16 * kb, 8 * nb
    a = jnp.asarray(rng.integers(-3, 4, (m, k)), jnp.float8_e4m3)
    b = jnp.asarray(rng.integers(-3, 4, (k, n)), jnp.float8_e5m2)
    out = ops.exsdotp_gemm(a, b, 1.0, out_dtype=jnp.float32,
                           impl="pallas_interpret", blocks=(8, 8, 16))
    want = ref.exsdotp_gemm_ref(a, b, 1.0, out_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


# ------------------------------------------- ExSdotp widening and tiles ---

FP8 = {"e4m3": (jnp.float8_e4m3, "fp8alt"), "e5m2": (jnp.float8_e5m2, "fp8")}
FP8_PAIRS = [("e4m3", "e4m3"), ("e5m2", "e4m3"), ("e4m3", "e5m2"),
             ("e5m2", "e5m2")]
#: (m, k, n) of train-stablelm-l6's ExSdotp GEMMs: forward, dgrad, wgrad
CELL_SHAPES = [(4096, 2048, 2048), (4096, 2048, 5632), (4096, 5632, 2048),
               (2048, 4096, 2048), (2048, 4096, 5632), (5632, 4096, 2048)]


def _fp8_edge_operand(shape, name, rng, specials=False):
    """Random fp8 values with the format's edges planted in: ±0, the
    smallest and largest subnormal, ±max normal (and inf/NaN codes)."""
    dtype, fmt = FP8[name]
    f = get_format(fmt)
    x = rng.normal(0, 1, shape)
    edges = [0.0, -0.0, f.min_subnormal, -f.min_subnormal,
             f.min_normal - f.min_subnormal, f.max_normal, -f.max_normal]
    if specials:
        edges += [np.inf, -np.inf, np.nan]
    idx = rng.choice(x.size, len(edges), replace=False)
    x.flat[idx] = edges
    return jnp.asarray(x, dtype)


@pytest.mark.parametrize("fmt", ["fp8", "fp8alt"])
def test_widen_bits_bf16_is_decode_exactly(fmt):
    """The kernel's bf16 widening of every 8-bit code is bit for bit
    ``decode``'s value in bf16: subnormals, ±0, ±max, inf; NaN stays NaN."""
    from repro.core.formats import decode
    from repro.kernels.exsdotp_gemm import _widen_bits_bf16
    codes = jnp.arange(256, dtype=jnp.uint8).reshape(2, 128)
    got = np.asarray(_widen_bits_bf16(codes, fmt), np.float32)
    want = np.asarray(decode(codes, fmt), np.float32)
    nan = np.isnan(want)
    np.testing.assert_array_equal(np.isnan(got), nan)
    np.testing.assert_array_equal(got[~nan].view(np.uint32),
                                  want[~nan].view(np.uint32))


@pytest.mark.parametrize("pair", FP8_PAIRS, ids="-".join)
def test_gemm_fp8_pairs_match_oracles(pair):
    """Every pair of 8-bit sources takes the bf16 path at the default
    tiles and matches ``exsdotp_gemm_ref`` and the ``exsdotp_gemm_np``
    ExSdotp chain (fp16 holds every fp8/fp8alt value) within f32
    summation order: |err| <= K 2^-23 (|A| @ |B|)."""
    from repro.core.exsdotp import exsdotp_gemm_np
    from repro.kernels.exsdotp_gemm import widen_dtype
    rng = np.random.default_rng(11)
    m, k, n = 40, 200, 72
    a = _fp8_edge_operand((m, k), pair[0], rng)
    b = _fp8_edge_operand((k, n), pair[1], rng)
    assert widen_dtype(a.dtype, None, b.dtype, None) == jnp.bfloat16
    out = np.asarray(ops.exsdotp_gemm(a, b, 1.0, impl="pallas_interpret"),
                     np.float64)
    af, bf = np.asarray(a, np.float64), np.asarray(b, np.float64)
    bound = k * 2.0 ** -23 * (np.abs(af) @ np.abs(bf))
    for want in (np.asarray(ref.exsdotp_gemm_ref(a, b, 1.0), np.float64),
                 exsdotp_gemm_np(af, bf, "fp16", "fp32")):
        assert (np.abs(out - want) <= bound).all()


@pytest.mark.parametrize("pair", FP8_PAIRS, ids="-".join)
def test_gemm_fp8_specials_match_ref(pair):
    """inf/NaN codes of each format reach the output as the f32 upcast
    gives them: the same NaN and ±inf entries, finite ones close."""
    rng = np.random.default_rng(12)
    a = _fp8_edge_operand((24, 256), pair[0], rng, specials=True)
    b = _fp8_edge_operand((256, 16), pair[1], rng, specials=True)
    out = np.asarray(ops.exsdotp_gemm(a, b, 1.0, impl="pallas_interpret"))
    want = np.asarray(ref.exsdotp_gemm_ref(a, b, 1.0))
    np.testing.assert_array_equal(np.isnan(out), np.isnan(want))
    np.testing.assert_array_equal(np.isinf(out), np.isinf(want))
    fin = np.isfinite(want)
    np.testing.assert_array_equal(out[~fin & ~np.isnan(want)],
                                  want[~fin & ~np.isnan(want)])
    np.testing.assert_allclose(out[fin], want[fin], rtol=1e-5,
                               atol=1e-5 * np.abs(want[fin]).max())


@pytest.mark.parametrize("pair", FP8_PAIRS, ids="-".join)
@pytest.mark.parametrize("out_dtype", [jnp.bfloat16, jnp.float32],
                         ids=lambda d: d.__name__)
def test_gemm_fp8_bit_exact_on_integer_inputs(pair, out_dtype):
    """Integer-valued fp8 operands through the bf16 path at the default
    tiles: exact f32 accumulation, so equal to the f32 upcast bit for
    bit, final rounding included."""
    rng = np.random.default_rng(13)
    a = jnp.asarray(rng.integers(-8, 9, (48, 384)), FP8[pair[0]][0])
    b = jnp.asarray(rng.integers(-8, 9, (384, 136)), FP8[pair[1]][0])
    out = ops.exsdotp_gemm(a, b, 1.0, out_dtype=out_dtype,
                           impl="pallas_interpret")
    want = ref.exsdotp_gemm_ref(a, b, 1.0, out_dtype=out_dtype)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("src", [jnp.float16, jnp.bfloat16,
                                 jnp.float8_e4m3, jnp.float8_e5m2],
                         ids=lambda d: d.__name__)
def test_gemm_widening_follows_source_format(src):
    """fp16 (11 significant bits) keeps the f32 upcast; bf16 and the
    8-bit formats widen to bf16 — the tally records which path ran."""
    want = "float32" if src == jnp.float16 else "bfloat16"
    a = jnp.asarray(RNG.normal(0, 1, (16, 128)), src)
    b = jnp.asarray(RNG.normal(0, 1, (128, 8)), src)
    ops.EXSDOTP_CALLS.clear()
    out = ops.exsdotp_gemm(a, b, 1.0, impl="pallas_interpret")
    assert [key[0] for key in ops.EXSDOTP_CALLS] == [want]
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.exsdotp_gemm_ref(a, b, 1.0)),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("m", [1, 8, 40])
@pytest.mark.parametrize("n", [128, 200])
@pytest.mark.parametrize("k", [128, 200])
@pytest.mark.parametrize("src_bytes", [1, 2])
def test_default_blocks_legal_for_small_shapes(m, n, k, src_bytes):
    """Small problems get legal tiles shrunk to them: M a sublane
    multiple of 8 covering the row count, N and K lane multiples of 128
    no larger than the padded dims."""
    from repro.kernels.exsdotp_gemm import default_blocks
    bm, bn, bk = default_blocks(m, n, k, src_bytes, 2 * src_bytes)
    assert bm % 8 == 0 and m <= bm < m + 8
    assert bn % 128 == 0 and n <= bn < n + 128
    assert bk % 128 == 0 and k <= bk < k + 128


@pytest.mark.parametrize("shape", CELL_SHAPES, ids=str)
def test_default_blocks_large_at_cell_shapes(shape):
    """At train-stablelm-l6's GEMM shapes every tile is >= 512 (each
    widened element feeds >= 512 MACs), the tiles divide the shape, fit
    the VMEM budget, and the grid has >= 16x fewer steps than the
    128 x 128 x 512 tiles had."""
    from repro.kernels.exsdotp_gemm import (VMEM_BUDGET, default_blocks,
                                            vmem_bytes)
    m, k, n = shape
    bm, bn, bk = default_blocks(m, n, k, 1, 2)
    assert min(bm, bn, bk) >= 512
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    assert vmem_bytes(bm, bn, bk, 1, 2) <= VMEM_BUDGET
    old = (m // 128) * (n // 128) * (k // 512)
    assert old >= 16 * (m // bm) * (n // bn) * (k // bk)


def test_tally_hfp8_qlinear_takes_bf16_path():
    """An hfp8 ``qlinear`` forward and backward traces three ExSdotp
    GEMMs (forward, dgrad, wgrad), all on the bf16 path."""
    from repro.core.linear import qlinear
    x = jnp.asarray(RNG.normal(0, 1, (2, 64, 256)), jnp.float32)
    w = jnp.asarray(RNG.normal(0, 0.05, (256, 384)), jnp.float32)
    ops.EXSDOTP_CALLS.clear()
    jax.make_jaxpr(jax.grad(
        lambda x, w: jnp.sum(qlinear(x, w, "hfp8",
                                     impl="pallas_interpret") ** 2),
        argnums=(0, 1)))(x, w)
    assert sum(ops.EXSDOTP_CALLS.values()) == 3
    assert {key[0] for key in ops.EXSDOTP_CALLS} == {"bfloat16"}


def test_tally_stablelm_hfp8_step_takes_large_bf16_tiles():
    """The train-stablelm-l6 step (stablelm-1.6b widths under hfp8, one
    scanned layer, 1 x 4096 tokens), traced for the Pallas path: every
    ExSdotp GEMM widens to bf16 with tiles of >= 512."""
    import dataclasses
    from repro.configs import get_arch
    from repro.models import build_model
    from repro.optim.adamw import AdamWConfig
    from repro.train.train_step import make_train_state, make_train_step
    cfg = dataclasses.replace(get_arch("stablelm-1.6b"), n_layers=1)
    assert cfg.policy_name == "hfp8"
    model = build_model(cfg)
    opt = AdamWConfig(lr=1e-3, warmup_steps=1, schedule="constant")
    state = jax.eval_shape(
        lambda: make_train_state(model, jax.random.key(0), opt))
    tokens = jax.ShapeDtypeStruct((1, 4096), jnp.int32)
    ops.EXSDOTP_CALLS.clear()
    jax.eval_shape(make_train_step(model, opt, impl="pallas"), state, tokens)
    assert sum(ops.EXSDOTP_CALLS.values()) >= 21  # 7 projections x 3
    for wide, bm, bn, bk in ops.EXSDOTP_CALLS:
        assert wide == "bfloat16" and min(bm, bn, bk) >= 512


# ------------------------------------------------------ flash attention ---

@pytest.mark.parametrize("blocks", [(32, 32), (64, 64), (128, 128),
                                    (32, 128), (128, 32)], ids=str)
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("shape", [(2, 128, 16), (1, 256, 64)], ids=str)
def test_flash_attention_matches_ref(causal, shape, blocks):
    """Block shapes up to the full 128 tile, square and rectangular —
    the online-softmax recurrence must not care how the sweep tiles."""
    from repro.kernels.flash_attention import flash_attention_pallas
    bh, s, hd = shape
    bq, bk = blocks
    q = jnp.asarray(RNG.normal(0, 1, (bh, s, hd)), jnp.bfloat16)
    k = jnp.asarray(RNG.normal(0, 1, (bh, s, hd)), jnp.bfloat16)
    v = jnp.asarray(RNG.normal(0, 1, (bh, s, hd)), jnp.bfloat16)
    out = flash_attention_pallas(q, k, v, causal=causal, block_q=bq,
                                 block_k=bk, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_flash_attention_rejects_misaligned_lengths():
    """The kernels assert S/T divide the blocks instead of silently
    padding (a padded length would corrupt the positional mask)."""
    from repro.kernels.flash_attention import flash_attention_pallas
    q = jnp.zeros((1, 48, 16), jnp.float32)
    kv = jnp.zeros((1, 48, 16), jnp.float32)
    with pytest.raises(AssertionError):
        flash_attention_pallas(q, kv, kv, block_q=32, block_k=32,
                               interpret=True)
    with pytest.raises(AssertionError):  # T misaligned, S fine
        flash_attention_pallas(q[:, :32], kv, kv, block_q=32, block_k=32,
                               interpret=True)


def test_flash_attention_cross_lengths():
    """S != T (cross attention / cached decode windows)."""
    from repro.kernels.flash_attention import flash_attention_pallas
    q = jnp.asarray(RNG.normal(0, 1, (2, 32, 16)), jnp.float32)
    k = jnp.asarray(RNG.normal(0, 1, (2, 128, 16)), jnp.float32)
    v = jnp.asarray(RNG.normal(0, 1, (2, 128, 16)), jnp.float32)
    out = flash_attention_pallas(q, k, v, causal=False, block_q=32,
                                 block_k=32, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
