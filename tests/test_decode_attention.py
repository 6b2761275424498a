"""Decode attention over the serving KV cache (DESIGN.md §12):
oracle-backed harness, mirroring test_mx_attention.py.

1. the numpy oracle (``ref.mx_decode_attention_ref``) is pinned to the
   carrier decode reference on losslessly-quantizable operands;
2. the packed Pallas kernel (interpret mode) and the xla ops branch
   must match the oracle **bit for bit** on
   ``fuzz.exact_decode_operands`` — per-sequence base offsets, NaN
   garbage beyond the live prefix, and poison (NaN-scale) groups
   inside it — for every serving MX format;
3. the base-offset carry-skip doubles as a *page-skip*: KV tiles past
   ``(iq+1)·bq + lens[b]`` never execute (``debug_visited``), and
   skipping is bitwise neutral;
4. structural garbage masking: non-finite trash in dead cache slots
   (stale payloads of a freed page) cannot leak into live rows.
"""
import jax.numpy as jnp
import numpy as np
import pytest

import fuzz
from repro.core import formats as F
from repro.kernels import ops, ref
from repro.kernels.decode_attention import (decode_attention_pallas,
                                            mx_decode_attention_pallas)

POLICY_FORMATS = ["mxfp8e4m3", "mxfp6e2m3", "mxfp4e2m1"]

#: (bh, s, t, hd, lens) — s=1 is steady-state decode, s>1 block prefill
SHAPES = [
    (2, 4, 64, 64, [3, 17]),
    (2, 1, 64, 64, [1, 40]),      # single-row decode tiles (bq = 1)
    (3, 8, 128, 32, [5, 64, 100]),
]


def _quantized(k, v, name):
    kp, ks8 = ops.mx_quantize_kv(jnp.asarray(k), name, impl="xla")
    vp, vs8 = ops.mx_quantize_kv(jnp.asarray(v), name, impl="xla")
    return kp, ks8, vp, vs8


def _run_all_impls(q, k, v, lens, name):
    """(oracle, interpret, xla) outputs for one format."""
    want = ref.mx_decode_attention_ref(q, k, v, lens, mx_k=name)
    kp, ks8, vp, vs8 = _quantized(k, v, name)
    qj, lj = jnp.asarray(q), jnp.asarray(lens)
    got_i = np.asarray(ops.mx_decode_attention_packed(
        qj, kp, ks8, vp, vs8, lj, mx_k=name, impl="pallas_interpret"))
    got_x = np.asarray(ops.mx_decode_attention_packed(
        qj, kp, ks8, vp, vs8, lj, mx_k=name, impl="xla"))
    return want, got_i, got_x


# ------------------------------------------------------------- oracle ----

def test_oracle_is_carrier_decode_on_lossless_operands():
    """k/v from {0, ±64, ±128, ±256} survive every MX quantizer exactly,
    so the quantized oracle must equal the unquantized decode reference
    (garbage excluded structurally by both)."""
    rng = np.random.default_rng(0)
    q, k, v, lens = fuzz.exact_decode_operands(rng, 2, 4, 64, 64, [3, 17],
                                               garbage=False)
    plain = np.asarray(ref.decode_attention_ref(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(lens)))
    for name in F.MX_FORMATS:
        want = ref.mx_decode_attention_ref(q, k, v, lens, mx_k=name)
        np.testing.assert_array_equal(want, plain, err_msg=name)


# ------------------------------------------------- kernel bit-exactness --

@pytest.mark.parametrize("name", POLICY_FORMATS)
def test_kernel_bit_exact_vs_oracle(name):
    """Interpret kernel and xla branch vs the numpy oracle, bit for bit
    — garbage NaN beyond every sequence's live prefix included."""
    for i, (bh, s, t, hd, lens) in enumerate(SHAPES):
        rng = np.random.default_rng(100 + i)
        q, k, v, lens = fuzz.exact_decode_operands(rng, bh, s, t, hd, lens)
        want, got_i, got_x = _run_all_impls(q, k, v, lens, name)
        assert np.isfinite(want).all()   # garbage must not leak
        np.testing.assert_array_equal(got_i, want,
                                      err_msg=f"interp {(bh, s, t, hd)}")
        np.testing.assert_array_equal(got_x, want,
                                      err_msg=f"xla {(bh, s, t, hd)}")


def test_carrier_kernel_bit_exact_vs_ref():
    """The carrier-page kernel (bf16 fallback) against the jnp decode
    reference on the same exact operands."""
    for i, (bh, s, t, hd, lens) in enumerate(SHAPES):
        rng = np.random.default_rng(200 + i)
        q, k, v, lens = fuzz.exact_decode_operands(rng, bh, s, t, hd, lens)
        qj, kj, vj, lj = map(jnp.asarray, (q, k, v, lens))
        want = np.asarray(ref.decode_attention_ref(qj, kj, vj, lj))
        got = np.asarray(ops.decode_attention(qj, kj, vj, lj,
                                              impl="pallas_interpret"))
        np.testing.assert_array_equal(got, want, err_msg=str((bh, s, t, hd)))


@pytest.mark.parametrize("name", POLICY_FORMATS)
def test_kernel_poison_group_propagates(name):
    """A NaN-scale v group *inside the live prefix* poisons exactly its
    32 output columns for every query row — identically in kernel and
    oracle — while garbage NaN *outside* it stays fully masked."""
    rng = np.random.default_rng(7)
    q, k, v, lens = fuzz.exact_decode_operands(rng, 2, 4, 64, 64, [3, 17],
                                               specials=True)
    want, got_i, got_x = _run_all_impls(q, k, v, lens, name)
    nan_w = np.isnan(want)
    assert nan_w[:, :, :32].all() and not nan_w[:, :, 32:].any()
    for got, tag in ((got_i, "interp"), (got_x, "xla")):
        np.testing.assert_array_equal(np.isnan(got), nan_w, err_msg=tag)
        np.testing.assert_array_equal(got[~nan_w], want[~nan_w],
                                      err_msg=tag)


def test_garbage_slots_cannot_leak():
    """Freed-page trash: with every dead slot NaN (both k and v), all
    outputs stay finite — the masking is structural (0-fill before the
    dot), not a softmax-weight zero, which 0·NaN would defeat."""
    rng = np.random.default_rng(11)
    q, k, v, lens = fuzz.exact_decode_operands(rng, 2, 4, 64, 64, [1, 9])
    assert np.isnan(k).any() and np.isnan(v).any()   # trash present
    for name in POLICY_FORMATS:
        want, got_i, got_x = _run_all_impls(q, k, v, lens, name)
        assert np.isfinite(got_i).all() and np.isfinite(got_x).all(), name
    got = np.asarray(decode_attention_pallas(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(lens),
        block_q=4, block_k=32, interpret=True))
    assert np.isfinite(got).all()


def test_kernel_tolerance_on_arbitrary_data():
    """Random data: same quantization in kernel and oracle, so drift is
    f32 summation order only."""
    rng = np.random.default_rng(13)
    bh, s, t, hd = 2, 4, 64, 64
    q = rng.normal(0, 1, (bh, s, hd)).astype(np.float32)
    k = rng.normal(0, 1, (bh, t, hd)).astype(np.float32)
    v = rng.normal(0, 1, (bh, t, hd)).astype(np.float32)
    lens = np.asarray([3, 17], np.int32)
    for name in POLICY_FORMATS:
        want, got_i, got_x = _run_all_impls(q, k, v, lens, name)
        np.testing.assert_allclose(got_i, want, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(got_x, want, rtol=1e-4, atol=1e-4)


# ----------------------------------------------------------- page-skip ---

def test_page_skip_visits_only_live_tiles():
    """The per-sequence base offset feeds the carry-skip: a KV tile
    executes iff ``kk·bk < (iq+1)·bq + lens[b]`` — so a short sequence
    skips the pages it never filled."""
    rng = np.random.default_rng(17)
    bh, s, t, hd, bq, bk = 2, 4, 128, 32, 2, 32
    lens = np.asarray([3, 90], np.int32)
    q, k, v, lens = fuzz.exact_decode_operands(rng, bh, s, t, hd, lens)
    _, vis = decode_attention_pallas(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(lens),
        block_q=bq, block_k=bk, debug_visited=True, interpret=True)
    iq = np.arange(s // bq)[:, None]
    kk = np.arange(t // bk)[None, :]
    live = (kk * bk < (iq + 1) * bq + lens[:, None, None]).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(vis), live)
    # the short sequence actually skips pages the long one visits
    assert np.asarray(vis)[0].sum() < np.asarray(vis)[1].sum()


def test_page_skip_is_bitwise_neutral():
    rng = np.random.default_rng(19)
    q, k, v, lens = fuzz.exact_decode_operands(rng, 2, 4, 128, 32,
                                               [3, 90])
    for name in POLICY_FORMATS[:1] + [None]:
        if name is None:
            run = lambda skip: decode_attention_pallas(
                jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                jnp.asarray(lens), block_q=2, block_k=32,
                skip_masked=skip, interpret=True)
        else:
            kp, ks8, vp, vs8 = _quantized(k, v, name)
            run = lambda skip: mx_decode_attention_pallas(
                jnp.asarray(q), kp, ks8, vp, vs8, jnp.asarray(lens),
                mx_k=name, block_q=2, block_k=32, skip_masked=skip,
                interpret=True)
        np.testing.assert_array_equal(np.asarray(run(True)),
                                      np.asarray(run(False)),
                                      err_msg=str(name))


# ------------------------------------------------------- ops-layer API ---

def test_decode_attention_blocks_tiling():
    """Unlike attention_blocks, decode tiling never fails: q tiles have
    floor 1 (S=1 steady-state decode), KV tiles floor 8."""
    assert ops.decode_attention_blocks(1, 64) == (1, 64)
    assert ops.decode_attention_blocks(8, 128) == (8, 128)
    assert ops.decode_attention_blocks(7, 48) == (1, 16)   # 7 -> q tile 1
    assert ops.decode_attention_blocks(12, 12) == (4, 1)   # no 8-divisor


def test_packed_kernel_checks_payload_shapes():
    q = jnp.zeros((1, 4, 64), jnp.float32)
    lens = jnp.ones((1,), jnp.int32)
    kp, ks8 = ops.mx_quantize_kv(jnp.zeros((1, 32, 64)), "mxfp6e2m3",
                                 impl="xla")
    with pytest.raises(AssertionError):  # payload packed for another width
        mx_decode_attention_pallas(q, kp, ks8, kp, ks8, lens,
                                   mx_k="mxfp8e4m3", block_q=4,
                                   block_k=32, interpret=True)


@pytest.mark.parametrize("name", [None, "mxfp8e4m3"])
def test_q_rows_padded_to_tile(name):
    """A q tile that does not divide S (compiled TPU takes 8-row tiles
    for a 7-row prefill): the wrapper pads q's rows, and the garbage
    limit stays at the true ``lens + S`` — bitwise the same as the
    dividing tile, NaN trash just past the live prefix included."""
    rng = np.random.default_rng(23)
    q, k, v, lens = fuzz.exact_decode_operands(rng, 2, 7, 64, 64, [3, 40])
    qj, lj = jnp.asarray(q), jnp.asarray(lens)
    if name is None:
        run = lambda bq: decode_attention_pallas(
            qj, jnp.asarray(k), jnp.asarray(v), lj, block_q=bq,
            block_k=32, interpret=True)
    else:
        kp, ks8, vp, vs8 = _quantized(k, v, name)
        run = lambda bq: mx_decode_attention_pallas(
            qj, kp, ks8, vp, vs8, lj, mx_k=name, block_q=bq, block_k=32,
            interpret=True)
    padded, exact = np.asarray(run(8)), np.asarray(run(1))
    assert padded.shape == q.shape
    assert np.isfinite(padded).all()
    np.testing.assert_array_equal(padded, exact)
