"""Compile guards: the main-path Pallas kernels compile for a TPU v5e.

Each test lowers one kernel at the published widths of the repo's
smoke configurations (``stablelm-1.6b`` training, ``llama3.2-3b``
serving) and compiles it for a *described* v5e chip — the TPU compiler
runs here, no chip is attached — then checks the program really calls
a Mosaic kernel (``tpu_custom_call``).  A compile that passes here is
not a run: it proves only that Mosaic accepts the kernel (tiling,
dtypes, VMEM), which interpret mode cannot.

The topology is described inside a module fixture (never at import):
only the test worker that runs this file loads the TPU compiler.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import ml_dtypes
import pytest

from repro.core.formats import get_mx_format
from repro.kernels import ops
from repro.kernels.blockscale_gemm import mx_gemm_packed_pallas
from repro.kernels.codec import get_codec

# stablelm-1.6b: d_model 2048, d_ff 5632, 8 x 512 tokens
TRAIN_M, TRAIN_K, TRAIN_N = 4096, 2048, 5632
# llama3.2-3b: d_model 3072, d_ff 8192, 24/8 heads of 128, 16-slot pages
SERVE_D, SERVE_FF, SERVE_H, SERVE_HD, PAGE = 3072, 8192, 24, 128, 16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    """Compile ``fn`` for the described chip; return its HLO text."""
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("dtype", [ml_dtypes.float8_e4m3,
                                   ml_dtypes.float8_e5m2],
                         ids=["e4m3", "e5m2"])
def test_exsdotp_gemm_compiles(one_chip, dtype):
    """The hfp8 GEMM at a stablelm MLP shape, in the repo's own fp8
    dtypes (IEEE E4M3 rides in as uint8 bit patterns)."""
    _compile(lambda a, b: ops.exsdotp_gemm(a, b, 0.5, out_dtype=jnp.bfloat16,
                                           impl="pallas"),
             _spec(one_chip, (TRAIN_M, TRAIN_K), dtype),
             _spec(one_chip, (TRAIN_K, TRAIN_N), dtype))


#: train-stablelm-l6's ExSdotp GEMMs (M, K, N): E4M3 forward, E5M2 x
#: E4M3^T dgrad, E4M3^T x E5M2 wgrad (M = d_ff for w_down's), 4096 tokens
E4, E5 = ml_dtypes.float8_e4m3, ml_dtypes.float8_e5m2
CELL_GEMMS = {
    "fwd-K2048": (E4, E4, TRAIN_M, TRAIN_K, TRAIN_K),
    "fwd-K5632": (E4, E4, TRAIN_M, TRAIN_N, TRAIN_K),
    "dgrad-K2048": (E5, E4, TRAIN_M, TRAIN_K, TRAIN_N),
    "dgrad-K5632": (E5, E4, TRAIN_M, TRAIN_N, TRAIN_K),
    "wgrad-M2048": (E4, E5, TRAIN_K, TRAIN_M, TRAIN_N),
    "wgrad-M5632": (E4, E5, TRAIN_N, TRAIN_M, TRAIN_K),
}


@pytest.mark.parametrize("gemm", list(CELL_GEMMS))
def test_exsdotp_gemm_cell_tiles_compile(one_chip, gemm):
    """The bf16-widening kernel at its large default tiles within the
    16 MiB VMEM budget, at each kind of the training cell's GEMM shapes."""
    da, db, m, k, n = CELL_GEMMS[gemm]
    ops.EXSDOTP_CALLS.clear()
    _compile(lambda a, b: ops.exsdotp_gemm(a, b, 0.5, out_dtype=jnp.bfloat16,
                                           impl="pallas"),
             _spec(one_chip, (m, k), da), _spec(one_chip, (k, n), db))
    (wide, bm, bn, bk), = ops.EXSDOTP_CALLS
    assert wide == "bfloat16" and min(bm, bn, bk) >= 512


@pytest.mark.parametrize("mx", ["mxfp8e4m3", "mxfp4e2m1"])
def test_packed_quantize_compiles(one_chip, mx):
    _compile(lambda x: ops.mx_quantize(x, mx, impl="pallas", packed=True),
             _spec(one_chip, (TRAIN_M, SERVE_D), jnp.bfloat16))


def test_unpacked_quantize_compiles(one_chip):
    """The value-space MX quantize kernel (test oracle path) too."""
    _compile(lambda x: ops.mx_quantize(x, "mxfp8e4m3", impl="pallas"),
             _spec(one_chip, (512, SERVE_D), jnp.float32))


@pytest.mark.parametrize("double_buffer", [False, True],
                         ids=["grid", "manual_dma"])
def test_mx_gemm_packed_compiles(one_chip, double_buffer):
    """Both K loops of the packed GEMM at a llama MLP shape."""
    mx = get_mx_format("mxfp8e4m3")
    c = get_codec(mx)
    m, k, n = 512, SERVE_D, SERVE_FF
    _compile(lambda ap, bp, sa, sb: mx_gemm_packed_pallas(
                 ap, bp, sa, sb, mx_a=mx, out_dtype=jnp.bfloat16,
                 block_k=512, double_buffer=double_buffer),
             _spec(one_chip, (m, c.packed_cols(k)), jnp.uint8),
             _spec(one_chip, (n, c.packed_cols(k)), jnp.uint8),
             _spec(one_chip, (m, k), jnp.uint8),
             _spec(one_chip, (n, k), jnp.uint8))


def _packed_kv(sharding, bh, t):
    return (_spec(sharding, (bh, t, SERVE_HD), jnp.uint8),
            _spec(sharding, (bh, t, SERVE_HD // 32), jnp.uint8)) * 2


def test_mx_flash_attention_packed_compiles(one_chip):
    bh, s = SERVE_H, 512
    _compile(lambda q, kp, ks, vp, vs: ops.mx_flash_attention_packed(
                 q, kp, ks, vp, vs, mx_k="mxfp8e4m3", impl="pallas"),
             _spec(one_chip, (bh, s, SERVE_HD), jnp.bfloat16),
             *_packed_kv(one_chip, bh, s))


@pytest.mark.parametrize("s", [1, PAGE], ids=["S1", "Spage"])
def test_decode_attention_compiles(one_chip, s):
    """Carrier-page decode over 4 slots x 256 cached positions."""
    bh, t = 4 * SERVE_H, 256
    kv = _spec(one_chip, (bh, t, SERVE_HD), jnp.bfloat16)
    _compile(lambda q, k, v, lens: ops.decode_attention(q, k, v, lens,
                                                        impl="pallas"),
             _spec(one_chip, (bh, s, SERVE_HD), jnp.bfloat16), kv, kv,
             _spec(one_chip, (bh,), jnp.int32))


@pytest.mark.parametrize("s", [1, PAGE], ids=["S1", "Spage"])
def test_mx_decode_attention_packed_compiles(one_chip, s):
    """Packed-page decode (the mxfp8 server's attention)."""
    bh, t = 4 * SERVE_H, 256
    _compile(lambda q, kp, ks, vp, vs, lens: ops.mx_decode_attention_packed(
                 q, kp, ks, vp, vs, lens, mx_k="mxfp8e4m3", impl="pallas"),
             _spec(one_chip, (bh, s, SERVE_HD), jnp.bfloat16),
             *_packed_kv(one_chip, bh, t),
             _spec(one_chip, (bh,), jnp.int32))
