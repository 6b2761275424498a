"""Shared building blocks: norms, RoPE, GQA attention (train/prefill/decode),
MLPs. Every projection routes through ``core.linear`` so the paper's
quantized expanding GEMM is the universal compute primitive.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.linear import linear
from ..core.policy import get_policy
from ..kernels import ops, ref
from ..parallel.tp_gemm import (tp_applicable, tp_column_linear,
                                tp_row_linear)


def proj(x, w, b, policy, rules, impl, kind="plain", quantized=True):
    """Projection router: explicit narrow-wire TP GEMMs when applicable
    (train/prefill with sequence parallelism), GSPMD qlinear otherwise.

    Block-scaled policies (``policy.block_scale > 0``) ride the same TP
    path: operands quantize per-(row-tile × K-tile) block and the fp8
    payloads ship with their scale grids riding along, so ``hfp8_block``
    composes with sequence parallelism instead of falling back to a
    GSPMD reshard (DESIGN.md §3, "block scaling × TP/SP").

    MX policies (``mxfp8``/``mxfp6``/``mxfp4`` — DESIGN.md §9/§10) ride
    the same wire natively: operands quantize per-(row × group-of-32)
    and the narrow payloads — native fp8 bytes, or *packed* sub-byte
    codec lanes (FP6: 0.75 B/elem, FP4: 0.5 B/elem) — ship with packed
    E8M0 byte grids riding along (one uint8 per group, ~1/32 of payload
    bytes), provided every contraction axis the groups run along — K
    forward, the local N columns for dgrad, the token axis for wgrad —
    tiles into whole groups (group alignment subsumes pack alignment);
    otherwise they fall back to the GSPMD-sharded packed MX pipeline
    (``ops.mx_gemm_packed``), which is numerically identical either
    way."""
    ok = quantized and tp_applicable(x, rules, policy)
    if ok:
        tp = rules.model_size
        dp = 1
        for a in rules.batch_axes:
            dp *= rules.mesh.shape[a]
        if kind == "col":
            ok = w.shape[0] % dp == 0 and w.shape[1] % tp == 0
        elif kind == "row":
            ok = (w.shape[0] % tp == 0 and w.shape[1] % dp == 0
                  and x.shape[2] % tp == 0)
        else:
            ok = False
    if ok and getattr(policy, "mx_fwd", ""):
        # group structure must survive the model-axis split: dgrad
        # groups run along the local N columns (col) / the local
        # feature slice (row)
        from ..core.formats import get_mx_format
        g = get_mx_format(policy.mx_fwd).group
        if kind == "col":
            ok = w.shape[0] % g == 0 and (w.shape[1] // tp) % g == 0
        else:
            # row: fwd groups along the local feature slice, dgrad
            # groups along the full output dim K = w.shape[1]
            ok = (x.shape[2] // tp) % g == 0 and w.shape[1] % g == 0
    if ok and kind == "col":
        y = tp_column_linear(x, w, policy, rules)
    elif ok and kind == "row":
        y = tp_row_linear(x, w, policy, rules)
    else:
        if (rules is not None and rules.mesh is not None
                and ops.resolve_impl(impl) == "pallas"):
            # GSPMD partitions this GEMM, and compiled Mosaic kernels
            # cannot be partitioned automatically: run it as XLA dots
            impl = "xla"
        return linear(x, w, b, policy=policy, impl=impl, quantized=quantized)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y

# ---------------------------------------------------------------------------
# Norms (computed in f32, cast back — low-precision training hygiene)
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, scale, bias, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


@jax.named_scope("norm")
def apply_norm(x, p, cfg):
    if cfg.norm == "layernorm":
        return layer_norm(x, p["scale"], p["bias"], cfg.norm_eps)
    return rms_norm(x, p["scale"], cfg.norm_eps)


def init_norm(cfg, dtype):
    p = {"scale": jnp.ones((cfg.d_model,), dtype)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((cfg.d_model,), dtype)
    return p


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def _rope_sincos(positions, head_dim, theta):
    """positions [..., S] -> sin/cos [..., S, head_dim//2] (f32)."""
    freqs = jnp.exp(
        -jnp.log(jnp.float32(theta))
        * (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x, positions, theta):
    """x [..., S, H, hd]; positions [..., S]."""
    hd = x.shape[-1]
    sin, cos = _rope_sincos(positions, hd, theta)  # [..., S, hd/2]
    sin = sin[..., None, :]
    cos = cos[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA): q-chunked exact softmax — O(chunk * T) score memory, so
# prefill_32k fits without a dedicated kernel; decode is a single-row case.
# ---------------------------------------------------------------------------

def init_attention(key, cfg, dtype, d_model=None):
    d = d_model or cfg.d_model
    hd = cfg.head_dim_eff
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = d ** -0.5
    p = {
        "wq": jax.random.normal(k1, (d, cfg.n_heads * hd), dtype) * s,
        "wk": jax.random.normal(k2, (d, cfg.n_kv_heads * hd), dtype) * s,
        "wv": jax.random.normal(k3, (d, cfg.n_kv_heads * hd), dtype) * s,
        "wo": jax.random.normal(k4, (cfg.n_heads * hd, d), dtype) * s,
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), dtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), dtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), dtype)
    return p


def _sdpa_chunked(q, k, v, *, causal, q_positions, kv_valid_len, chunk,
                  rules=None):
    """q [B,S,H,hd], k/v [B,T,KV,hd] -> [B,S,H,hd]. Exact, chunked over S.

    ``q_positions`` [S] absolute positions for causal masking;
    ``kv_valid_len`` masks cache slots >= this length (decode).
    """
    b, s, h, hd = q.shape
    t, kv = k.shape[1], k.shape[2]
    rep = h // kv
    kr = jnp.repeat(k, rep, axis=2)
    vr = jnp.repeat(v, rep, axis=2)
    scale = hd ** -0.5
    tpos = jnp.arange(t)

    def one_chunk(qc, pc):
        # qc [B,C,H,hd]; scores [B,H,C,T]
        sc = jnp.einsum("bchd,bthd->bhct", qc.astype(jnp.float32),
                        kr.astype(jnp.float32)) * scale
        mask = tpos[None, :] <= pc[:, None] if causal else (
            jnp.ones((qc.shape[1], t), bool))
        if kv_valid_len is not None:
            mask = mask & (tpos[None, :] < kv_valid_len)
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1)
        # rows with no valid key (shouldn't happen) -> zeros, not NaN
        w = jnp.where(jnp.isnan(w), 0.0, w)
        return jnp.einsum("bhct,bthd->bchd", w, vr.astype(jnp.float32))

    if s <= chunk or s % chunk:
        out = one_chunk(q, q_positions)
    else:
        nc = s // chunk
        qs = q.reshape(b, nc, chunk, h, hd).swapaxes(0, 1)
        ps = q_positions.reshape(nc, chunk)
        out = jax.lax.map(lambda args: one_chunk(*args), (qs, ps))
        out = out.swapaxes(0, 1).reshape(b, s, h, hd)
    return out.astype(q.dtype)


# Quantized-KV attention under MX policies (DESIGN.md §11): forward
# runs the packed flash pipeline — k/v quantize per (row × group-of-32
# along hd) into packed payloads + E8M0 byte grids, the KV sweep
# decodes them in-register next to the f32 online-softmax accumulator.
# Backward recomputes exact-softmax attention on the *dequantized* KV
# (the packed payloads are the residuals — the same one-fwd-rounding
# memory story as qlinear's MX branch) and differentiates through it:
# straight-through across the quantization, exactly like the GEMM path.

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _mx_sdpa(q, k, v, mx_name: str, causal: bool, impl: str):
    """q/k/v [BH, S|T, hd] -> [BH, S, hd] with MX-quantized KV."""
    out, _ = _mx_sdpa_fwd(q, k, v, mx_name, causal, impl)
    return out


def _mx_sdpa_fwd(q, k, v, mx_name, causal, impl):
    kp, ks8 = ops.mx_quantize_kv(k, mx_name, impl=impl)
    vp, vs8 = ops.mx_quantize_kv(v, mx_name, impl=impl)
    out = ops.mx_flash_attention_packed(q, kp, ks8, vp, vs8, mx_k=mx_name,
                                        causal=causal, impl=impl)
    return out, (q, kp, ks8, vp, vs8)


def _mx_sdpa_bwd(mx_name, causal, impl, res, g):
    q, kp, ks8, vp, vs8 = res
    hd = q.shape[-1]
    kf = ops.mx_dequantize_packed(kp, ks8, mx_name, k=hd).astype(q.dtype)
    vf = ops.mx_dequantize_packed(vp, vs8, mx_name, k=hd).astype(q.dtype)
    _, vjp = jax.vjp(
        lambda q_, k_, v_: ref.flash_attention_ref(q_, k_, v_,
                                                   causal=causal),
        q, kf, vf)
    return vjp(g)


_mx_sdpa.defvjp(_mx_sdpa_fwd, _mx_sdpa_bwd)


def _mx_attention_applicable(policy, *, s, t, hd, kv_cache, cross_kv):
    """Route train/prefill self-attention through the quantized kernel?

    Requires an MX policy, no decode cache and no cross-KV (so q
    positions are 0..S-1 and the kernel's raw-index causal mask is the
    model's mask), hd a whole number of groups, and a legal S/T tiling.
    Anything else falls back to ``_sdpa_chunked`` — numerically the
    unquantized path, exactly as misaligned shapes fall off the TP wire.
    """
    if not getattr(policy, "mx", False) or not policy.mx_attn_name:
        return False
    if kv_cache is not None or cross_kv is not None:
        return False
    from ..core.formats import get_mx_format
    if hd % get_mx_format(policy.mx_attn_name).group != 0:
        return False
    return ops.attention_blocks(s, t) is not None


@jax.named_scope("attn")
def attention(x, p, cfg, policy, *, positions, kv_cache=None, cross_kv=None,
              causal=None, rules=None, impl="auto"):
    """Returns (out [B,S,D], new_kv_cache).

    * train/prefill: kv_cache None -> full self-attention over x.
    * decode: kv_cache dict(k, v, idx) -> append and attend to the cache.
    * cross_kv (Bx[T,KV,hd] pair): encoder-decoder cross attention.

    Runs under the ``attn`` scope, its parts under ``repro.scopes.ATTN``.
    """
    policy = get_policy(policy)
    b, s, _ = x.shape
    hd = cfg.head_dim_eff
    causal = cfg.causal if causal is None else causal

    with jax.named_scope("qkv"):
        q = proj(x, p["wq"], p.get("bq"), policy, rules, impl, kind="col")
        q = q.reshape(b, s, cfg.n_heads, hd)
        if cross_kv is None:
            k = proj(x, p["wk"], p.get("bk"), policy, rules, impl,
                     kind="col")
            v = proj(x, p["wv"], p.get("bv"), policy, rules, impl,
                     kind="col")
            k = k.reshape(b, s, cfg.n_kv_heads, hd)
            v = v.reshape(b, s, cfg.n_kv_heads, hd)
    if cross_kv is None:
        if cfg.pos_embed == "rope":
            with jax.named_scope("rope"):
                q = apply_rope(q, positions, cfg.rope_theta)
                k = apply_rope(k, positions, cfg.rope_theta)
    else:
        k, v = cross_kv
        causal = False

    if kv_cache is not None and "pt" in kv_cache:
        # paged serving cache (DESIGN.md §12): append the new rows into
        # the page pool (packed MX payloads or carrier pages) and run
        # the decode kernel against the gathered page slots.  RoPE was
        # applied above with per-sequence absolute positions [B, S].
        from ..serve.kv_cache import paged_attend
        out, new_kv = paged_attend(q, k, v, kv_cache["kv"], kv_cache["pt"],
                                   kv_cache["lens"], cfg=cfg, policy=policy,
                                   impl=impl)
        out = out.reshape(b, s, cfg.n_heads * hd)
        with jax.named_scope("out"):
            out = proj(out, p["wo"], None, policy, rules, impl, kind="row")
        return out, new_kv

    new_cache = None
    kv_valid_len = None
    if kv_cache is not None:
        idx = kv_cache["idx"]
        with jax.named_scope("kv_write"):
            k = jax.lax.dynamic_update_slice(kv_cache["k"], k.astype(
                kv_cache["k"].dtype), (0, idx, 0, 0))
            v = jax.lax.dynamic_update_slice(kv_cache["v"], v.astype(
                kv_cache["v"].dtype), (0, idx, 0, 0))
        new_cache = {"k": k, "v": v, "idx": idx + s}
        kv_valid_len = idx + s
        # causal masking still applies via absolute positions (cache slots
        # are laid out absolutely); for decode s=1 it coincides with the
        # kv_valid_len mask.

    if rules is not None:
        q = rules.act(q, "batch", None, "heads", None)
        k = rules.act(k, "batch", None, "kv_heads" if cfg.n_kv_heads > 1 else None, None)
        v = rules.act(v, "batch", None, "kv_heads" if cfg.n_kv_heads > 1 else None, None)

    t = k.shape[1]
    with jax.named_scope("core"):
        if _mx_attention_applicable(policy, s=s, t=t, hd=hd,
                                    kv_cache=kv_cache, cross_kv=cross_kv):
            # GQA repeat stays OUTSIDE the custom_vjp: repeat's own
            # autodiff sums dk/dv back over the head groups for free.
            rep = cfg.n_heads // cfg.n_kv_heads
            kr = jnp.repeat(k, rep, axis=2).transpose(0, 2, 1, 3)
            vr = jnp.repeat(v, rep, axis=2).transpose(0, 2, 1, 3)
            qh = q.transpose(0, 2, 1, 3)                     # [B,H,S,hd]
            h = cfg.n_heads
            out = _mx_sdpa(qh.reshape(b * h, s, hd),
                           kr.reshape(b * h, t, hd),
                           vr.reshape(b * h, t, hd),
                           policy.mx_attn_name, causal, impl)
            out = out.reshape(b, h, s, hd).transpose(0, 2, 1, 3)
        else:
            out = _sdpa_chunked(q, k, v, causal=causal, q_positions=positions,
                                kv_valid_len=kv_valid_len,
                                chunk=cfg.attn_q_chunk, rules=rules)
    out = out.reshape(b, s, cfg.n_heads * hd)
    with jax.named_scope("out"):
        out = proj(out, p["wo"], None, policy, rules, impl, kind="row")
    if rules is not None:
        # row-parallel output lands sequence-sharded (TP path does this by
        # construction; the constraint keeps the GSPMD path on RS too, D1)
        out = rules.act(out, "batch", "seq", None)
    return out, new_cache


def init_kv_cache(cfg, batch, max_len, dtype, d_model=None):
    hd = cfg.head_dim_eff
    return {
        "k": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), dtype),
        "idx": jnp.zeros((), jnp.int32),
    }


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def init_mlp(key, cfg, dtype, d_ff=None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    s = d ** -0.5
    if cfg.mlp == "gated_silu":
        return {
            "w_gate": jax.random.normal(k1, (d, f), dtype) * s,
            "w_up": jax.random.normal(k2, (d, f), dtype) * s,
            "w_down": jax.random.normal(k3, (f, d), dtype) * (f ** -0.5),
        }
    return {  # gelu
        "w_up": jax.random.normal(k1, (d, f), dtype) * s,
        "b_up": jnp.zeros((f,), dtype),
        "w_down": jax.random.normal(k2, (f, d), dtype) * (f ** -0.5),
        "b_down": jnp.zeros((d,), dtype),
    }


@jax.named_scope("mlp")
def mlp(x, p, cfg, policy, *, rules=None, impl="auto"):
    if cfg.mlp == "gated_silu" or "w_gate" in p:
        g = proj(x, p["w_gate"], None, policy, rules, impl, kind="col")
        u = proj(x, p["w_up"], None, policy, rules, impl, kind="col")
        if rules is not None:
            g = rules.act(g, "batch", None, "ff")
            u = rules.act(u, "batch", None, "ff")
        h = jax.nn.silu(g.astype(jnp.float32)).astype(g.dtype) * u
    else:
        h = proj(x, p["w_up"], p.get("b_up"), policy, rules, impl,
                 kind="col")
        if rules is not None:
            h = rules.act(h, "batch", None, "ff")
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(h.dtype)
    out = proj(h, p["w_down"], p.get("b_down"), policy, rules, impl,
               kind="row")
    if rules is not None:
        out = rules.act(out, "batch", "seq", None)  # RS not AR (§Perf D1)
    return out
