"""Packed paged KV cache for serving (DESIGN.md §12).

The serving cache is a fixed pool of page slots shared by every
sequence in the batch, instead of one contiguous ``[B, max_len, ...]``
strip per sequence:

* **pool** — per layer, ``[P, page_size, KV, ...]`` arrays where
  ``P = 1 + batch · max_pages``; page 0 is a reserved *trash page* that
  absorbs out-of-range writes (a position past a sequence's page table
  routes there instead of clobbering live data).
* **page table** ``pt [B, max_pages]`` int32 — row ``b`` lists the pool
  pages backing sequence ``b`` in order; unallocated entries are 0
  (the trash page), whose garbage contents the decode kernel excludes
  structurally via ``lens``.
* **lens [B]`` int32 — live prefix length per sequence (cache slots
  ``0..lens-1`` are history; an attend of S new rows writes
  ``lens..lens+S-1``).

Under an MX serving policy (``policy.mx_kv_cache_name``) with a
group-aligned head dim, pool pages hold *packed* codec payloads +
E8M0 scale codes — the exact bytes ``ops.mx_quantize_kv`` emits, at
0.53–1.03 B/elem instead of 2 (bf16) — and attention runs the packed
decode kernel, dequantizing groups in-register.  Otherwise pages hold
carrier-precision k/v (the bf16 fallback: same paging, full bytes).

The page table itself is model state but *policy-free*: schedulers
(``serve.scheduler``) rewrite ``pt``/``lens`` host-side to admit,
grow, and retire sequences mid-flight; the simple ``generate`` path
uses the static identity table this module preallocates.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.formats import get_mx_format
from ..core.policy import get_policy
from ..kernels import ops

__all__ = ["paged_kv_applicable", "max_pages", "init_paged_kv",
           "paged_attend", "paged_kv_bytes_per_seq"]


def paged_kv_applicable(cfg, policy) -> bool:
    """Packed pages? Requires an MX cache format and a head dim that
    tiles into whole scale groups; anything else serves carrier pages."""
    policy = get_policy(policy)
    name = policy.mx_kv_cache_name
    if not name:
        return False
    return cfg.head_dim_eff % get_mx_format(name).group == 0


def max_pages(max_len: int, page_size: int) -> int:
    return -(-max_len // page_size)


def init_paged_kv(cfg, policy, batch: int, max_len: int, *,
                  page_size: int = 16, dtype=jnp.bfloat16):
    """One layer's page pool + the shared (pt, lens) tables.

    Returns ``(kv, pt, lens)``: ``kv`` is the per-layer leaf dict
    (packed: kp/ks/vp/vs; carrier: k/v), ``pt [B, MP]`` the identity
    page table (slot ``j`` of sequence ``b`` -> page ``1 + b·MP + j``),
    ``lens [B]`` zeros.  Pool size ``P = 1 + batch · MP`` — page 0 is
    the trash page."""
    policy = get_policy(policy)
    mp = max_pages(max_len, page_size)
    p_pool = 1 + batch * mp
    kv_h, hd = cfg.n_kv_heads, cfg.head_dim_eff
    if paged_kv_applicable(cfg, policy):
        mx = get_mx_format(policy.mx_kv_cache_name)
        from ..kernels.codec import get_codec
        pw = get_codec(mx).packed_cols(hd)
        kv = {
            "kp": jnp.zeros((p_pool, page_size, kv_h, pw), jnp.uint8),
            "ks": jnp.zeros((p_pool, page_size, kv_h, hd // mx.group),
                            jnp.uint8),
            "vp": jnp.zeros((p_pool, page_size, kv_h, pw), jnp.uint8),
            "vs": jnp.zeros((p_pool, page_size, kv_h, hd // mx.group),
                            jnp.uint8),
        }
    else:
        kv = {
            "k": jnp.zeros((p_pool, page_size, kv_h, hd), dtype),
            "v": jnp.zeros((p_pool, page_size, kv_h, hd), dtype),
        }
    pt = 1 + jnp.arange(batch * mp, dtype=jnp.int32).reshape(batch, mp)
    lens = jnp.zeros((batch,), jnp.int32)
    return kv, pt, lens


def paged_kv_bytes_per_seq(cfg, policy, max_len: int, *,
                           page_size: int = 16,
                           carrier_bytes: int = 2) -> int:
    """HBM cache bytes one sequence's pages pin, per layer-stack total
    — the quantity BENCH_serve gates."""
    policy = get_policy(policy)
    mp = max_pages(max_len, page_size)
    elems = page_size * cfg.n_kv_heads * cfg.head_dim_eff
    if paged_kv_applicable(cfg, policy):
        mx = get_mx_format(policy.mx_kv_cache_name)
        per_page = int(2 * elems * mx.packed_bytes_per_element)
    else:
        per_page = 2 * elems * carrier_bytes
    return cfg.n_layers * mp * per_page


def _slot_index(pt, lens, s, page_size):
    """Pool coordinates for the S new rows: (pidx [B,S], off [B,S]).

    Positions past the page table route to the trash page 0."""
    mp = pt.shape[1]
    pos = lens[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    pcol = pos // page_size
    inb = pcol < mp
    pidx = jnp.take_along_axis(pt, jnp.minimum(pcol, mp - 1), axis=1)
    pidx = jnp.where(inb, pidx, 0)
    return pidx, pos % page_size


def _gather(leaf, pt):
    """[P, page, KV, W] pool + [B, MP] table -> [B, MP·page, KV, W]."""
    b, mp = pt.shape
    pages = leaf[pt]                       # [B, MP, page, KV, W]
    return pages.reshape(b, mp * pages.shape[2], *leaf.shape[2:])


def _heads_to_rows(x, n_heads):
    """[B, T, KV, W] -> [B·H, T, W] with GQA repeat along heads."""
    b, t, kv_h, w = x.shape
    x = jnp.repeat(x, n_heads // kv_h, axis=2)
    return x.transpose(0, 2, 1, 3).reshape(b * n_heads, t, w)


def paged_attend(q, k_new, v_new, kv, pt, lens, *, cfg, policy,
                 impl: str = "auto"):
    """Append S rows to the paged cache and attend against it.

    ``q [B,S,H,hd]``, ``k_new/v_new [B,S,KV,hd]`` (RoPE already
    applied with per-sequence absolute positions); returns
    ``(out [B,S,H,hd], new_kv)`` — the functionally-updated pool
    leaves.  Packed pools quantize the new rows once on the way in
    (``ops.mx_quantize_kv``) and the decode kernel streams payloads;
    carrier pools store ``k_new`` at pool dtype.  The append runs under
    the ``kv_write`` scope, the page gather under ``kv_gather``, the
    kernel under ``core`` (``repro.scopes.ATTN``).
    """
    policy = get_policy(policy)
    b, s, h, hd = q.shape
    page_size = next(iter(kv.values())).shape[1]
    pidx, off = _slot_index(pt, lens, s, page_size)
    lens_r = jnp.repeat(lens, h)

    if "kp" in kv:
        name = policy.mx_kv_cache_name
        with jax.named_scope("kv_write"):
            kp, ks8 = ops.mx_quantize_kv(k_new, name, impl=impl)
            vp, vs8 = ops.mx_quantize_kv(v_new, name, impl=impl)
            new_kv = {"kp": kv["kp"].at[pidx, off].set(kp),
                      "ks": kv["ks"].at[pidx, off].set(ks8),
                      "vp": kv["vp"].at[pidx, off].set(vp),
                      "vs": kv["vs"].at[pidx, off].set(vs8)}
        with jax.named_scope("kv_gather"):
            args = [_heads_to_rows(_gather(new_kv[n], pt), h)
                    for n in ("kp", "ks", "vp", "vs")]
        with jax.named_scope("core"):
            out = ops.mx_decode_attention_packed(
                q.transpose(0, 2, 1, 3).reshape(b * h, s, hd), *args, lens_r,
                mx_k=name, impl=impl)
    else:
        with jax.named_scope("kv_write"):
            new_kv = {"k": kv["k"].at[pidx, off].set(k_new.astype(
                          kv["k"].dtype)),
                      "v": kv["v"].at[pidx, off].set(v_new.astype(
                          kv["v"].dtype))}
        with jax.named_scope("kv_gather"):
            kg = _heads_to_rows(_gather(new_kv["k"], pt), h)
            vg = _heads_to_rows(_gather(new_kv["v"], pt), h)
        with jax.named_scope("core"):
            out = ops.decode_attention(
                q.transpose(0, 2, 1, 3).reshape(b * h, s, hd), kg, vg,
                lens_r, impl=impl)
    out = out.reshape(b, h, s, hd).transpose(0, 2, 1, 3)
    return out.astype(q.dtype), new_kv
