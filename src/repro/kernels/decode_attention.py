"""Decode attention over the serving KV cache — Pallas TPU kernels
(DESIGN.md §12).

Serving attends S new query rows (S=1 steady-state decode, S=prompt
for batched prefill) against a cache of T slots of which only a
per-sequence prefix ``lens + S`` is live: slots ``0..lens-1`` hold the
history, ``lens..lens+S-1`` the rows being computed, and everything
beyond is garbage (unwritten, or stale payloads from a freed page).
Both kernels reuse the flash-attention shell (``_kernel``/``_call``)
with two decode-specific twists threaded through the shared
online-softmax core:

* **base offset** — the per-sequence lengths enter as one ``[BH]``
  int32 operand held whole in SMEM (a ``[BH, 1]`` VMEM block would
  break the (8, 128) tiling rule), read at the grid's batch·head
  coordinate; q row ``i`` sits at
  absolute cache slot ``base + i``, so the causal mask is
  ``col <= base + row`` and the carry-skip condition gains ``+ base``
  — with a dynamic base the skip doubles as a *page-skip*: KV tiles
  past a short sequence's live prefix never execute.
* **garbage masking** — the loader zeroes key slots at index
  ``>= base + S`` *structurally* (before any dot), so non-finite trash
  in dead cache slots — e.g. NaN-scale poison left by a retired
  sequence whose pages were re-used — cannot leak into live rows via
  ``0 · NaN``.  Poison *inside* the live prefix still propagates
  (0xFF scale codes decode NaN), exactly like the train-path kernels.

``mx_decode_attention_pallas`` streams the cache as *packed* codec
payloads + E8M0 scale codes and decodes groups in-register beside the
f32 (m, l, acc) accumulators — the same ``codec.decode_tile`` fold
point as ``mx_flash_attention_pallas``.  ``decode_attention_pallas``
is the carrier-precision variant (the bf16 page-pool fallback).

Compiled-TPU lane legality follows the §11 convention: packed payload
rows must be 128-byte multiples (hd a whole number of groups), and a q
tile is either the whole S=1 row or a sublane 8-multiple.  A q tile
that does not divide S is legal: the wrapper pads q's rows and keeps
the garbage limit at the true ``base + S``, so padded rows never widen
the live prefix real rows see.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.formats import e8m0_decode, get_mx_format
from .codec import get_codec
from .flash_attention import _call, _kernel

__all__ = ["decode_attention_pallas", "mx_decode_attention_pallas"]


#: lens [BH] int32, whole in SMEM (scalar reads at program_id(0))
_LENS_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _prepare(q, lens, block_q):
    """Shape-check ``lens`` and zero-pad q's rows to a ``block_q``
    multiple; returns ``(q, lens, live_rows)``."""
    bh, s, _ = q.shape
    lens = jnp.asarray(lens, jnp.int32)
    assert lens.shape == (bh,), (lens.shape, bh)
    pad = (-s) % block_q
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
    return q, lens, s


def _mask_garbage(k, v, kk, limit, block_k):
    """Zero key/value slots at cache index >= limit (structural
    exclusion of dead slots — not via softmax weights, which would turn
    stale NaN into NaN·0)."""
    idx = kk * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (k.shape[0], 1), 0)
    good = idx < limit
    return jnp.where(good, k, 0.0), jnp.where(good, v, 0.0)


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_k", "skip_masked", "debug_visited",
                     "interpret"))
def decode_attention_pallas(q, k, v, lens, *, block_q: int = 8,
                            block_k: int = 128, skip_masked: bool = True,
                            debug_visited: bool = False,
                            interpret: bool = False):
    """q [BH, S, hd], k/v [BH, T, hd], lens [BH] -> [BH, S, hd].

    The serving sweep over a carrier-precision cache (DESIGN.md §12).
    q row ``i`` of sequence-head ``b`` attends cache slots
    ``0..lens[b]+i``; slots beyond ``lens[b]+S`` are treated as garbage
    and excluded structurally.  ``debug_visited=True`` additionally
    returns the int32 [BH, S/bq, T/bk] visit grid (page-skip tests).

    Tile-legality contract (DESIGN.md §12/§14): ``block_k`` | T
    exactly (positional mask — assert, don't pad).  q rows are padded
    to a ``block_q`` multiple (the live limit stays ``lens + S``).  On
    compiled TPU ``block_q`` is 1 only for S=1 and a sublane 8-multiple
    otherwise (``ops.decode_attention`` picks so); smaller q tiles are
    interpret/CPU-only.
    """
    q, lens, live = _prepare(q, lens, block_q)
    hd = q.shape[-1]
    t = k.shape[1]
    assert t % block_k == 0, (t, block_k)

    def load_kv(refs):
        lens_ref, k_ref, v_ref = refs[0], refs[1], refs[2]
        base = lens_ref[pl.program_id(0)]

        def loader(kk):
            return _mask_garbage(k_ref[0].astype(jnp.float32),
                                 v_ref[0].astype(jnp.float32),
                                 kk, base + live, block_k)

        return loader, base, refs[3:]

    kern = functools.partial(
        _kernel, load_kv=load_kv, causal=True, scale=hd ** -0.5,
        block_q=block_q, block_k=block_k, skip_masked=skip_masked,
        debug_visited=debug_visited)
    specs = [_LENS_SPEC,
             pl.BlockSpec((1, block_k, hd), lambda b, i, kk: (b, kk, 0)),
             pl.BlockSpec((1, block_k, hd), lambda b, i, kk: (b, kk, 0))]
    out = _call(kern, q, (lens, k, v), specs,
                block_q=block_q, block_k=block_k, t=t,
                debug_visited=debug_visited, interpret=interpret)
    return _unpad(out, live, debug_visited)


@functools.partial(
    jax.jit,
    static_argnames=("mx_k", "mx_v", "block_q", "block_k", "skip_masked",
                     "debug_visited", "interpret"))
def mx_decode_attention_pallas(q, kp, ks8, vp, vs8, lens, *, mx_k,
                               mx_v=None, block_q: int = 8,
                               block_k: int = 128,
                               skip_masked: bool = True,
                               debug_visited: bool = False,
                               interpret: bool = False):
    """Decode attention straight from the packed paged KV cache
    (DESIGN.md §12).

    ``q [BH, S, hd]`` carrier precision; ``(kp, ks8)`` / ``(vp, vs8)``
    are the gathered page slots in ``ops.mx_quantize_kv`` layout:
    payload ``[BH, T, hd·w/8]`` uint8 + E8M0 codes ``[BH, T, hd/group]``
    (group scales along the head dimension); ``lens [BH]`` int32 live
    lengths.  Tiles stream packed from HBM and decode in-register; a
    0xFF scale code inside the live prefix decodes NaN and poisons
    exactly the rows that attend to it, while garbage slots beyond
    ``lens + S`` are structurally zeroed before the dots.

    Bit-exact vs ``ref.mx_decode_attention_ref`` on exact-arithmetic
    operands (``tests/fuzz.exact_decode_operands``) — the same bar as
    every codec kernel.

    Tile-legality contract: as ``decode_attention_pallas`` (§12/§14),
    plus hd a whole number of groups so the packed byte run is
    lane-legal.
    """
    mx_k = get_mx_format(mx_k)
    mx_v = mx_k if mx_v is None else get_mx_format(mx_v)
    ck, cv = get_codec(mx_k), get_codec(mx_v)
    g = mx_k.group
    assert mx_v.group == g, (mx_k.name, mx_v.name)
    q, lens, live = _prepare(q, lens, block_q)
    bh, _, hd = q.shape
    t = kp.shape[1]
    assert t % block_k == 0, (t, block_k)
    assert hd % g == 0, (hd, g)
    assert kp.shape == (bh, t, ck.packed_cols(hd)), (kp.shape, (bh, t, hd))
    assert vp.shape == (bh, t, cv.packed_cols(hd)), (vp.shape, (bh, t, hd))
    assert ks8.shape == vs8.shape == (bh, t, hd // g), (ks8.shape, vs8.shape)
    # scale codes at element resolution (compact grids are lane-illegal
    # on compiled TPU — the §8 rule)
    ks8e = jnp.repeat(ks8, g, axis=-1)
    vs8e = jnp.repeat(vs8, g, axis=-1)

    def load_kv(refs):
        lens_ref = refs[0]
        kp_ref, ks_ref, vp_ref, vs_ref = refs[1:5]
        base = lens_ref[pl.program_id(0)]

        def loader(kk):
            k = ck.decode_tile(kp_ref[0]) * e8m0_decode(ks_ref[0])
            v = cv.decode_tile(vp_ref[0]) * e8m0_decode(vs_ref[0])
            return _mask_garbage(k, v, kk, base + live, block_k)

        return loader, base, refs[5:]

    kern = functools.partial(
        _kernel, load_kv=load_kv, causal=True, scale=hd ** -0.5,
        block_q=block_q, block_k=block_k, skip_masked=skip_masked,
        debug_visited=debug_visited)
    pk, pv = ck.packed_cols(hd), cv.packed_cols(hd)
    specs = [_LENS_SPEC,
             pl.BlockSpec((1, block_k, pk), lambda b, i, kk: (b, kk, 0)),
             pl.BlockSpec((1, block_k, hd), lambda b, i, kk: (b, kk, 0)),
             pl.BlockSpec((1, block_k, pv), lambda b, i, kk: (b, kk, 0)),
             pl.BlockSpec((1, block_k, hd), lambda b, i, kk: (b, kk, 0))]
    out = _call(kern, q, (lens, kp, ks8e, vp, vs8e), specs,
                block_q=block_q, block_k=block_k, t=t,
                debug_visited=debug_visited, interpret=interpret)
    return _unpad(out, live, debug_visited)


def _unpad(out, live, debug_visited):
    """Drop the padded q rows (the visit grid keeps its tile rows)."""
    if debug_visited:
        return out[0][:, :live], out[1]
    return out[:, :live]
