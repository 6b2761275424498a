"""Flash attention — Pallas TPU kernels (online-softmax, O(S) memory).

The attention analogue of the ExSdotp rule: logits and the softmax
accumulator live in f32 VMEM scratch at full precision for the whole KV
sweep (never materialized to HBM), with a single rounding to the carrier
dtype when the output block retires. This removes the O(S^2) score
materialization that dominates the prefill_32k memory roofline term
(EXPERIMENTS.md §Roofline).

Two kernels share one online-softmax core (``_sweep_body``):

* ``flash_attention_pallas`` — carrier-precision q/k/v (the original).
* ``mx_flash_attention_pallas`` — the KV sweep quantized (DESIGN.md
  §11): k/v enter the kernel as *packed* codec payloads (uint8 lanes at
  ``width/8`` bytes per element) plus E8M0 group-scale codes over the
  head dimension, and are unpacked + decoded in-register
  (``codec.decode_tile(...) * e8m0_decode(...)``) right before the
  q·kᵀ and p·v dots — the same fold point as ``mx_gemm_packed_pallas``.
  E8M0 scales are exact powers of two, so folding the dequant into the
  decoded operands is bit-identical to rescaling partial products at
  accumulator granularity; the logits and the (m, l, acc) state never
  see narrow precision.

Layout: q [BH, S, hd], k/v [BH, T, hd] (packed: [BH, T, hd·w/8] payload
+ [BH, T, hd/group] E8M0 codes); grid (BH, S/bq, T/bk), KV innermost
('arbitrary'); running (m, l, acc) in VMEM scratch. Causal masking by
absolute position.

Carry-skip (``skip_masked``, default on): a causal tile whose every
column index exceeds its every row index (``kk·bk ≥ (iq+1)·bq``) is a
structural zero — its masked logits contribute ``exp(-1e30 - m) = 0``
to l/acc and never move the running max — so the whole exp/dot body is
skipped under ``pl.when``.  Output is bit-identical with the skip on or
off for finite operands; causal prefill stops paying ~half the sweep.

Compiled-TPU lane legality: the packed payload's last axis is
``hd·width/8`` bytes, which must be a 128-multiple on real hardware
(``codec.lane_unit`` — satisfied by hd=128 FP8; other combinations pad
the head axis at the layer above).  Interp/CPU CI masks violations —
the same convention as ``ops.blockscale_blocks``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.formats import e8m0_decode
from .codec import get_codec

__all__ = ["flash_attention_pallas", "mx_flash_attention_pallas"]

NEG_INF = -1e30
_F32 = jax.lax.Precision.HIGHEST


def _sweep_body(q, k, v, m_ref, l_ref, acc_ref, *, iq, kk, causal, scale,
                block_q, block_k, base=None):
    """One KV tile of the online-softmax recurrence (f32 throughout).

    ``q [bq, hd]``, ``k/v [bk, hd]`` are already-decoded f32 operands —
    all kernels funnel through here, so the carry-skip and the MX
    variant cannot drift from the carrier-precision kernel's math.
    ``iq``/``kk`` are the grid coordinates, read once at the kernel's
    top level (``pl.program_id`` must not be bound inside a ``pl.when``
    body — the carry-skip wraps this whole function in one).

    ``base`` (decode kernels — DESIGN.md §12) is a per-sequence scalar
    offsetting q's absolute positions: q row ``i`` sits at cache slot
    ``base + i``, so the causal mask becomes ``col <= base + row``.
    ``base=None`` is the train/prefill case (identical to ``base=0``).
    """
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if causal:
        rows = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        if base is not None:
            rows = rows + base
        cols = kk * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(cols <= rows, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    # HIGHEST: p is f32 relative to this tile's running max, which a
    # default (one bf16 pass) TPU dot rounds to bf16; the references
    # use the row max, so a row spanning several KV tiles would round
    # otherwise there.  q·k needs no pin: its operands are exact in bf16.
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32, precision=_F32)
    m_ref[...] = m_new


def _kernel(q_ref, *refs, load_kv, causal, scale, block_q, block_k,
            skip_masked, debug_visited):
    """Shared kernel shell: init / carry-skip / sweep / retire.

    ``load_kv(refs)`` returns ``(loader, base, rest)`` — the only point
    the carrier, packed, and decode variants differ.  ``loader(kk)``
    yields the decoded f32 (k, v) tiles for KV-tile ``kk`` (the decode
    kernels zero key slots beyond the live prefix ``base + S`` — their
    structural exclusion of garbage cache slots, so stale poison in
    freed pages can't leak through ``0·NaN``).  ``base`` (None for
    train/prefill) is the per-sequence absolute-position offset; with
    it, q's S rows cover cache slots ``base..base+S-1``.
    """
    loader, base, refs = load_kv(refs)
    if debug_visited:
        o_ref, vis_ref = refs[0], refs[1]
        m_ref, l_ref, acc_ref = refs[2:]
    else:
        o_ref, vis_ref = refs[0], None
        m_ref, l_ref, acc_ref = refs[1:]
    iq, kk = pl.program_id(1), pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if vis_ref is not None:
        vis_ref[0, 0, 0] = jnp.int32(0)

    def _update():
        q = q_ref[0].astype(jnp.float32)                # [bq, hd]
        k, v = loader(kk)
        _sweep_body(q, k, v, m_ref, l_ref, acc_ref,
                    iq=iq, kk=kk, causal=causal, scale=scale,
                    block_q=block_q, block_k=block_k, base=base)
        if vis_ref is not None:
            vis_ref[0, 0, 0] = jnp.int32(1)

    if causal and skip_masked:
        # carry-skip: the tile is live iff its smallest column index can
        # reach its largest row index (kk·bk <= base + iq·bq + bq - 1);
        # otherwise every logit is the structural-zero NEG_INF and the
        # update is exactly a no-op — skip the exp/dot work entirely.
        # With a dynamic ``base`` this doubles as the page-skip: tiles
        # beyond a sequence's live length never execute.  Tile kk=0 is
        # always live (base >= 0), so (m, l) never retire all-masked.
        live = kk * block_k < (iq + 1) * block_q + (
            0 if base is None else base)

        @pl.when(live)
        def _live():
            _update()
    else:
        _update()

    @pl.when(kk == pl.num_programs(2) - 1)
    def _write():
        # single rounding into the carrier dtype (the ExSdotp rule)
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def _call(kern, q, operands, operand_specs, *, block_q, block_k, t,
          debug_visited, interpret):
    bh, s, hd = q.shape
    grid = (bh, s // block_q, t // block_k)
    out_shape = [jax.ShapeDtypeStruct((bh, s, hd), q.dtype)]
    out_specs = [pl.BlockSpec((1, block_q, hd), lambda b, i, kk: (b, i, 0))]
    if debug_visited:
        out_shape.append(jax.ShapeDtypeStruct(grid, jnp.int32))
        out_specs.append(
            pl.BlockSpec((1, 1, 1), lambda b, i, kk: (b, i, kk)))
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[pl.BlockSpec((1, block_q, hd), lambda b, i, kk: (b, i, 0)),
                  *operand_specs],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),      # running max
            pltpu.VMEM((block_q, 1), jnp.float32),      # running sum
            pltpu.VMEM((block_q, hd), jnp.float32),     # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, *operands)
    return tuple(out) if debug_visited else out[0]


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "skip_masked",
                     "debug_visited", "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           block_q: int = 128, block_k: int = 128,
                           skip_masked: bool = True,
                           debug_visited: bool = False,
                           interpret: bool = False):
    """q [BH, S, hd], k/v [BH, T, hd] -> [BH, S, hd] (same dtype as q).

    The carrier-precision online-softmax sweep (DESIGN.md §11).
    ``skip_masked`` enables the causal carry-skip (bit-identical output
    for finite operands).  ``debug_visited=True`` additionally returns
    an int32 [BH, S/bq, T/bk] grid marking which tiles executed the
    sweep body — the interpret-mode hook for the masked-tile tests.

    Tile-legality contract (DESIGN.md §11/§14): ``block_q`` must divide
    S and ``block_k`` divide T *exactly* — the mask is positional, so
    this kernel asserts rather than pads; ``ops.attention_blocks`` (or
    the §14 autotuner, whose candidates divide by construction) picks
    legal tiles.  On compiled TPU ``block_q`` is a sublane 8-multiple
    and hd a lane 128-multiple (masked on CPU CI).
    """
    bh, s, hd = q.shape
    t = k.shape[1]
    assert s % block_q == 0 and t % block_k == 0, ((s, t),
                                                   (block_q, block_k))

    def load_kv(refs):
        k_ref, v_ref = refs[0], refs[1]

        def loader(kk):
            return (k_ref[0].astype(jnp.float32),
                    v_ref[0].astype(jnp.float32))

        return loader, None, refs[2:]

    kern = functools.partial(
        _kernel, load_kv=load_kv, causal=causal, scale=hd ** -0.5,
        block_q=block_q, block_k=block_k, skip_masked=skip_masked,
        debug_visited=debug_visited)
    specs = [pl.BlockSpec((1, block_k, hd), lambda b, i, kk: (b, kk, 0)),
             pl.BlockSpec((1, block_k, hd), lambda b, i, kk: (b, kk, 0))]
    return _call(kern, q, (k, v), specs, block_q=block_q, block_k=block_k,
                 t=t, debug_visited=debug_visited, interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("mx_k", "mx_v", "causal", "block_q", "block_k",
                     "skip_masked", "debug_visited", "interpret"))
def mx_flash_attention_pallas(q, kp, ks8, vp, vs8, *, mx_k, mx_v=None,
                              causal: bool = True, block_q: int = 128,
                              block_k: int = 128, skip_masked: bool = True,
                              debug_visited: bool = False,
                              interpret: bool = False):
    """Flash attention over *packed* MX KV (DESIGN.md §11).

    ``q [BH, S, hd]`` carrier precision; ``(kp, ks8)`` / ``(vp, vs8)``
    are ``ops.mx_quantize(k/v, mx, packed=True)``: payload
    ``[BH, T, hd·w/8]`` uint8 and E8M0 codes ``[BH, T, hd/group]`` —
    group scales run along the head dimension (the contraction axis of
    the q·kᵀ dot; for p·v the pow2 fold is per output column, equally
    exact).  Tiles stream packed from HBM and decode in-register; a
    0xFF scale code (non-finite group) decodes NaN and poisons exactly
    the rows that attend to it.

    Bit-exact vs ``ref.mx_flash_attention_ref`` on exact-arithmetic
    operands (``tests/fuzz.exact_attention_operands``) — the same bar
    every codec kernel meets.

    Tile-legality contract (DESIGN.md §11/§14): ``block_q`` | S and
    ``block_k`` | T exactly (positional mask — assert, don't pad), hd a
    whole number of groups; on compiled TPU ``block_q`` is a sublane
    8-multiple and the packed hd byte run a 128-multiple lane tile
    (``ops.mx_quantize_kv`` guarantees it for hd % group == 0).  Any
    legal tile choice is bitwise-equivalent — the §14 autotune axis.
    """
    from ..core.formats import get_mx_format
    mx_k = get_mx_format(mx_k)
    mx_v = mx_k if mx_v is None else get_mx_format(mx_v)
    ck, cv = get_codec(mx_k), get_codec(mx_v)
    g = mx_k.group
    assert mx_v.group == g, (mx_k.name, mx_v.name)
    bh, s, hd = q.shape
    t = kp.shape[1]
    assert s % block_q == 0 and t % block_k == 0, ((s, t),
                                                   (block_q, block_k))
    assert hd % g == 0, (hd, g)
    assert kp.shape == (bh, t, ck.packed_cols(hd)), (kp.shape, (bh, t, hd))
    assert vp.shape == (bh, t, cv.packed_cols(hd)), (vp.shape, (bh, t, hd))
    assert ks8.shape == vs8.shape == (bh, t, hd // g), (ks8.shape, vs8.shape)
    # scale codes enter the kernel at element resolution (compact
    # [.., hd/32] grids are lane-illegal on compiled TPU — the §8 rule,
    # one u8 per element; the repeat is exact and nearly free vs the
    # f32-wide value path it replaces)
    ks8e = jnp.repeat(ks8, g, axis=-1)
    vs8e = jnp.repeat(vs8, g, axis=-1)

    def load_kv(refs):
        kp_ref, ks_ref, vp_ref, vs_ref = refs[:4]

        def loader(kk):
            return (ck.decode_tile(kp_ref[0]) * e8m0_decode(ks_ref[0]),
                    cv.decode_tile(vp_ref[0]) * e8m0_decode(vs_ref[0]))

        return loader, None, refs[4:]

    kern = functools.partial(
        _kernel, load_kv=load_kv, causal=causal, scale=hd ** -0.5,
        block_q=block_q, block_k=block_k, skip_masked=skip_masked,
        debug_visited=debug_visited)
    pk, pv = ck.packed_cols(hd), cv.packed_cols(hd)
    specs = [pl.BlockSpec((1, block_k, pk), lambda b, i, kk: (b, kk, 0)),
             pl.BlockSpec((1, block_k, hd), lambda b, i, kk: (b, kk, 0)),
             pl.BlockSpec((1, block_k, pv), lambda b, i, kk: (b, kk, 0)),
             pl.BlockSpec((1, block_k, hd), lambda b, i, kk: (b, kk, 0))]
    return _call(kern, q, (kp, ks8e, vp, vs8e), specs, block_q=block_q,
                 block_k=block_k, t=t, debug_visited=debug_visited,
                 interpret=interpret)
