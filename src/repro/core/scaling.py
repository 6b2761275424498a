"""Scaling machinery for narrow formats (DESIGN.md §3).

Two independent mechanisms live here:

* **dynamic loss scaling** — required hygiene for narrow-range gradient
  formats (fp16 / FP8-E5M2 per-tensor-scaled).  Classic scheme: multiply
  the loss by ``scale``; unscale gradients; if any gradient is
  non-finite, skip the update and halve the scale; after
  ``growth_interval`` clean steps, double it (capped).

* **per-block quantization scales** — one dequant factor per
  (row-tile × K-tile) of a GEMM operand, instead of one per tensor.
  Flexpoint-style shared exponents and Graphcore's block formats both
  show this is what makes 8-bit training robust to outliers: the scale
  tracks the local amax, so a single huge activation no longer flushes
  the rest of the tensor into the subnormal mud.  ``BlockScaleConfig``
  is the knob threaded through policy → linear → kernels; scales default
  to powers of two (MX-style), which makes the quantize/dequant rescale
  *exact* — quantization error then comes only from the mantissa
  rounding, never from the scaling itself.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

__all__ = ["loss_scale_init", "check_and_update_scale",
           "BlockScaleConfig", "compute_block_scales", "apply_block_scales",
           "compute_group_scales", "group_scales_from_amax",
           "apply_group_scales", "expand_group_scales", "pow2_reciprocal",
           "block_loss_scale_init", "check_and_update_block_scales"]


# ---------------------------------------------------------------------------
# Per-block quantization scales (DESIGN.md §3)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BlockScaleConfig:
    """Granularity + rounding of per-block dequantization scales.

    A GEMM operand ``A[M, K]`` gets one f32 scale per
    ``(block_m, block_k)`` tile (``B[K, N]`` per ``(block_k, block_n)``),
    so the fused kernel can dequantize each partial product at
    accumulator granularity: the fp32 accumulator stays wide across the
    whole K loop and is rounded once — eq. 1's structure, per block.
    """

    #: row-tile of the left operand / output rows
    block_m: int = 128
    #: column-tile of the right operand / output columns
    block_n: int = 128
    #: K-tile shared by both operands (scale granularity on the
    #: contraction axis == the kernel's accumulation granularity)
    block_k: int = 128
    #: headroom: quantized amax lands at ``margin * max_normal``
    margin: float = 1.0
    #: round scales up to powers of two (MX-style shared exponents);
    #: pow2 rescaling is exact, so dequant introduces no extra rounding
    pow2: bool = True

    @classmethod
    def from_policy(cls, policy) -> "BlockScaleConfig | None":
        """The config a ``Policy`` asks for (None = per-tensor scaling).

        ``margin``/``pow2`` come from the policy's ``block_margin`` /
        ``block_pow2`` fields, so policies can express quantization
        headroom instead of the fields being silently dropped here.
        """
        n = int(getattr(policy, "block_scale", 0) or 0)
        if n <= 0:
            return None
        return cls(block_m=n, block_n=n, block_k=n,
                   margin=float(getattr(policy, "block_margin", 1.0)),
                   pow2=bool(getattr(policy, "block_pow2", True)))


def _pow2_ceil(x: jax.Array) -> jax.Array:
    """Smallest power of two >= x, exact, for normal-range f32 x > 0.

    Built from exponent bits (``jnp.exp2`` is approximate on CPU XLA).
    """
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    exp = ((bits >> 23) & jnp.uint32(0xFF)).astype(jnp.int32)   # biased
    man = bits & jnp.uint32(0x7FFFFF)
    # 2**(e+1) unless x is already an exact power of two
    exp = jnp.where(man == 0, exp, exp + 1)
    pow2 = jax.lax.bitcast_convert_type(
        (jnp.clip(exp, 1, 254).astype(jnp.uint32) << 23), jnp.float32)
    return pow2


def compute_block_scales(x: jax.Array, block_r: int, block_c: int,
                         q_dtype, *, margin: float = 1.0,
                         pow2: bool = True) -> jax.Array:
    """Per-(block_r × block_c)-tile dequant scales for ``x[..., R, C]``.

    Returns ``s[..., R//block_r, C//block_c]`` (f32) such that ``x / s``
    (broadcast per tile) fills ``q_dtype``'s range: quantized ≈ x / s,
    dequantized = quantized * s.  All-zero tiles get scale 1.  Shapes
    must already be padded to tile multiples (``kernels.ops`` pads).

    Leading dims are batch: tiles never cross them, so a 3D activation
    gets per-(batch, row-tile × col-tile) granularity at native rank —
    sequence-sharded leading dims survive without a flatten.

    Tiles whose amax is non-finite get scale 1 so the ``inf``/``NaN``
    elements propagate through quantize → dequant into the output (and
    from there to ``check_and_update_scale``'s skip logic) instead of
    being laundered into zeros by an ``inf`` scale.
    """
    *lead, r, c = x.shape
    assert r % block_r == 0 and c % block_c == 0, ((r, c), (block_r, block_c))
    xb = jnp.abs(x.astype(jnp.float32)).reshape(
        *lead, r // block_r, block_r, c // block_c, block_c)
    amax = jnp.max(xb, axis=(-3, -1))
    max_normal = jnp.float32(jnp.finfo(q_dtype).max)
    s = amax / (max_normal * jnp.float32(margin))
    if pow2:
        s = _pow2_ceil(jnp.maximum(s, jnp.float32(2.0 ** -126)))
    return jnp.where((amax > 0) & jnp.isfinite(amax), s, jnp.float32(1.0))


def apply_block_scales(x: jax.Array, s: jax.Array, block_r: int,
                       block_c: int, *, inverse: bool = False) -> jax.Array:
    """Broadcast per-tile scales over ``x[..., R, C]``: ``x * s`` per
    (block_r × block_c) tile (``inverse=True`` divides — the quantize
    direction). ``s[..., R//block_r, C//block_c]`` as produced by
    ``compute_block_scales``; leading dims are batch."""
    *lead, r, c = x.shape
    xb = x.reshape(*lead, r // block_r, block_r, c // block_c, block_c)
    st = s[..., :, None, :, None]
    xb = xb / st if inverse else xb * st
    return xb.reshape(x.shape)


# ---------------------------------------------------------------------------
# MX group scales: shared exponents over groups of 32 along K (DESIGN.md §8)
# ---------------------------------------------------------------------------

def compute_group_scales(x: jax.Array, group: int, elem_max: float,
                         *, nan_scale: bool = True) -> jax.Array:
    """E8M0 shared scales for ``x[..., K]``: one power-of-two f32 scale
    per ``group`` consecutive elements of the last axis.

    Returns ``s[..., K//group]`` such that ``x / s`` (broadcast per
    group) fills the element format's range ``[-elem_max, elem_max]``.
    E8M0 semantics: the scale is *pow2-only* (no mantissa — ``_pow2_ceil``
    on exponent bits, so the quantize/dequant rescale is exact) and fits
    the 8-bit biased-exponent code: values clamp to [2^-126, 2^127]
    (within E8M0's [2^-127, 2^127] window).  All-zero groups get the
    neutral scale 1.  A group whose amax is non-finite gets scale NaN —
    the E8M0 NaN encoding (0xFF): the whole group reads back NaN, which
    propagates to ``check_and_update_scale``'s skip logic.  Pass
    ``nan_scale=False`` for the f32-path convention (neutral scale 1,
    per-element poison) instead.

    Unlike ``compute_block_scales``' 2-D tiles, groups are 1×``group``
    strips along the contraction axis only — K-granular, M-exact — so a
    single outlier perturbs at most 31 neighbours' quantization.
    """
    *lead, k = x.shape
    assert k % group == 0, (k, group)
    xg = jnp.abs(x.astype(jnp.float32)).reshape(*lead, k // group, group)
    return group_scales_from_amax(jnp.max(xg, axis=-1), elem_max,
                                  nan_scale=nan_scale)


def group_scales_from_amax(amax: jax.Array, elem_max: float,
                           *, nan_scale: bool = True) -> jax.Array:
    """The E8M0 scale formula of ``compute_group_scales`` applied to
    group amaxes already reduced (at any resolution — the Pallas
    kernels reduce in-register and keep one amax per element)."""
    s = _pow2_ceil(jnp.maximum(amax / jnp.float32(elem_max),
                               jnp.float32(2.0 ** -126)))
    s = jnp.where(amax > 0, s, jnp.float32(1.0))
    bad = jnp.float32(jnp.nan) if nan_scale else jnp.float32(1.0)
    return jnp.where(jnp.isfinite(amax), s, bad)


def expand_group_scales(s: jax.Array, group: int) -> jax.Array:
    """Broadcast per-group scales to element resolution along the last
    axis: ``s[..., K/group] -> [..., K]``, each scale repeated over its
    1×``group`` strip.  The single definition of the group layout —
    the fused kernels, the jnp refs and the GEMM wrappers all expand
    through here, so kernel/oracle bit-exactness can't silently
    desynchronize on a layout change."""
    return jnp.repeat(s, group, axis=-1)


def apply_group_scales(x: jax.Array, s: jax.Array, group: int,
                       *, inverse: bool = False) -> jax.Array:
    """Broadcast per-group scales over ``x[..., K]``: ``x * s`` per
    ``group``-element strip (``inverse=True`` divides — the quantize
    direction).  Exact for pow2 scales."""
    se = expand_group_scales(s, group).reshape(x.shape)
    return x * pow2_reciprocal(se) if inverse else x * se


def pow2_reciprocal(s: jax.Array) -> jax.Array:
    """``1 / s`` for power-of-two f32 scales in [2^-126, 2^126] (every
    E8M0 group scale), exact by construction: the biased exponent
    flips to ``254 - e``.  NaN stays NaN.  Quantizing is then ``x *
    pow2_reciprocal(s)``, the correctly rounded ``x / s`` on every
    backend — a TPU divide is a reciprocal estimate plus refinement,
    and a last-bit slip there moves a rounding tie of the narrow cast.
    """
    bits = jax.lax.bitcast_convert_type(s.astype(jnp.float32), jnp.uint32)
    e = (bits >> 23) & jnp.uint32(0xFF)
    r = jax.lax.bitcast_convert_type((jnp.uint32(254) - e) << 23,
                                     jnp.float32)
    return jnp.where(jnp.isnan(s), s, r)


def loss_scale_init(initial: float = 2.0 ** 15):
    return {"scale": jnp.float32(initial),
            "good_steps": jnp.zeros((), jnp.int32)}


def check_and_update_scale(state, grads, *, growth_interval: int = 2000,
                           factor: float = 2.0, max_scale: float = 2.0 ** 24):
    """Returns (unscaled_grads, new_state, skip_update)."""
    finite = jnp.array(True)
    for g in jax.tree.leaves(grads):
        finite &= jnp.all(jnp.isfinite(g.astype(jnp.float32)))
    scale = state["scale"]
    unscaled = jax.tree.map(
        lambda g: (g.astype(jnp.float32) / scale).astype(g.dtype), grads)
    good = jnp.where(finite, state["good_steps"] + 1, 0)
    grow = good >= growth_interval
    new_scale = jnp.where(
        ~finite, jnp.maximum(scale / factor, 1.0),
        jnp.where(grow, jnp.minimum(scale * factor, max_scale), scale))
    new_state = {"scale": new_scale,
                 "good_steps": jnp.where(grow, 0, good)}
    return unscaled, new_state, ~finite


# ---------------------------------------------------------------------------
# Per-block dynamic loss scaling (DESIGN.md §8)
# ---------------------------------------------------------------------------

def block_loss_scale_init(n_blocks: int, initial: float = 2.0 ** 15):
    """Per-row-tile loss-scale state: ``n_blocks`` independent scales.

    The classic scheme keys the *whole step* off the worst tensor: one
    inf anywhere halves the single global scale and skips everything.
    With per-block state, each row tile (e.g. a microbatch's slice of
    the token axis) carries its own scale and good-step counter, so a
    divergence in one block backs off only that block's scale while the
    rest keep growing — the loss-scaling analogue of per-block
    quantization scales.
    """
    return {"scale": jnp.full((n_blocks,), initial, jnp.float32),
            "good_steps": jnp.zeros((n_blocks,), jnp.int32)}


def check_and_update_block_scales(state, grad, *, growth_interval: int = 2000,
                                  factor: float = 2.0,
                                  max_scale: float = 2.0 ** 24):
    """Per-row-tile variant of ``check_and_update_scale``.

    ``grad``'s leading axis is split into ``n_blocks = state['scale'].shape[0]``
    equal contiguous row tiles, each scaled by its own ``scale[b]``.
    Returns ``(unscaled, new_state, skip)`` where ``skip[b]`` is True for
    tiles whose gradients contain inf/NaN — their unscaled values are not
    trustworthy and their scale has been backed off (floor 1.0); finite
    tiles follow the usual growth schedule (×``factor`` after
    ``growth_interval`` clean steps, capped at ``max_scale``).

    Composes with the global skip logic: ``skip.any()`` is exactly the
    ``check_and_update_scale`` skip decision, so a trainer can either
    mask per-tile updates or fall back to skipping the whole step.
    """
    n = state["scale"].shape[0]
    m = grad.shape[0]
    assert m % n == 0, (m, n)
    gb = grad.astype(jnp.float32).reshape(n, m // n, *grad.shape[1:])
    finite = jnp.all(jnp.isfinite(gb), axis=tuple(range(1, gb.ndim)))
    scale = state["scale"]
    bshape = (n,) + (1,) * (gb.ndim - 1)
    unscaled = (gb / scale.reshape(bshape)).reshape(grad.shape).astype(
        grad.dtype)
    good = jnp.where(finite, state["good_steps"] + 1, 0)
    grow = good >= growth_interval
    new_scale = jnp.where(
        ~finite, jnp.maximum(scale / factor, 1.0),
        jnp.where(grow, jnp.minimum(scale * factor, max_scale), scale))
    new_state = {"scale": new_scale,
                 "good_steps": jnp.where(grow, jnp.zeros_like(good), good)}
    return unscaled, new_state, ~finite
