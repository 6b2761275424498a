"""AdamW with low-precision state options — the optimizer-side half of the
paper's story.

Master weights and moments can each be stored narrow (bf16/fp16) while the
*update arithmetic* is always f32 ("accumulate wide, store narrow" — the
ExSdotp rule applied to the optimizer). Optional stochastic rounding on the
param downcast removes the bias that RNE introduces when |update| << ulp —
the standard companion trick for low-precision training at scale.

State layout mirrors the param tree leaf-for-leaf, so ZeRO partitioning is
just "shard the state like the params" (parallel/sharding.py) and gradient
reduce-scatter falls out of GSPMD.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

__all__ = ["AdamWConfig", "adamw_init", "adamw_update"]


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    master_dtype: jnp.dtype = jnp.float32
    moment_dtype: jnp.dtype = jnp.float32
    stochastic_round: bool = False
    warmup_steps: int = 100
    schedule: str = "cosine"      # cosine | constant
    total_steps: int = 10_000


def _lr_at(cfg: AdamWConfig, step):
    step = step.astype(jnp.float32)
    warm = jnp.minimum(1.0, (step + 1) / max(cfg.warmup_steps, 1))
    if cfg.schedule == "cosine":
        t = jnp.clip((step - cfg.warmup_steps)
                     / max(cfg.total_steps - cfg.warmup_steps, 1), 0.0, 1.0)
        decay = 0.5 * (1 + jnp.cos(jnp.pi * t))
    else:
        decay = 1.0
    return cfg.lr * warm * decay


def adamw_init(params, cfg: AdamWConfig):
    return {
        "step": jnp.zeros((), jnp.int32),
        "master": jax.tree.map(
            lambda p: p.astype(cfg.master_dtype), params),
        "m": jax.tree.map(
            lambda p: jnp.zeros(p.shape, cfg.moment_dtype), params),
        "v": jax.tree.map(
            lambda p: jnp.zeros(p.shape, cfg.moment_dtype), params),
    }


def _stochastic_cast(x_f32, dtype, key):
    """Stochastic rounding f32 -> dtype (unbiased downcast).

    The next representable value toward ``x`` is computed sign-aware on
    the sign/magnitude encoding: incrementing raw bits only walks the
    value lattice within one sign, and ``lo == -0.0`` (raw 0x8000)
    decrements straight into the NaN space (0x7FFF) if treated as "a
    negative number, step the integer".  Split sign bit and magnitude,
    step the magnitude, and flip the sign when the step crosses zero —
    updates in (-ulp, 0) land on -0.0 and must round toward the first
    *negative* subnormal, not truncate.
    """
    lo = x_f32.astype(dtype)
    lof = lo.astype(jnp.float32)
    nbits = 16 if dtype in (jnp.bfloat16, jnp.float16) else 8
    ui = jnp.uint16 if nbits == 16 else jnp.uint8
    bits = jax.lax.bitcast_convert_type(lo, ui).astype(jnp.int32)
    sign = bits >> (nbits - 1)
    mag = bits & ((1 << (nbits - 1)) - 1)
    up = x_f32 > lof          # step toward +inf (else toward -inf)
    # magnitude delta for a value-lattice step: +1 if the step moves
    # away from zero on this sign, -1 if toward zero
    mag_step = jnp.where(sign == 0, jnp.where(up, 1, -1),
                         jnp.where(up, -1, 1))
    nmag = mag + mag_step
    nsign = jnp.where(nmag < 0, 1 - sign, sign)   # ±0 crossing
    nmag = jnp.abs(nmag)
    nxt = jax.lax.bitcast_convert_type(
        ((nsign << (nbits - 1)) | nmag).astype(ui), dtype).astype(jnp.float32)
    span = nxt - lof
    frac = jnp.where(span != 0, (x_f32 - lof) / jnp.where(span == 0, 1, span),
                     0.0)
    u = jax.random.uniform(key, x_f32.shape)
    return jnp.where(u < jnp.abs(frac), nxt, lof).astype(dtype)


@jax.named_scope("optimizer")
def adamw_update(grads, opt_state, params, cfg: AdamWConfig,
                 *, skip: Optional[jax.Array] = None, rng=None):
    """One step. ``skip`` (bool scalar) freezes everything (loss-scale
    overflow); gradients are f32-upcast, globally clipped, and every
    arithmetic op runs in f32 regardless of storage dtypes."""
    step = opt_state["step"]
    gf = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(gf)))
    clip = jnp.minimum(1.0, cfg.grad_clip / (gnorm + 1e-9))
    gf = jax.tree.map(lambda g: g * clip, gf)
    lr = _lr_at(cfg, step)
    b1, b2 = cfg.b1, cfg.b2
    bc1 = 1 - b1 ** (step.astype(jnp.float32) + 1)
    bc2 = 1 - b2 ** (step.astype(jnp.float32) + 1)
    if skip is None:
        skip = jnp.zeros((), bool)

    leaves, treedef = jax.tree_util.tree_flatten(params)
    keys = (list(jax.random.split(rng, len(leaves))) if rng is not None
            else [None] * len(leaves))
    keytree = jax.tree_util.tree_unflatten(treedef, keys)

    def upd(g, m, v, master, p, key):
        mf = b1 * m.astype(jnp.float32) + (1 - b1) * g
        vf = b2 * v.astype(jnp.float32) + (1 - b2) * g * g
        mhat = mf / bc1
        vhat = vf / bc2
        mw = master.astype(jnp.float32)
        upd = mhat / (jnp.sqrt(vhat) + cfg.eps) + cfg.weight_decay * mw
        neww = mw - lr * upd
        # skip: keep previous state bit-for-bit
        neww = jnp.where(skip, mw, neww)
        mf = jnp.where(skip, m.astype(jnp.float32), mf)
        vf = jnp.where(skip, v.astype(jnp.float32), vf)
        if cfg.stochastic_round and key is not None and p.dtype in (
                jnp.bfloat16, jnp.float16):
            newp = _stochastic_cast(neww, p.dtype, key)
        else:
            newp = neww.astype(p.dtype)
        return (mf.astype(cfg.moment_dtype), vf.astype(cfg.moment_dtype),
                neww.astype(cfg.master_dtype), newp)

    out = jax.tree.map(upd, gf, opt_state["m"], opt_state["v"],
                       opt_state["master"], params, keytree,
                       is_leaf=lambda x: x is None)
    m = jax.tree.map(lambda o: o[0], out, is_leaf=lambda x: isinstance(x, tuple))
    v = jax.tree.map(lambda o: o[1], out, is_leaf=lambda x: isinstance(x, tuple))
    master = jax.tree.map(lambda o: o[2], out,
                          is_leaf=lambda x: isinstance(x, tuple))
    newp = jax.tree.map(lambda o: o[3], out,
                        is_leaf=lambda x: isinstance(x, tuple))
    new_state = {"step": step + jnp.where(skip, 0, 1), "master": master,
                 "m": m, "v": v}
    return newp, new_state, {"grad_norm": gnorm, "lr": lr}
