"""Mixture-of-Experts FFN: sort-based token grouping (MaxText/MegaBlocks
style), expert-parallel over the ``model`` mesh axis.

Dispatch avoids the O(T*E*C) one-hot tensors: top-k expert ids are sorted,
tokens are scattered into a capacity-bounded [E, C, D] buffer (dropping
overflow — standard capacity-factor semantics), each expert runs a dense
(quantized, expanding-GEMM) FFN over its buffer, and results are gathered
back weighted by router probabilities. GSPMD turns the data->expert
resharding into all-to-alls on the ``model`` axis.

Arctic's "dense residual" (a parallel always-on FFN) is supported via
``cfg.moe_dense_ff``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import functools

from ..compat import shard_map
from jax.sharding import PartitionSpec as P

from ..core.formats import get_mx_format
from ..core.linear import linear
from . import layers

__all__ = ["init_moe", "moe_ffn"]


def _ep_capacity(cfg, t_loc: int, e_pad: int) -> int:
    """Per-expert buffer capacity on the EP path.  Clamped to the local
    token supply (``t_loc * k`` routes exist in total — a capacity above
    that only allocates dispatch buffer that can never fill, which for
    large ``capacity_factor`` made the a2a buffers *bigger* than the
    token stream they carry)."""
    c = int(cfg.top_k * t_loc * cfg.capacity_factor / e_pad)
    return max(8, min(c, t_loc * cfg.top_k))


def _aux_metrics(loss, keep, cap, axis=None, ba=()):
    """The aux dict both MoE paths return: the router load-balancing
    ``loss`` (what the trainer adds to CE), the realized ``drop_frac``
    (fraction of (token, k) routes beyond capacity — the observable the
    capacity clamp trades against), and the ``capacity`` itself."""
    drop = 1.0 - jnp.mean(keep.astype(jnp.float32))
    if axis is not None:
        drop = jax.lax.pmean(jax.lax.pmean(drop, axis), ba)
    return {"loss": loss, "drop_frac": drop,
            "capacity": jnp.float32(cap)}


def _ep_applicable(x, cfg, rules):
    if rules is None or rules.mesh is None or rules.model_size <= 1:
        return False
    dp = 1
    for a in rules.batch_axes:
        dp *= rules.mesh.shape[a]
    if not (x.ndim == 3 and x.shape[0] % dp == 0 and dp > 0):
        return False
    # capacity padding dominates when local tokens << experts (decode with
    # tiny per-shard batches) — the local einsum dispatch is cheaper there
    tp = rules.model_size
    e_pad = -(-cfg.n_experts // tp) * tp
    t_loc = (x.shape[0] // dp) * x.shape[1]
    return t_loc * cfg.top_k >= e_pad


def moe_ffn_ep(x, p, cfg, policy, *, rules, impl="auto"):
    """Expert-parallel MoE via fully-manual shard_map (§Perf G1).

    Tokens are batch-sharded; experts are sharded over the ``model`` axis
    (padded to a multiple of it). Each shard routes its own tokens, sorts
    them by expert, ships capacity-bounded buffers — packed MX payloads +
    E8M0 group grids under MX policies (DESIGN.md §13), carrier bf16
    otherwise — with ONE all-to-all, runs its local experts, and ships
    results back with a second all-to-all. No GSPMD resharding of the dispatch tensors can
    occur — this replaces the O(10 TB) gather/AR storm the einsum dispatch
    generates at 256 chips.
    """
    mesh, axis, tp = rules.mesh, rules.model_axis, rules.model_size
    ba = rules.batch_axes
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    e_pad = -(-e // tp) * tp
    epl = e_pad // tp
    manual = set(ba) | {axis}

    # pad expert weights/router on the expert dim (outside the manual region)
    wg = jnp.pad(p["experts"]["w_gate"], ((0, e_pad - e), (0, 0), (0, 0)))
    wu = jnp.pad(p["experts"]["w_up"], ((0, e_pad - e), (0, 0), (0, 0)))
    wo = jnp.pad(p["experts"]["w_out"], ((0, e_pad - e), (0, 0), (0, 0)))
    router = jnp.pad(p["router"].astype(jnp.float32),
                     ((0, 0), (0, e_pad - e)))  # logits masked inside

    dp = 1
    for a in ba:
        dp *= mesh.shape[a]
    t_loc = (b // dp) * s
    cap = _ep_capacity(cfg, t_loc, e_pad)
    manual = manual | {rules.fsdp_axis}
    from ..parallel.tp_gemm import make_fsdp_gather, mx_dispatch_a2a
    # packed dispatch wire (DESIGN.md §13): MX policies ship both
    # dispatch a2as as codec payloads + E8M0 grids over groups of 32
    # along d_model — activations in the forward element format, the
    # dispatch cotangent in the backward one.  Misaligned d_model keeps
    # the raw carrier a2a (the grid would cut a group).
    mx_fwd = get_mx_format(policy.mx_fwd) if policy.mx else None
    mx_bwd = get_mx_format(policy.mx_bwd_name) if policy.mx else None
    use_mx_wire = mx_fwd is not None and d % mx_fwd.group == 0

    def dispatch_a2a(buf):
        if use_mx_wire:
            return mx_dispatch_a2a(buf, axis, mx_fwd, mx_bwd)
        return jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=0,
                                  tiled=True)
    # w_gate/w_up are [E, D(fsdp), F]; w_out is [E, F, D(fsdp)]
    fsdp_gather1 = make_fsdp_gather(rules, dim=1)
    fsdp_gather2 = make_fsdp_gather(rules, dim=2)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(ba, None, None), P(None, None),
                  P(axis, rules.fsdp_axis, None),
                  P(axis, rules.fsdp_axis, None),
                  P(axis, None, rules.fsdp_axis)),
        out_specs=(P(ba, None, None), P()),
        axis_names=manual, check_vma=False)
    def ep(xl, rtr, wgl, wul, wol):
        # ZeRO-3 weight gather inside the manual region: no boundary
        # resharding, narrow-wire gradient RS on the way back (§Perf G2)
        wgl = fsdp_gather1(wgl)
        wul = fsdp_gather1(wul)
        wol = fsdp_gather2(wol)
        bl = xl.shape[0]
        xt = xl.reshape(bl * s, d)
        t = bl * s
        logits = jnp.dot(xt.astype(jnp.float32), rtr)
        # mask the padded expert columns (never routable)
        eidx = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        logits = jnp.where(eidx < e, logits, -1e9)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, eid = jax.lax.top_k(probs, k)
        gate = gate / jnp.sum(gate, -1, keepdims=True)

        me = jnp.mean(probs[:, :e], axis=0)
        ce = jnp.mean(jax.nn.one_hot(eid[:, 0], e, dtype=jnp.float32), 0)
        aux = cfg.router_aux_coef * e * jnp.sum(me * ce)
        aux = jax.lax.pmean(jax.lax.pmean(aux, axis), ba)

        # local sort-based dispatch into [e_pad * cap, d]
        flat_e = eid.reshape(-1)
        order = jnp.argsort(flat_e)
        tok_of = order // k
        se = flat_e[order]
        pos = jnp.arange(t * k)
        seg = jnp.searchsorted(se, jnp.arange(e_pad), side="left")
        rank = pos - seg[se]
        keep = rank < cap
        slot = jnp.where(keep, se * cap + rank, e_pad * cap)
        send = jnp.zeros((e_pad * cap + 1, d), xl.dtype
                         ).at[slot].set(xt[tok_of])[:-1]
        # ship to expert shards: [tp, epl*cap, d] -> a2a -> local experts
        send = send.reshape(tp, epl * cap, d)
        recv = dispatch_a2a(send)
        buf = recv.reshape(tp, epl, cap, d).transpose(1, 0, 2, 3) \
                  .reshape(epl, tp * cap, d)

        def expert(xb, g_, u_, o_):
            gg = linear(xb, g_, policy=policy, impl=impl)
            uu = linear(xb, u_, policy=policy, impl=impl)
            hh = jax.nn.silu(gg.astype(jnp.float32)).astype(gg.dtype) * uu
            return linear(hh, o_, policy=policy, impl=impl)

        out = jax.vmap(expert)(buf, wgl, wul, wol)
        out = out.reshape(epl, tp, cap, d).transpose(1, 0, 2, 3) \
                 .reshape(tp, epl * cap, d)
        back = dispatch_a2a(out)
        flat_out = back.reshape(e_pad * cap, d)
        gathered = jnp.where(keep[:, None],
                             flat_out[jnp.where(keep, slot, 0)], 0)
        contrib = gathered * gate.reshape(-1)[order][:, None].astype(xl.dtype)
        yt = jnp.zeros((t, d), jnp.float32).at[tok_of].add(
            contrib.astype(jnp.float32))
        return (yt.astype(xl.dtype).reshape(bl, s, d),
                _aux_metrics(aux, keep, cap, axis=axis, ba=ba))

    y, aux = ep(x, router, wg, wu, wo)
    if cfg.moe_dense_ff:
        y = y + layers.mlp(x, p["dense"], cfg, policy, rules=rules,
                           impl=impl)
    return y, aux


def init_moe(key, cfg, dtype):
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 5)
    s = d ** -0.5
    p = {
        "router": jax.random.normal(ks[0], (d, e), jnp.float32) * s,
        "experts": {
            "w_gate": jax.random.normal(ks[1], (e, d, f), dtype) * s,
            "w_up": jax.random.normal(ks[2], (e, d, f), dtype) * s,
            "w_out": jax.random.normal(ks[3], (e, f, d), dtype) * (f ** -0.5),
        },
    }
    if cfg.moe_dense_ff:
        p["dense"] = layers.init_mlp(ks[4], cfg, dtype, d_ff=cfg.moe_dense_ff)
    return p


def _capacity(cfg, n_tokens: int) -> int:
    c = int(cfg.top_k * n_tokens * cfg.capacity_factor / cfg.n_experts)
    return max(8, min(c, n_tokens))


@jax.named_scope("moe")
def moe_ffn(x, p, cfg, policy, *, rules=None, impl="auto"):
    """x [B,S,D] -> ([B,S,D], aux) where ``aux`` is the metrics dict of
    ``_aux_metrics`` (``aux["loss"]`` is what joins the objective).
    Dispatches to the explicit expert-parallel path on multi-device
    meshes (§Perf G1); the einsum path below is the single-device /
    reference implementation."""
    if _ep_applicable(x, cfg, rules):
        return moe_ffn_ep(x, p, cfg, policy, rules=rules, impl=impl)
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k
    xt = x.reshape(t, d)

    # --- router (fp32: small and accuracy-critical; never quantized) ---
    logits = jnp.dot(xt.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)                     # [T,E]
    gate, eid = jax.lax.top_k(probs, k)                         # [T,k]
    gate = gate / jnp.sum(gate, -1, keepdims=True)

    # load-balancing aux loss (Switch-style)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(eid[:, 0], e, dtype=jnp.float32), axis=0)
    aux = cfg.router_aux_coef * e * jnp.sum(me * ce)

    # --- sort-based dispatch into [E, C, D] ---
    cap = _capacity(cfg, t)
    flat_e = eid.reshape(-1)                                    # [T*k]
    order = jnp.argsort(flat_e)                                 # stable
    tok_of = order // k                                         # token index
    se = flat_e[order]
    # rank within expert segment
    pos = jnp.arange(t * k)
    seg_start = jnp.searchsorted(se, jnp.arange(e), side="left")  # [E]
    rank = pos - seg_start[se]
    keep = rank < cap
    slot = jnp.where(keep, se * cap + rank, e * cap)            # overflow bin
    buf = jnp.zeros((e * cap + 1, d), x.dtype).at[slot].set(xt[tok_of])
    buf = buf[:-1].reshape(e, cap, d)
    if rules is not None:
        buf = rules.act(buf, "experts", None, None)

    # --- expert FFN (batched over experts; quantized expanding GEMMs) ---
    def expert_mlp(xb, wg, wu, wo):
        g = linear(xb, wg, policy=policy, impl=impl)
        u = linear(xb, wu, policy=policy, impl=impl)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(g.dtype) * u
        return linear(h, wo, policy=policy, impl=impl)

    out_buf = jax.vmap(expert_mlp)(buf, p["experts"]["w_gate"],
                                   p["experts"]["w_up"], p["experts"]["w_out"])
    if rules is not None:
        out_buf = rules.act(out_buf, "experts", None, None)

    # --- gather back + combine with gate weights ---
    flat_out = out_buf.reshape(e * cap, d)
    gathered = jnp.where(keep[:, None], flat_out[jnp.where(keep, slot, 0)], 0)
    contrib = gathered * gate.reshape(-1)[order][:, None].astype(x.dtype)
    yt = jnp.zeros((t, d), jnp.float32).at[tok_of].add(
        contrib.astype(jnp.float32))
    y = yt.astype(x.dtype).reshape(b, s, d)

    if cfg.moe_dense_ff:
        y = y + layers.mlp(x, p["dense"], cfg, policy, rules=rules, impl=impl)
    return y, _aux_metrics(aux, keep, cap)
