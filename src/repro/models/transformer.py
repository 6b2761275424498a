"""Model assembly for all assigned families.

Everything is scan-over-layers (stacked [L, ...] parameters) so compile
time and HLO size are O(1) in depth — required for the 80-layer dry-run
cells. Each family provides:

    init(key)                        -> params (compute dtype)
    apply(params, tokens, aux, ...)  -> (logits, aux_loss)   [train/prefill]
    init_cache(batch, max_len)       -> decode cache
    decode_step(params, tok, cache)  -> (logits, cache)      [1 token]
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from ..core.linear import linear
from ..core.policy import get_policy
from ..configs.base import ModelConfig
from . import layers as L
from . import moe as MOE
from . import mamba2 as M2
from . import xlstm as XL

Pytree = Any


@dataclasses.dataclass
class ModelApi:
    cfg: ModelConfig
    init: Callable
    apply: Callable          # (params, tokens, aux=None, ...) -> (logits, aux_loss)
    init_cache: Callable     # (batch, max_len) -> cache
    decode_step: Callable    # (params, tok[B], cache, ...) -> (logits[B,V], cache)
    #: decode_step also accepts tok [B, S] (block prefill: S tokens
    #: appended in one call, full [B, S, V] logits back) — attention
    #: families; recurrent families step strictly one token at a time.
    block_decode: bool = False

    def loss(self, params, tokens, aux=None, **kw):
        """Next-token cross-entropy, vocab-parallel safe.

        logsumexp reduces over the (possibly 'model'-sharded) vocab dim
        with scalar-sized collectives; the target logit is picked with an
        iota mask instead of take_along_axis, whose arbitrary-index gather
        would force GSPMD to all-gather the full logits (§Perf D1).
        """
        logits, aux_loss = self.apply(params, tokens, aux=aux, **kw)
        with jax.named_scope("loss"):
            tgt = tokens[:, 1:]
            lg = logits[:, :-1].astype(jnp.float32)
            lse = jax.nn.logsumexp(lg, axis=-1)
            vocab_iota = jax.lax.broadcasted_iota(jnp.int32, lg.shape, 2)
            picked = jnp.sum(jnp.where(vocab_iota == tgt[..., None], lg,
                                       0.0), axis=-1)
            ce = jnp.mean(lse - picked)
        return ce + aux_loss


# ---------------------------------------------------------------------------
# shared embedding / head
# ---------------------------------------------------------------------------

def _init_embed(key, cfg, dtype):
    k1, k2 = jax.random.split(key)
    p = {"embed": jax.random.normal(k1, (cfg.vocab_size, cfg.d_model),
                                    dtype) * 0.02}
    if not cfg.tie_embeddings:
        p["lm_head"] = jax.random.normal(
            k2, (cfg.d_model, cfg.vocab_size), dtype) * (cfg.d_model ** -0.5)
    return p


@jax.named_scope("embed")
def _embed(params, tokens, cfg, rules):
    from ..parallel.tp_gemm import embed_ep_applicable, embed_lookup_ep
    if rules is not None and embed_ep_applicable(tokens, params["embed"],
                                                 rules):
        # vocab-parallel lookup; lands sequence-sharded (§Perf G3)
        return embed_lookup_ep(params["embed"], tokens, rules)
    x = params["embed"][tokens]
    if rules is not None:
        x = rules.act(x, "batch", None, None)
    return x


@jax.named_scope("head")
def _head(params, x, cfg, policy, rules, impl):
    xn = L.apply_norm(x, params["final_norm"], cfg)
    w = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = linear(xn, w, policy=policy, impl=impl,
                    quantized=cfg.quantize_head)
    if rules is not None:
        logits = rules.logits(logits)
    return logits


# ---------------------------------------------------------------------------
# dense / MoE decoder family (deepseek, llama, qwen, stablelm, arctic,
# granite, and the LM backbone of internvl / whisper-decoder)
# ---------------------------------------------------------------------------

def _init_decoder_layer(key, cfg, dtype, cross_attn=False):
    ks = jax.random.split(key, 5)
    p = {
        "norm1": L.init_norm(cfg, dtype),
        "attn": L.init_attention(ks[0], cfg, dtype),
        "norm2": L.init_norm(cfg, dtype),
    }
    if cross_attn:
        p["norm_x"] = L.init_norm(cfg, dtype)
        p["xattn"] = L.init_attention(ks[1], cfg, dtype)
    if cfg.family == "moe":
        p["moe"] = MOE.init_moe(ks[2], cfg, dtype)
    else:
        p["mlp"] = L.init_mlp(ks[3], cfg, dtype)
    return p


def _gather_seq(x, rules, policy):
    """Megatron-SP block entry: ONE explicit all-gather of the
    sequence-sharded activations, consumed by all column-parallel GEMMs of
    the block (§Perf D2); reduce-scatter on the backward pass (D3).

    Skipped when the explicit TP-GEMM path applies — it gathers the
    fp8-quantized activations itself at 1/2-1/4 the wire bytes (D5)."""
    from ..parallel.tp_gemm import tp_applicable
    if rules is None or tp_applicable(x, rules, policy):
        return x
    return rules.gather_seq(x)


def _decoder_layer(x, lp, cfg, policy, *, positions, kv_cache=None,
                   cross_kv=None, x_cache=None, rules=None, impl="auto"):
    xn = _gather_seq(L.apply_norm(x, lp["norm1"], cfg), rules, policy)
    h, new_kv = L.attention(xn, lp["attn"], cfg, policy,
                            positions=positions,
                            kv_cache=kv_cache, rules=rules, impl=impl)
    x = x + h
    if cross_kv is not None:
        hx, _ = L.attention(
            _gather_seq(L.apply_norm(x, lp["norm_x"], cfg), rules, policy),
            lp["xattn"], cfg, policy, positions=positions,
            cross_kv=cross_kv, rules=rules, impl=impl)
        x = x + hx
    aux = jnp.zeros((), jnp.float32)
    xn = _gather_seq(L.apply_norm(x, lp["norm2"], cfg), rules, policy)
    if cfg.family == "moe":
        ff, moe_aux = MOE.moe_ffn(xn, lp["moe"], cfg, policy, rules=rules,
                                  impl=impl)
        aux = moe_aux["loss"]   # drop_frac/capacity are diagnostics
    else:
        ff = L.mlp(xn, lp["mlp"], cfg, policy, rules=rules, impl=impl)
    x = x + ff
    if rules is not None:
        x = rules.act(x, "batch", "seq", None)
    return x, aux, new_kv


def _stack_init(key, cfg, dtype, n, init_one):
    """Initialize n layers and stack leaves along a leading axis."""
    keys = jax.random.split(key, n)
    ps = [init_one(k) for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *ps)


def build_dense(cfg: ModelConfig) -> ModelApi:
    policy = get_policy(cfg.policy_name)
    dtype = policy.compute_dtype

    def init(key):
        k1, k2, k3 = jax.random.split(key, 3)
        p = _init_embed(k1, cfg, dtype)
        p["layers"] = _stack_init(
            k2, cfg, dtype, cfg.n_layers,
            lambda k: _init_decoder_layer(k, cfg, dtype))
        p["final_norm"] = L.init_norm(cfg, dtype)
        if cfg.family == "vlm":
            p["patch_proj"] = jax.random.normal(
                k3, (cfg.frontend_dim, cfg.d_model), dtype) * (
                    cfg.frontend_dim ** -0.5)
        return p

    def apply(params, tokens, aux=None, *, rules=None, impl="auto",
              remat=False, policy_=None):
        pol = policy_ or policy
        x = _embed(params, tokens, cfg, rules)
        if cfg.family == "vlm" and aux is not None and "patches" in aux:
            pe = linear(aux["patches"], params["patch_proj"], policy=pol,
                        impl=impl, quantized=False)
            x = jnp.concatenate([pe.astype(x.dtype), x], axis=1)
        b, s, _ = x.shape
        positions = jnp.arange(s)

        def body(carry, lp):
            x, aux_acc = carry
            x, aux, _ = _decoder_layer(x, lp, cfg, pol, positions=positions,
                                       rules=rules, impl=impl)
            return (x, aux_acc + aux), None

        body_fn = jax.checkpoint(body) if remat else body
        (x, aux_loss), _ = jax.lax.scan(body_fn, (x, jnp.zeros((), jnp.float32)),
                                        params["layers"])
        if cfg.family == "vlm" and aux is not None and "patches" in aux:
            x = x[:, -tokens.shape[1]:]
        return _head(params, x, cfg, pol, rules, impl), aux_loss

    def init_cache(batch, max_len, *, paged=None, page_size=16):
        """paged=None -> paged pool iff the policy has a packed cache
        format for this head dim; True forces paging (carrier pages
        when packing doesn't apply — the bf16 fallback); False keeps
        the contiguous carrier strip."""
        from ..serve import kv_cache as KV
        if paged is None:
            paged = KV.paged_kv_applicable(cfg, policy)
        if paged:
            kv, pt, lens = KV.init_paged_kv(cfg, policy, batch, max_len,
                                            page_size=page_size, dtype=dtype)
            stacked = jax.tree.map(lambda v: jnp.broadcast_to(
                v, (cfg.n_layers,) + v.shape).copy(), kv)
            return {"kv": stacked, "pt": pt, "lens": lens}
        kv = L.init_kv_cache(cfg, batch, max_len, dtype)
        return {"kv": jax.tree.map(
            lambda v: jnp.broadcast_to(v, (cfg.n_layers,) + v.shape).copy()
            if v.ndim else jnp.zeros((cfg.n_layers,), v.dtype), kv)}

    def decode_step(params, tok, cache, *, rules=None, impl="auto"):
        tok2 = tok if tok.ndim == 2 else tok[:, None]
        s = tok2.shape[1]
        x = _embed(params, tok2, cfg, rules)
        if "pt" in cache:
            pt, lens = cache["pt"], cache["lens"]
            positions = lens[:, None] + jnp.arange(s)  # [B, S] per-seq

            def body(carry, inp):
                x, _ = carry
                lp, kvc = inp
                x, aux, new_kv = _decoder_layer(
                    x, lp, cfg, policy, positions=positions,
                    kv_cache={"kv": kvc, "pt": pt, "lens": lens},
                    rules=rules, impl=impl)
                return (x, aux), new_kv

            (x, _), new_kv = jax.lax.scan(
                body, (x, jnp.zeros((), jnp.float32)),
                (params["layers"], cache["kv"]))
            new_cache = {"kv": new_kv, "pt": pt, "lens": lens + s}
        else:
            idx = cache["kv"]["idx"][0]
            positions = jnp.arange(s) + idx

            def body(carry, inp):
                x, _ = carry
                lp, kvc = inp
                x, aux, new_kv = _decoder_layer(
                    x, lp, cfg, policy, positions=positions, kv_cache=kvc,
                    rules=rules, impl=impl)
                return (x, aux), new_kv

            (x, _), new_kv = jax.lax.scan(
                body, (x, jnp.zeros((), jnp.float32)),
                (params["layers"], cache["kv"]))
            new_cache = {"kv": new_kv}
        logits = _head(params, x, cfg, policy, rules, impl)
        return (logits if tok.ndim == 2 else logits[:, 0]), new_cache

    return ModelApi(cfg, init, apply, init_cache, decode_step,
                    block_decode=True)


# ---------------------------------------------------------------------------
# encoder-decoder (whisper): stubbed frame embeddings -> encoder -> decoder
# ---------------------------------------------------------------------------

def build_encdec(cfg: ModelConfig) -> ModelApi:
    policy = get_policy(cfg.policy_name)
    dtype = policy.compute_dtype

    def _init_enc_layer(key):
        ks = jax.random.split(key, 2)
        return {
            "norm1": L.init_norm(cfg, dtype),
            "attn": L.init_attention(ks[0], cfg, dtype),
            "norm2": L.init_norm(cfg, dtype),
            "mlp": L.init_mlp(ks[1], cfg, dtype),
        }

    def init(key):
        ks = jax.random.split(key, 6)
        p = _init_embed(ks[0], cfg, dtype)
        p["frame_proj"] = jax.random.normal(
            ks[1], (cfg.d_model, cfg.d_model), dtype) * (cfg.d_model ** -0.5)
        p["enc_pos"] = jax.random.normal(
            ks[2], (cfg.enc_seq, cfg.d_model), dtype) * 0.02
        # sized for the largest assigned decode context (decode_32k)
        p["dec_pos"] = jax.random.normal(
            ks[3], (32768, cfg.d_model), dtype) * 0.02
        p["enc_layers"] = _stack_init(ks[4], cfg, dtype, cfg.n_enc_layers,
                                      _init_enc_layer)
        p["layers"] = _stack_init(
            ks[5], cfg, dtype, cfg.n_layers,
            lambda k: _init_decoder_layer(k, cfg, dtype, cross_attn=True))
        p["final_norm"] = L.init_norm(cfg, dtype)
        p["enc_norm"] = L.init_norm(cfg, dtype)
        return p

    def encode(params, frames, rules, impl):
        x = linear(frames, params["frame_proj"], policy=policy, impl=impl,
                   quantized=False)
        x = x + params["enc_pos"][None, :x.shape[1]].astype(x.dtype)
        positions = jnp.arange(x.shape[1])

        def body(x, lp):
            h, _ = L.attention(L.apply_norm(x, lp["norm1"], cfg), lp["attn"],
                               cfg, policy, positions=positions, causal=False,
                               rules=rules, impl=impl)
            x = x + h
            x = x + L.mlp(L.apply_norm(x, lp["norm2"], cfg), lp["mlp"], cfg,
                          policy, rules=rules, impl=impl)
            return x, None

        x, _ = jax.lax.scan(body, x, params["enc_layers"])
        return L.apply_norm(x, params["enc_norm"], cfg)

    def _cross_kv(params, enc_out, impl, rules):
        """Precompute K,V of the encoder output for every decoder layer."""
        b, t, _ = enc_out.shape
        hd = cfg.head_dim_eff

        def per_layer(lp):
            k = linear(enc_out, lp["xattn"]["wk"], policy=policy, impl=impl)
            v = linear(enc_out, lp["xattn"]["wv"], policy=policy, impl=impl)
            return (k.reshape(b, t, cfg.n_kv_heads, hd),
                    v.reshape(b, t, cfg.n_kv_heads, hd))

        return jax.vmap(per_layer)(params["layers"])

    def apply(params, tokens, aux=None, *, rules=None, impl="auto",
              remat=False, policy_=None):
        pol = policy_ or policy
        frames = aux["frames"]
        enc_out = encode(params, frames, rules, impl)
        ckv = _cross_kv(params, enc_out, impl, rules)
        x = _embed(params, tokens, cfg, rules)
        x = x + params["dec_pos"][None, :x.shape[1]].astype(x.dtype)
        positions = jnp.arange(x.shape[1])

        def body(carry, inp):
            x, aux_acc = carry
            lp, kv = inp
            x, aux_l, _ = _decoder_layer(x, lp, cfg, pol, positions=positions,
                                         cross_kv=kv, rules=rules, impl=impl)
            return (x, aux_acc + aux_l), None

        body_fn = jax.checkpoint(body) if remat else body
        (x, aux_loss), _ = jax.lax.scan(
            body_fn, (x, jnp.zeros((), jnp.float32)), (params["layers"], ckv))
        return _head(params, x, cfg, pol, rules, impl), aux_loss

    def init_cache(batch, max_len):
        kv = L.init_kv_cache(cfg, batch, max_len, dtype)
        hd = cfg.head_dim_eff
        stack = lambda v: (jnp.broadcast_to(
            v, (cfg.n_layers,) + v.shape).copy() if v.ndim
            else jnp.zeros((cfg.n_layers,), v.dtype))
        return {
            "kv": jax.tree.map(stack, kv),
            "cross": (
                jnp.zeros((cfg.n_layers, batch, cfg.enc_seq,
                           cfg.n_kv_heads, hd), dtype),
                jnp.zeros((cfg.n_layers, batch, cfg.enc_seq,
                           cfg.n_kv_heads, hd), dtype)),
        }

    def prefill_cache(params, frames, cache, *, rules=None, impl="auto"):
        enc_out = encode(params, frames, rules, impl)
        ck, cv = _cross_kv(params, enc_out, impl, rules)
        return {**cache, "cross": (ck.astype(dtype), cv.astype(dtype))}

    def decode_step(params, tok, cache, *, rules=None, impl="auto"):
        tok2 = tok if tok.ndim == 2 else tok[:, None]
        s = tok2.shape[1]
        x = _embed(params, tok2, cfg, rules)
        idx = cache["kv"]["idx"][0]
        x = x + jax.lax.dynamic_slice_in_dim(
            params["dec_pos"], idx, s, 0)[None].astype(x.dtype)
        positions = jnp.arange(s) + idx

        def body(carry, inp):
            x, _ = carry
            lp, kvc, ck, cv = inp
            x, aux, new_kv = _decoder_layer(
                x, lp, cfg, policy, positions=positions, kv_cache=kvc,
                cross_kv=(ck, cv), rules=rules, impl=impl)
            return (x, aux), new_kv

        (x, _), new_kv = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)),
            (params["layers"], cache["kv"],
             cache["cross"][0], cache["cross"][1]))
        logits = _head(params, x, cfg, policy, rules, impl)
        return (logits if tok.ndim == 2 else logits[:, 0]), {**cache,
                                                             "kv": new_kv}

    api = ModelApi(cfg, init, apply, init_cache, decode_step,
                   block_decode=True)
    api.prefill_cache = prefill_cache
    return api


# ---------------------------------------------------------------------------
# xLSTM: groups of (slstm_every-1) mLSTM blocks + 1 sLSTM block
# ---------------------------------------------------------------------------

def build_xlstm(cfg: ModelConfig) -> ModelApi:
    policy = get_policy(cfg.policy_name)
    dtype = policy.compute_dtype
    per = max(cfg.slstm_every, 1)
    n_groups = cfg.n_layers // per
    n_m = per - 1  # mLSTM layers per group

    def init(key):
        ks = jax.random.split(key, 4)
        p = _init_embed(ks[0], cfg, dtype)

        def group_init(k):
            k1, k2 = jax.random.split(k)
            g = {"slstm": XL.init_slstm(k2, cfg, dtype),
                 "snorm": L.init_norm(cfg, dtype)}
            if n_m:
                g["mlstm"] = _stack_init(
                    k1, cfg, dtype, n_m, lambda kk: {
                        "blk": XL.init_mlstm(kk, cfg, dtype),
                        "norm": L.init_norm(cfg, dtype)})
            return g

        p["groups"] = _stack_init(ks[1], cfg, dtype, n_groups, group_init)
        p["final_norm"] = L.init_norm(cfg, dtype)
        return p

    def _group_fwd(x, gp, pol, caches, rules, impl):
        new_m, new_s = None, None
        if n_m:
            def mbody(carry, inp):
                x = carry
                lp, mc = inp
                h, nc = XL.mlstm_block(
                    L.apply_norm(x, lp["norm"], cfg), lp["blk"], cfg, pol,
                    cache=mc, rules=rules, impl=impl)
                return x + h, nc
            x, new_m = jax.lax.scan(
                mbody, x, (gp["mlstm"],
                           None if caches is None else caches["m"]))
        h, new_s = XL.slstm_block(L.apply_norm(x, gp["snorm"], cfg),
                                  gp["slstm"], cfg, pol,
                                  cache=None if caches is None else caches["s"],
                                  rules=rules, impl=impl)
        return x + h, {"m": new_m, "s": new_s}

    def apply(params, tokens, aux=None, *, rules=None, impl="auto",
              remat=False, policy_=None):
        pol = policy_ or policy
        x = _embed(params, tokens, cfg, rules)

        def body(carry, gp):
            x, acc = carry
            x, _ = _group_fwd(x, gp, pol, None, rules, impl)
            return (x, acc), None

        body_fn = jax.checkpoint(body) if remat else body
        (x, _), _ = jax.lax.scan(body_fn, (x, jnp.zeros((), jnp.float32)),
                                 params["groups"])
        return (_head(params, x, cfg, pol, rules, impl),
                jnp.zeros((), jnp.float32))

    def init_cache(batch, max_len):
        mc = XL.init_mlstm_cache(cfg, batch)
        sc = XL.init_slstm_cache(cfg, batch)
        stack = lambda t, n: jax.tree.map(
            lambda v: jnp.broadcast_to(v, n + v.shape).copy(), t)
        return {"groups": {"m": stack(mc, (n_groups, n_m)) if n_m else None,
                           "s": stack(sc, (n_groups,))}}

    def decode_step(params, tok, cache, *, rules=None, impl="auto"):
        x = _embed(params, tok[:, None], cfg, rules)

        def body(carry, inp):
            x = carry
            gp, gc = inp
            x, nc = _group_fwd(x, gp, policy, gc, rules, impl)
            return x, nc

        gc = {"m": cache["groups"]["m"], "s": cache["groups"]["s"]}
        x, ncache = jax.lax.scan(body, x, (params["groups"], gc))
        logits = _head(params, x, cfg, policy, rules, impl)
        return logits[:, 0], {"groups": ncache}

    return ModelApi(cfg, init, apply, init_cache, decode_step)


# ---------------------------------------------------------------------------
# zamba2 hybrid: groups of ``attn_every`` Mamba2 blocks + one *shared*
# attention/MLP block applied after each group (shared weights, per-group
# KV caches)
# ---------------------------------------------------------------------------

def build_hybrid(cfg: ModelConfig) -> ModelApi:
    policy = get_policy(cfg.policy_name)
    dtype = policy.compute_dtype
    per = max(cfg.attn_every, 1)
    n_groups = cfg.n_layers // per
    n_tail = cfg.n_layers - n_groups * per   # e.g. zamba2: 81 = 13*6 + 3

    def init(key):
        ks = jax.random.split(key, 5)
        p = _init_embed(ks[0], cfg, dtype)
        p["groups"] = _stack_init(
            ks[1], cfg, dtype, n_groups,
            lambda k: {"mamba": _stack_init(
                k, cfg, dtype, per, lambda kk: {
                    "blk": M2.init_mamba2(kk, cfg, dtype),
                    "norm": L.init_norm(cfg, dtype)})})
        if n_tail:
            p["tail"] = _stack_init(
                ks[4], cfg, dtype, n_tail, lambda kk: {
                    "blk": M2.init_mamba2(kk, cfg, dtype),
                    "norm": L.init_norm(cfg, dtype)})
        # the shared attention block (one set of weights)
        p["shared"] = {
            "norm1": L.init_norm(cfg, dtype),
            "attn": L.init_attention(ks[2], cfg, dtype),
            "norm2": L.init_norm(cfg, dtype),
            "mlp": L.init_mlp(ks[3], cfg, dtype),
        }
        p["final_norm"] = L.init_norm(cfg, dtype)
        return p

    def _mamba_stack(x, stacked, pol, caches, rules, impl):
        def mbody(carry, inp):
            x = carry
            lp, mc = inp
            h, nc = M2.mamba2_block(
                L.apply_norm(x, lp["norm"], cfg), lp["blk"], cfg, pol,
                cache=mc, rules=rules, impl=impl)
            return x + h, nc

        return jax.lax.scan(mbody, x, (stacked, caches))

    def _group_fwd(x, gp, shared, pol, positions, caches, rules, impl):
        x, new_m = _mamba_stack(
            x, gp["mamba"], pol, None if caches is None else caches["m"],
            rules, impl)
        h, new_kv = L.attention(L.apply_norm(x, shared["norm1"], cfg),
                                shared["attn"], cfg, pol, positions=positions,
                                kv_cache=None if caches is None else caches["kv"],
                                rules=rules, impl=impl)
        x = x + h
        x = x + L.mlp(L.apply_norm(x, shared["norm2"], cfg), shared["mlp"],
                      cfg, pol, rules=rules, impl=impl)
        return x, {"m": new_m, "kv": new_kv}

    def apply(params, tokens, aux=None, *, rules=None, impl="auto",
              remat=False, policy_=None):
        pol = policy_ or policy
        x = _embed(params, tokens, cfg, rules)
        positions = jnp.arange(x.shape[1])

        def body(carry, gp):
            x, acc = carry
            x, _ = _group_fwd(x, gp, params["shared"], pol, positions, None,
                              rules, impl)
            return (x, acc), None

        body_fn = jax.checkpoint(body) if remat else body
        (x, _), _ = jax.lax.scan(body_fn, (x, jnp.zeros((), jnp.float32)),
                                 params["groups"])
        if n_tail:
            x, _ = _mamba_stack(x, params["tail"], pol, None, rules, impl)
        return (_head(params, x, cfg, pol, rules, impl),
                jnp.zeros((), jnp.float32))

    def init_cache(batch, max_len):
        mc = M2.init_mamba2_cache(cfg, batch)
        kv = L.init_kv_cache(cfg, batch, max_len, dtype)
        stack = lambda t, n: jax.tree.map(
            lambda v: (jnp.broadcast_to(v, n + v.shape).copy()
                       if v.ndim else jnp.zeros(n, v.dtype)), t)
        cache = {"groups": {"m": stack(mc, (n_groups, per)),
                            "kv": stack(kv, (n_groups,))}}
        if n_tail:
            cache["tail"] = stack(mc, (n_tail,))
        return cache

    def decode_step(params, tok, cache, *, rules=None, impl="auto"):
        x = _embed(params, tok[:, None], cfg, rules)
        idx = cache["groups"]["kv"]["idx"][0]
        positions = jnp.arange(1) + idx

        def body(carry, inp):
            x = carry
            gp, gc = inp
            x, nc = _group_fwd(x, gp, params["shared"], policy, positions,
                               gc, rules, impl)
            return x, nc

        x, ncache = jax.lax.scan(body, x, (params["groups"],
                                           cache["groups"]))
        new_cache = {"groups": ncache}
        if n_tail:
            x, ntail = _mamba_stack(x, params["tail"], policy,
                                    cache["tail"], rules, impl)
            new_cache["tail"] = ntail
        logits = _head(params, x, cfg, policy, rules, impl)
        return logits[:, 0], new_cache

    return ModelApi(cfg, init, apply, init_cache, decode_step)
