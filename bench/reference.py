"""The plain reference: a dense decoder transformer in float32 ``jax.numpy``.

Written from the published architecture (pre-norm decoder; LayerNorm or
RMSNorm; rotary embeddings on the whole head, rotate-half convention;
softmax attention; SwiGLU MLP; untied head), with no kernel, cache,
batching or quantization, every product at ``Precision.HIGHEST``.  It
imports nothing of the program.  It takes the harness's weights (made
from the seed in ``harness.make_weights``), whose tree the names below
read, upcast to float32.

It runs after the measured window, in blocks that fit one chip: the
training loss remats each layer and chunks attention and the loss over
query rows; the serving forward runs layer by layer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def norm(x, p, conf):
    if conf["norm_type"] == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + conf["layer_norm_eps"]) \
            * p["scale"] + p["bias"]
    var = jnp.mean(x * x, -1, keepdims=True)
    return x * jax.lax.rsqrt(var + conf["rms_norm_eps"]) * p["scale"]


def rope(x, pos, theta):
    """x [T, H, hd], pos [T]: rotate-half rotary over the whole head."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _proj(x, w, b=None):
    y = jnp.einsum("td,dn->tn", x, w, precision=HI)
    return y if b is None else y + b


def _attend(q, k, v, q0, chunk):
    """Causal softmax attention of q rows ``q0..q0+C-1`` (one chunk)."""
    hd = q.shape[-1]
    s = jnp.einsum("chd,thd->hct", q, k, precision=HI) * hd ** -0.5
    qpos = q0 + jnp.arange(q.shape[0])
    mask = jnp.arange(k.shape[0])[None, :] <= qpos[:, None]
    s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("hct,thd->chd", p, v, precision=HI)


def layer(x, lp, conf, *, chunk: int):
    """One decoder layer on one sequence, x [T, D] float32."""
    t, _ = x.shape
    h, kvh = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf["hidden_size"] // h
    a = lp["attn"]
    xn = norm(x, lp["norm1"], conf)
    pos = jnp.arange(t)
    q = rope(_proj(xn, a["wq"], a.get("bq")).reshape(t, h, hd), pos,
             conf["rope_theta"])
    k = rope(_proj(xn, a["wk"], a.get("bk")).reshape(t, kvh, hd), pos,
             conf["rope_theta"])
    v = _proj(xn, a["wv"], a.get("bv")).reshape(t, kvh, hd)
    k, v = jnp.repeat(k, h // kvh, 1), jnp.repeat(v, h // kvh, 1)
    if t <= chunk:
        o = _attend(q, k, v, 0, chunk)
    else:
        body = jax.checkpoint(lambda qc, q0: _attend(qc, k, v, q0, chunk))
        qs = q.reshape(t // chunk, chunk, h, hd)
        o = jax.lax.map(lambda a_: body(*a_),
                        (qs, jnp.arange(0, t, chunk)))
        o = o.reshape(t, h, hd)
    x = x + _proj(o.reshape(t, h * hd), a["wo"])
    m = lp["mlp"]
    xn = norm(x, lp["norm2"], conf)
    g = _proj(xn, m["w_gate"])
    u = _proj(xn, m["w_up"])
    return x + _proj(jax.nn.silu(g) * u, m["w_down"])


# ------------------------------------------------------------ training --

def loss(params, tokens, conf, *, chunk: int = 512):
    """Mean next-token cross-entropy over ``tokens [B, S]``."""
    p = _f32(params)

    def one(seq):
        x = p["embed"][seq]
        body = jax.checkpoint(lambda x, lp: (layer(x, lp, conf,
                                                   chunk=chunk), None))
        x, _ = jax.lax.scan(body, x, p["layers"])
        x = norm(x, p["final_norm"], conf)

        def ce(xc, tc):
            lg = jnp.einsum("td,dv->tv", xc, p["lm_head"], precision=HI)
            lse = jax.nn.logsumexp(lg, -1)
            pick = jnp.take_along_axis(lg, tc[:, None], -1)[:, 0]
            return jnp.sum(lse - pick)

        ce = jax.checkpoint(ce)
        s = seq.shape[0]
        xs = x[:-1]
        ts = seq[1:]
        n = (s - 1) // chunk * chunk
        tot = jnp.sum(jax.lax.map(lambda a: ce(*a), (
            xs[:n].reshape(-1, chunk, xs.shape[-1]),
            ts[:n].reshape(-1, chunk))))
        if n < s - 1:
            tot = tot + ce(xs[n:], ts[n:])
        return tot

    tots = jax.lax.map(one, tokens)
    return jnp.sum(tots) / (tokens.shape[0] * (tokens.shape[1] - 1))


def adamw_step(params, m, v, step, grads, opt):
    """AdamW as the workload states it: global-norm clip, linear warmup
    then cosine decay, decoupled weight decay; all float32."""
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    clip = jnp.minimum(1.0, opt["grad_clip"] / (gn + 1e-9))
    grads = jax.tree.map(lambda g: g * clip, grads)
    t = step.astype(jnp.float32)
    warm = jnp.minimum(1.0, (t + 1) / max(opt["warmup_steps"], 1))
    frac = jnp.clip((t - opt["warmup_steps"]) / max(
        opt["total_steps"] - opt["warmup_steps"], 1), 0.0, 1.0)
    lr = opt["lr"] * warm * 0.5 * (1 + jnp.cos(jnp.pi * frac))
    b1, b2 = opt["b1"], opt["b2"]
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)
    c1, c2 = 1 - b1 ** (t + 1), 1 - b2 ** (t + 1)
    params = jax.tree.map(
        lambda p, m_, v_: p - lr * ((m_ / c1) / (jnp.sqrt(v_ / c2)
                                                 + opt["eps"])
                                    + opt["weight_decay"] * p),
        params, m, v)
    return params, m, v, grads


def train_step_fn(conf, opt, norms_fn):
    """Jitted reference step: ``(params, m, v, step, tokens) -> (params,
    m, v, loss, per-leaf norms of the clipped gradient, the clipped
    gradient of the head)``."""

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, m, v, t, tokens):
        lval, grads = jax.value_and_grad(loss)(params, tokens, conf)
        params, m, v, gclip = adamw_step(params, m, v, t, grads, opt)
        return params, m, v, lval, norms_fn(gclip), gclip["lm_head"]

    return step


# ------------------------------------------------------------- serving --

@functools.partial(jax.jit, static_argnames=("conf_items",))
def _layer_fwd(x, lp, conf_items):
    conf = dict(conf_items)
    return layer(x, _f32(lp), conf, chunk=x.shape[0])


@functools.partial(jax.jit, static_argnames=("conf_norm",))
def _head(x, final_norm, lm_head, served, other, conf_norm):
    conf = dict(conf_norm)
    xn = norm(x, _f32(final_norm), conf)
    lg = jnp.einsum("td,dv->tv", xn, lm_head.astype(jnp.float32),
                    precision=HI)
    best = jnp.max(lg, -1)
    pick = lambda ids: jnp.take_along_axis(lg, ids[:, None], -1)[:, 0]
    return best, pick(served), pick(other)


def serve_logit_gaps(params, conf, seq, served_pos, served, other,
                     bucket: int = 512):
    """Per served position of one sequence: the reference's best logit
    minus its logit of the served token, and minus its logit of the
    ``other`` token (a control's pick).  ``seq`` is prompt + served
    tokens; ``served_pos[i]`` is the position whose logits chose
    ``served[i]``.  The sequence is padded to a multiple of ``bucket``
    (causal, so padding changes no earlier row) and run layer by layer."""
    import numpy as np
    t = len(seq)
    tp = -(-t // bucket) * bucket
    toks = np.zeros(tp, np.int32)
    toks[:t] = seq
    items = tuple(sorted((k, v) for k, v in conf.items()
                         if not isinstance(v, (dict, list))))
    x = params["embed"][jnp.asarray(toks)].astype(jnp.float32)
    n_layers = jax.tree.leaves(params["layers"])[0].shape[0]
    for i in range(n_layers):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        x = _layer_fwd(x, lp, items)
    n = len(served)
    npad = -(-n // bucket) * bucket

    def padded(a):
        out = np.zeros(npad, np.int32)
        out[:n] = a
        return jnp.asarray(out)

    nitems = tuple((k, v) for k, v in items
                   if k in ("norm_type", "layer_norm_eps", "rms_norm_eps"))
    best, got, alt = _head(x[padded(served_pos)], params["final_norm"],
                           params["lm_head"], padded(served), padded(other),
                           conf_norm=nitems)
    best, got, alt = (np.asarray(a, np.float64)[:n] for a in (best, got, alt))
    return best - got, best - alt
