"""Plain reference of ``models/attn_moe.py``'s decoder, in float32
``jax.numpy``, kept with the tests.

RMSNorm (1 + scale), rotate-half rotary embedding, grouped-query attention
with an optional sliding window, then in place of the MLP: a float32 router,
softmax over every expert, the ``k`` largest kept and renormalised, each
expert a SwiGLU MLP, and the shared experts' SwiGLU added for every token.
Every sequence is its own row; every expert runs on every token and the
routing weights pick.  Imports nothing of the program under test.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + scale)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    ang = pos[:, None, None] * theta ** (-jnp.arange(half) / half)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def _swiglu(h, wg, wu, wd):
    g = jnp.einsum("ld,...df->...lf", h, wg, precision=HI)
    u = jnp.einsum("ld,...df->...lf", h, wu, precision=HI)
    return jnp.einsum("...lf,...fd->...ld", jax.nn.silu(g) * u, wd,
                      precision=HI)


def _layer(x, p, theta, window, eps, top_k):
    L = x.shape[0]
    pos = jnp.arange(L, dtype=jnp.float32)
    h = _norm(x, p["norm_attn"]["scale"], eps)
    a = p["attn"]
    q = _rope(jnp.einsum("ld,dhk->lhk", h, a["wq"], precision=HI), pos, theta)
    k = _rope(jnp.einsum("ld,dhk->lhk", h, a["wk"], precision=HI), pos, theta)
    v = jnp.einsum("ld,dhk->lhk", h, a["wv"], precision=HI)
    G = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, G, axis=1), jnp.repeat(v, G, axis=1)
    s = jnp.einsum("qhk,shk->hqs", q, k, precision=HI) * q.shape[-1] ** -0.5
    i, j = jnp.arange(L)[:, None], jnp.arange(L)[None, :]
    mask = j <= i if window is None else (j <= i) & (i - j < window)
    s = jnp.where(mask, s, -jnp.inf)
    o = jnp.einsum("hqs,shk->qhk", jax.nn.softmax(s, -1), v, precision=HI)
    x = x + jnp.einsum("lhk,hkd->ld", o, a["wo"], precision=HI)

    h = _norm(x, p["norm_mlp"]["scale"], eps)
    m = p["moe"]
    probs = jax.nn.softmax(jnp.einsum("ld,de->le", h, m["router"],
                                      precision=HI), -1)
    top, idx = jax.lax.top_k(probs, top_k)
    top = top / top.sum(-1, keepdims=True)
    weight = jnp.zeros_like(probs).at[jnp.arange(L)[:, None], idx].set(top)
    experts = _swiglu(h, m["w_gate"], m["w_up"], m["w_down"])   # (E, L, d)
    sh = m["shared"]
    return (x + jnp.einsum("le,eld->ld", weight, experts, precision=HI)
            + _swiglu(h, sh["w_gate"], sh["w_up"], sh["w_down"]))


@functools.partial(jax.jit, static_argnames=("theta", "window", "eps",
                                             "top_k"))
def _hidden(params, toks, *, theta, window, eps, top_k):
    """The final norm's output at every position of one row."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    seg = p["segments"][0][0]
    x = p["embed"]["table"][toks]
    for i in range(jax.tree.leaves(seg)[0].shape[0]):
        x = _layer(x, jax.tree.map(lambda a: a[i], seg), theta, window, eps,
                   top_k)
    return _norm(x, p["final_norm"]["scale"], eps)


def logits(params, arch: dict, seqs, want, *, quant: str | None = None):
    """Per sequence, the float32 logits predicting the token after each
    position in ``want[b]``.  Rows are padded at their end to a multiple of
    64 tokens, which causal attention keeps from the positions before."""
    if quant is not None:
        raise ValueError("this reference has no control path")
    kw = dict(theta=float(arch["rope_theta"]),
              window=arch.get("sliding_window"),
              eps=float(arch["rms_norm_eps"]),
              top_k=int(arch["num_experts_per_tok"]))
    table = params["head"]["table"].astype(jnp.float32)
    out = []
    for s, w in zip(seqs, want):
        toks = np.zeros(-(-len(s) // 64) * 64, np.int32)
        toks[:len(s)] = s
        h = _hidden(params, jnp.asarray(toks), **kw)[np.asarray(w)]
        out.append(np.asarray(jnp.einsum("nd,vd->nv", h, table,
                                         precision=HI)))
    return out
